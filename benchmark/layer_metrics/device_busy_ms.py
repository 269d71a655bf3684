"""Device execution: device-busy time inside the benchmark's own
annotation around each query, mean over the window's queries. Queries
that showed no device work at all read 0, and do not vanish."""

from ._busy import busy_ns


def read(run):
    per = [ns for v in busy_ns(run).values() for ns in v]
    if not per:
        return None
    return sum(per) / len(per) / 1e6
