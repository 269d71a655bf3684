"""Exchange: how unevenly the chips are loaded — the busiest device
plane's busy time inside the traced window over the planes' mean, minus
one, in %. 0 is an even mesh; a hot key or an uneven repartition shows
here. ``None`` with fewer than two device planes."""

from harness import trace as tr


def read(run):
    if run.trace is None or len(run.trace["devices"]) < 2:
        return None
    lo, hi = run.trace_window
    busy = [b.inside(lo, hi) for b in tr.planes(run.trace).values()]
    mean = sum(busy) / len(busy)
    if mean <= 0:
        return None
    return 100.0 * (max(busy) / mean - 1.0)
