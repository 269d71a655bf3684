"""95th percentile of all client latencies of the window. Listed for the
cells whose window holds hundreds of queries; the count is on the run's
``window`` line."""

from harness import stats


def read(run):
    return stats.percentile_ms(run.records, 95)
