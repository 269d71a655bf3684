"""Finished queries per second of the window, summed over streams."""

from harness import stats


def read(run):
    return stats.rate_per_s(run.records, run.t0)
