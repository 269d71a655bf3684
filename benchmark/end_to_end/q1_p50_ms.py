"""Median client latency of q1 in the window (SQL sent to last page)."""

from harness import stats


def read(run):
    return stats.class_p50_ms(run.records).get("q1")
