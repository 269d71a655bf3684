"""Median client latency of q1 in the window (SQL sent to last page).
A metric of ONE query class: BENCHMARK.json lists the cells that have
the class under its ``workloads``."""

from harness import stats

CLASS = "q1"


def read(run):
    return stats.class_p50_ms(run.records).get(CLASS)
