"""Bisection steps a join probe takes to find a probe row's run of
equal keys in the sorted build side: growth of
``trino_tpu_join_search_steps_total`` over that of
``trino_tpu_join_probes_total`` in the window. 0.0 where the build
side's directory is exact (one integer key column whose values span
less than the directory: both of q3's joins since PR 29); 2-4 over a
hashed bucket directory; 42 on the arithmetic of two full binary
searches over 2^20 rows (2 x 21); log2(build capacity)+1 where one key
fills a bucket. Each step is two probe-sized gathers on the device. A
program without the counters (one older than the directory) gives
``None``."""

from ._phases import family_growth


def read(run):
    probes = family_growth(run, "trino_tpu_join_probes_total")
    steps = family_growth(run, "trino_tpu_join_search_steps_total")
    if not probes or steps is None:
        return None
    return steps / probes
