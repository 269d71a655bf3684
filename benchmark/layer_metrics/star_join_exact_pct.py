"""Share of the window's join probes that took the exact directory
(no search): growth of ``trino_tpu_join_exact_probes_total`` over that
of ``trino_tpu_join_probes_total``, %. A build side's directory is
exact where its key is one integer column whose values span less than
the directory (32 x build capacity, at most 2^26): the surrogate keys
of a star schema's dimensions. A program without the counters (one
older than the directory) gives ``None``."""

from ._phases import family_growth


def read(run):
    probes = family_growth(run, "trino_tpu_join_probes_total")
    exact = family_growth(run, "trino_tpu_join_exact_probes_total")
    if not probes or exact is None:
        return None
    return 100.0 * exact / probes
