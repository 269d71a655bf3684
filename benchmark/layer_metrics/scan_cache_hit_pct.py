"""Scan cache: hits over lookups of ``trino_tpu_scan_cache_total``,
growth across the window."""

NAME = "trino_tpu_scan_cache_total"


def read(run):
    hits = lookups = 0.0
    for key, after in run.engine_after.items():
        if key.startswith(NAME + "{"):
            grown = after - run.engine_before.get(key, 0.0)
            lookups += grown
            if 'result="hit"' in key:
                hits += grown
    if lookups <= 0:
        return None
    return 100.0 * hits / lookups
