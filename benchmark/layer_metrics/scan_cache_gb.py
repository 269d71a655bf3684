"""Scan cache: bytes resident at the window's end, GB (1e9): the gauge
``trino_tpu_scan_cache_bytes``, summed over connectors, against the
budget (4 GiB = 4.29 GB by default). Lanes are counted at their padded
capacity, validity lanes too."""

NAME = "trino_tpu_scan_cache_bytes"


def read(run):
    held = [v for k, v in run.engine_after.items()
            if k.startswith(NAME + "{")]
    if not held:
        return None
    return sum(held) / 1e9
