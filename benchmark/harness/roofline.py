"""Peaks of the chip, and the bytes a query class has to read.

The least time of a query class is the bytes of the lanes it reads,
LIVE rows (not padded capacity) times each lane's SQL width, over the
chip's peak HBM rate. It counts the work of the QUERY, whatever program
does it: the same number for a Pallas kernel, for XLA, or for a later
kernel. The per-lane table sits in the configuration's file.
"""

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json: add it with its source")
    return table[device_kind]


def query_bytes(config: dict, cls: str) -> int:
    """Bytes the class must read once: for each table it scans, that
    table's live rows times the summed widths of the lanes it reads."""
    total = 0
    for table, lanes in config["lanes_read"][cls].items():
        rows = int(config["tables"][table]["rows"])
        total += rows * sum(int(config["lane_bytes"][lane])
                            for lane in lanes)
    return total


def least_seconds(config: dict, cls: str, peaks: dict) -> float:
    return query_bytes(config, cls) / (float(peaks["hbm_gb_per_s"]) * 1e9)
