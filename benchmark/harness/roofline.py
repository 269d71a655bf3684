"""Peaks of the chip, and the bytes a query class has to read.

The least time of a query class is the bytes its programs have to
bring from HBM once, over the chip's peak HBM rate: for each table, the
LIVE rows its scan delivers (not padded capacity) times the SQL widths
of the lanes it delivers. The scan delivers what the conjuncts the
planner pushes into it leave, compacted and kept resident per
constraint, so the programs of a class never see the other rows: the
configuration's ``scan_rows`` has that count per class and table
(``reference/pins.py`` computes it), ``lanes_read`` the lanes. The
count is the same whatever program reads them: a Pallas kernel, XLA, or
a later kernel.
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
    """Bytes the class must read once: for each table it scans, the rows
    the scan delivers (``scan_rows``; a configuration without the key
    counts the table's live rows) times the summed widths of the lanes
    it reads."""
    delivered = config.get("scan_rows", {}).get(cls, {})
    total = 0
    for table, lanes in config["lanes_read"][cls].items():
        rows = int(delivered[table]["rows"] if table in delivered
                   else config["tables"][table]["rows"])
        total += rows * sum(int(config["lane_bytes"][lane])
                            for lane in lanes)
    return total


def least_seconds(config: dict, cls: str, peaks: dict) -> float:
    return query_bytes(config, cls) / (float(peaks["hbm_gb_per_s"]) * 1e9)
