"""Data pins of a scale factor: row counts and one lane's sum per table,
from the benchmark's own generator. A configuration's file carries them;
set-up asks the served tables for the same numbers, so a PR that changes
the data shows as changed data and not as a speed-up.

    python -m benchmark.reference.pins 1.0
"""

import json
import sys

import numpy as np

from . import tpch_rows as rows

PIN_SQL = {
    "lineitem": "select count(*), sum(l_orderkey) from lineitem",
    "orders": "select count(*), sum(o_custkey) from orders",
    "customer": "select count(*), sum(c_custkey) from customer",
}


def pins(sf: float, chunk: int = 500_000) -> dict:
    n_orders = rows.table_rows("orders", sf)
    li_rows = li_sum = o_sum = 0
    for lo in range(0, n_orders, chunk):
        idx = np.arange(lo + 1, min(lo + chunk, n_orders) + 1,
                        dtype=np.int64)
        key = rows.lineitem(idx, sf)["l_orderkey"]
        li_rows += len(key)
        li_sum += int(key.sum())
        o_sum += int(rows.orders(idx, sf)["o_custkey"].sum())
    c = rows.customer(sf)["c_custkey"]
    return {"lineitem": {"rows": li_rows, "pin_sum": li_sum},
            "orders": {"rows": n_orders, "pin_sum": o_sum},
            "customer": {"rows": len(c), "pin_sum": int(c.sum())}}


if __name__ == "__main__":
    print(json.dumps(pins(float(sys.argv[1]))))
