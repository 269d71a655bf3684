"""Constants of a TPC-H scale factor, from the benchmark's own generator.

Data pins: row counts and one lane's sum per table. A configuration's
file carries them; set-up asks the served tables for the same numbers,
so a PR that changes the data shows as changed data and not as a
speed-up.

Scan rows: per query class and table, the rows that the conjuncts the
program's planner pushes into that table's scan leave (``PUSHED``: every
conjunct that compares a column with a literal; the scan compacts under
them, so its programs never see the other rows). The roofline counts
these rows (``harness/roofline.py``), and ``selfcheck/test_scan_rows.py``
holds them to what the engine's scans deliver.

    python -m benchmark.reference.pins 1.0      # two lines: pins, scan rows
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


# class -> table -> (the pushed conjuncts as the SQL has them, the rows
# they keep); None: nothing is pushed, the scan delivers the table. q1's
# ``l_shipdate <= date - interval``, q6's ``l_shipdate < date + interval``
# and ``l_discount between 0.06 - 0.01 and ...`` compare a column with
# an EXPRESSION and q3's ``c_mktsegment = 'BUILDING'`` goes through a
# cast: the planner pushes none of them, they stay filters of the program.
PUSHED = {
    "q1": {"lineitem": None},
    "q3": {"lineitem": ("l_shipdate > date '1995-03-15'",
                        lambda t: t["l_shipdate"] > rows.days(1995, 3, 15)),
           "orders": ("o_orderdate < date '1995-03-15'",
                      lambda t: t["o_orderdate"] < rows.days(1995, 3, 15)),
           "customer": None},
    "q6": {"lineitem": ("l_shipdate >= date '1994-01-01' and l_quantity < 24",
                        lambda t: (t["l_shipdate"] >= rows.days(1994, 1, 1))
                        & (t["l_quantity"] < 24))},
}


def _chunks(sf: float, chunk: int):
    """(lineitem lanes, orders lanes) of ``chunk`` orders at a time."""
    n_orders = rows.table_rows("orders", sf)
    for lo in range(0, n_orders, chunk):
        idx = np.arange(lo + 1, min(lo + chunk, n_orders) + 1,
                        dtype=np.int64)
        yield rows.lineitem(idx, sf), rows.orders(idx, sf)


def pins(sf: float, chunk: int = 500_000) -> dict:
    li_rows = li_sum = o_sum = 0
    for li, o in _chunks(sf, chunk):
        li_rows += len(li["l_orderkey"])
        li_sum += int(li["l_orderkey"].sum())
        o_sum += int(o["o_custkey"].sum())
    c = rows.customer(sf)["c_custkey"]
    return {"lineitem": {"rows": li_rows, "pin_sum": li_sum},
            "orders": {"rows": rows.table_rows("orders", sf),
                       "pin_sum": o_sum},
            "customer": {"rows": len(c), "pin_sum": int(c.sum())}}


def scan_rows(sf: float, chunk: int = 500_000) -> dict:
    """{class: {table: {"rows": n, "pushed": text}}} at ``sf``."""
    out = {cls: {t: {"rows": 0, "pushed": p[0] if p else "nothing"}
                 for t, p in tables.items()}
           for cls, tables in PUSHED.items()}

    def fold(table, lanes):
        n = len(next(iter(lanes.values())))
        for cls, tables in PUSHED.items():
            if table in tables:
                p = tables[table]
                out[cls][table]["rows"] += int(p[1](lanes).sum()) if p else n

    for li, o in _chunks(sf, chunk):
        fold("lineitem", li)
        fold("orders", o)
    fold("customer", rows.customer(sf))
    return out


if __name__ == "__main__":
    print(json.dumps(pins(float(sys.argv[1]))))
    print(json.dumps(scan_rows(float(sys.argv[1]))))
