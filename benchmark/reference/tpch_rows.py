"""The rows of this repo's TPC-H tables, from numpy alone.

The benchmark's own copy of the counter-based generator that
``trino_tpu/connectors/tpch.py`` defines (the data of a scale factor is
a constant of the program, not of ``--seed``): only the lanes q1, q3
and q6 read. It imports nothing of the program, so a PR that changes
the program's generator, on the host or on the device, shows as wrong
answers and a changed data pin, not as a speed-up.
"""

import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1).toordinal()


def days(y: int, m: int, d: int) -> int:
    return datetime.date(y, m, d).toordinal() - _EPOCH


STARTDATE = days(1992, 1, 1)
CURRENTDATE = days(1995, 6, 17)
ENDDATE = days(1998, 12, 31)
ORDER_DATE_SPAN = (ENDDATE - 151) - STARTDATE

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
RETURNFLAGS = np.array(["R", "A", "N"])
LINESTATUS = np.array(["F", "O"])

_BASE_ROWS = {"supplier": 10_000, "part": 200_000, "customer": 150_000,
              "orders": 1_500_000}
_SEED = {name: i * 1000 for i, name in enumerate(
    ["supplier", "part", "partsupp", "customer", "orders", "lineitem"])}

_C1 = np.uint64(0xBF58476D1CE4E5B9)
_C2 = np.uint64(0x94D049BB133111EB)
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def table_rows(table: str, sf: float) -> int:
    return int(round(_BASE_ROWS[table] * sf))


def _u64(seed: int, idx: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = np.uint64(seed) * _GOLDEN + idx.astype(np.uint64)
        x = x ^ (x >> np.uint64(30))
        x = x * _C1
        x = x ^ (x >> np.uint64(27))
        x = x * _C2
        x = x ^ (x >> np.uint64(31))
    return x


def _randint(seed: int, idx: np.ndarray, lo: int, hi: int) -> np.ndarray:
    span = np.uint64(hi - lo + 1)
    return lo + (_u64(seed, idx) % span).astype(np.int64)


def order_key(order_idx: np.ndarray) -> np.ndarray:
    i = order_idx.astype(np.int64)
    return ((i >> 3) << 5) | (i & 7)


def order_date(order_idx: np.ndarray) -> np.ndarray:
    return STARTDATE + _randint(_SEED["orders"] + 4, order_idx, 0,
                                ORDER_DATE_SPAN)


def customer(sf: float) -> dict:
    """c_custkey and c_mktsegment of every customer."""
    idx = np.arange(1, table_rows("customer", sf) + 1, dtype=np.int64)
    seg = _randint(_SEED["customer"] + 6, idx, 0, 4)
    return {"c_custkey": idx, "c_mktsegment": np.array(SEGMENTS)[seg]}


def orders(order_idx: np.ndarray, sf: float) -> dict:
    """The four orders lanes q3 reads, for 1-based order indices."""
    c_count = table_rows("customer", sf)
    j = _randint(_SEED["orders"] + 3, order_idx, 1,
                 max(2 * c_count // 3, 1))
    return {"o_orderkey": order_key(order_idx),
            "o_custkey": 3 * ((j - 1) // 2) + 1 + ((j - 1) % 2),
            "o_orderdate": order_date(order_idx),
            "o_shippriority": np.zeros(len(order_idx), np.int64)}


def lineitem(order_idx: np.ndarray, sf: float) -> dict:
    """The lineitem lanes q1, q3 and q6 read, for every line of the
    orders at 1-based ``order_idx`` (1 to 7 lines an order)."""
    S = _SEED["lineitem"]
    counts = _randint(S + 1, order_idx, 1, 7)
    rep = np.repeat(order_idx, counts)
    first = np.cumsum(counts) - counts
    line_no = np.arange(len(rep), dtype=np.int64) - np.repeat(first, counts) + 1
    rid = rep.astype(np.int64) * 8 + line_no
    partkey = _randint(S + 2, rid, 1, table_rows("part", sf))
    retail = (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0
    quantity = _randint(S + 4, rid, 1, 50).astype(np.float64)
    shipdate = order_date(rep) + _randint(S + 7, rid, 1, 121)
    receipt = shipdate + _randint(S + 9, rid, 1, 30)
    ra = (_u64(S + 20, rid) % np.uint64(2)).astype(np.int64)
    return {"l_orderkey": order_key(rep),
            "l_quantity": quantity,
            "l_extendedprice": quantity * retail,
            "l_discount": _randint(S + 5, rid, 0, 10) / 100.0,
            "l_tax": _randint(S + 6, rid, 0, 8) / 100.0,
            "l_shipdate": shipdate,
            "l_returnflag": np.where(receipt <= CURRENTDATE, ra, 2),
            "l_linestatus": (shipdate > CURRENTDATE).astype(np.int64)}
