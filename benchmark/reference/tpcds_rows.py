"""The rows of this repo's TPC-DS tables, from numpy alone.

The benchmark's own copy of the counter-based generator that
``trino_tpu/connectors/tpcds.py`` defines (the data of a scale factor is
a constant of the program, not of ``--seed``): the eight tables that
TPC-DS Q3, Q7 and Q96 read, and of them only the columns those classes
and the data pins read. It imports nothing of the program, so a PR that
changes the program's generator shows as wrong answers and a changed
data pin, not as a speed-up.

A foreign key of ``store_sales`` is NULL for about 2% of the tickets
(``*_valid`` lanes beside the keys): a NULL key joins nothing.
"""

import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1).toordinal()
_D0 = datetime.date(1900, 1, 2).toordinal()
DATE_SK0 = 2415022          # d_date_sk of 1900-01-02

# row counts of the TPC-DS specification's Table 3-2 at the scale
# factors the benchmark runs (0.01 is the rehearsal's ``tiny``)
ROWS = {
    "store_sales": {0.01: 120527, 1.0: 2880404, 10.0: 28800991},
    "item": {0.01: 2000, 1.0: 18000, 10.0: 102000},
    "customer_demographics": {0.01: 19208, 1.0: 1920800, 10.0: 1920800},
    "store": {0.01: 2, 1.0: 12, 10.0: 102},
    "promotion": {0.01: 30, 1.0: 300, 10.0: 500},
    "household_demographics": 7200,
    "date_dim": 73049,
    "time_dim": 86400,
}

_SEED = {"item": 1248, "promotion": 1279, "store": 1310,
         "store_sales": 1372}

UNITS = "Unknown ought able pri ese anti cally ation eing n st".split()
GENDER = np.array(["M", "F"])
MARITAL = np.array(["M", "S", "D", "W", "U"])
EDUCATION = np.array(["Primary", "Secondary", "College", "2 yr Degree",
                      "4 yr Degree", "Advanced Degree", "Unknown"])
CHANNEL = np.array(["N", "Y"])

_C1 = np.uint64(0xBF58476D1CE4E5B9)
_C2 = np.uint64(0x94D049BB133111EB)
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def table_rows(table: str, sf: float) -> int:
    n = ROWS[table]
    return n if isinstance(n, int) else n[float(sf)]


def _date_sk(y: int, m: int, d: int) -> int:
    return DATE_SK0 + datetime.date(y, m, d).toordinal() - _D0


_SALES_SK_LO = _date_sk(1998, 1, 1)
_SALES_SK_HI = _date_sk(2002, 12, 31)


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


def _uniform(seed: int, idx: np.ndarray) -> np.ndarray:
    return (_u64(seed, idx) >> np.uint64(11)).astype(np.float64) \
        / float(1 << 53)


def _price(seed: int, idx: np.ndarray, lo: float, hi: float) -> np.ndarray:
    return np.round(lo + _uniform(seed, idx) * (hi - lo), 2)


def _keys(table: str, sf: float) -> np.ndarray:
    return np.arange(1, table_rows(table, sf) + 1, dtype=np.int64)


# ---- the dimensions, whole (the largest has 1.9M rows) -----------------
def date_dim(sf: float) -> dict:
    k = _keys("date_dim", sf) - 1
    d64 = (_D0 + k - _EPOCH).astype("datetime64[D]")
    month = d64.astype("datetime64[M]").astype(np.int64)
    return {"d_date_sk": DATE_SK0 + k,
            "d_year": d64.astype("datetime64[Y]").astype(np.int64) + 1970,
            "d_moy": month % 12 + 1}


def item(sf: float) -> dict:
    idx = _keys("item", sf)
    s = _SEED["item"]
    return {"i_item_sk": idx,
            "i_brand_id": _randint(s + 6, idx, 1, 1000),
            "i_manufact_id": _randint(s + 7, idx, 1, 1000)}


def item_id(sk: int) -> str:
    return f"AAAAAAAA{int(sk):016d}"


def brand(brand_id: int) -> str:
    b = int(brand_id)
    return f"{UNITS[b % 10]}{UNITS[(b // 10) % 10]} #{b}"


def customer_demographics(sf: float) -> dict:
    idx = _keys("customer_demographics", sf)
    k = idx - 1
    return {"cd_demo_sk": idx,
            "cd_gender": GENDER[k % 2],
            "cd_marital_status": MARITAL[k // 2 % 5],
            "cd_education_status": EDUCATION[k // 10 % 7]}


def household_demographics(sf: float) -> dict:
    idx = _keys("household_demographics", sf)
    return {"hd_demo_sk": idx, "hd_dep_count": (idx - 1) // 120 % 10}


def time_dim(sf: float) -> dict:
    t = _keys("time_dim", sf) - 1
    return {"t_time_sk": t, "t_hour": t // 3600, "t_minute": t // 60 % 60}


def store(sf: float) -> dict:
    idx = _keys("store", sf)
    return {"s_store_sk": idx,
            "s_store_name": np.array(UNITS)[(idx - 1) % len(UNITS)]}


def promotion(sf: float) -> dict:
    idx = _keys("promotion", sf)
    s = _SEED["promotion"]
    two = np.uint64(2)
    return {"p_promo_sk": idx,
            "p_channel_email": CHANNEL[(_u64(s + 3, idx) % two)
                                       .astype(np.int64)],
            "p_channel_event": CHANNEL[(_u64(s + 5, idx) % two)
                                       .astype(np.int64)]}


# ---- the fact table, a block of rows at a time ---------------------------
def _fk(seed: int, ticket: np.ndarray, n_ref: int):
    key = 1 + (_u64(seed, ticket) % np.uint64(n_ref)).astype(np.int64)
    return key, _uniform(seed + 7777, ticket) >= 0.02


def store_sales(idx: np.ndarray, sf: float) -> dict:
    """The lanes of the rows ``idx`` (1-based); about twelve rows share
    a ticket, and with it the date, the time and every key but the
    item's."""
    s = _SEED["store_sales"]
    ticket = (idx - 1) // 12 + 1
    out = {"ss_item_sk": 1 + (_u64(s + 2, idx) % np.uint64(
               table_rows("item", sf))).astype(np.int64),
           "ss_sold_date_sk": _randint(s + 3, ticket, _SALES_SK_LO,
                                       _SALES_SK_HI),
           "ss_sold_date_sk_valid": _uniform(s + 103, ticket) >= 0.02,
           "ss_sold_time_sk": _randint(s + 33, ticket, 28800, 75600)}
    for name, ref, k in (("ss_cdemo_sk", "customer_demographics", 5),
                         ("ss_hdemo_sk", "household_demographics", 6),
                         ("ss_store_sk", "store", 8),
                         ("ss_promo_sk", "promotion", 9)):
        out[name], out[name + "_valid"] = _fk(s + k, ticket,
                                              table_rows(ref, sf))
    qty = _randint(s + 10, idx, 1, 100)
    whole = _price(s + 11, idx, 1.0, 100.0)
    lp = np.round(whole * (1.0 + _uniform(s + 12, idx)), 2)
    sp = np.round(lp * (0.2 + 0.8 * _uniform(s + 13, idx)), 2)
    out["ss_quantity"] = qty
    out["ss_list_price"] = lp
    out["ss_sales_price"] = sp
    out["ss_ext_sales_price"] = np.round(sp * qty, 2)
    out["ss_coupon_amt"] = np.where(_uniform(s + 14, idx) < 0.2,
                                    _price(s + 15, idx, 0.0, 500.0), 0.0)
    return out


def store_sales_blocks(sf: float, block: int = 1 << 21):
    n = table_rows("store_sales", sf)
    for lo in range(0, n, block):
        yield store_sales(np.arange(lo + 1, min(lo + block, n) + 1,
                                    dtype=np.int64), sf)
