"""The plain reference: TPC-DS Q3, Q7 and Q96 (qualification
substitution parameters) in numpy over ``tpcds_rows``, in the shape the
client returns rows.

Independent of the program: no import of ``trino_tpu``, no table, lane
or dictionary the program made. A star join is done the plain way: each
filtered dimension is a boolean mask addressed by its surrogate key (no
hash table, no sort), the masks are ANDed over the fact rows (a NULL
foreign key joins nothing), and the rows that survive are summed per
group. ``store_sales`` is walked in blocks, so SF 10 needs no more host
memory than a block.

``dtype`` is the precision of the DOUBLE lanes and of every sum and
average over them. The configuration states DOUBLE (float64); the
control of the output check runs the same reference in float32, the
nearest precision below it, and has to come out as not correct: it does
on q3 (sums of prices) and q7 (averages); q96 is a count, exact in any
precision.
"""

import numpy as np

from . import tpcds_rows as rows

# a dimension is pinned on its surrogate key
_PIN_KEY = {"customer_demographics": "cd_demo_sk", "item": "i_item_sk",
            "time_dim": "t_time_sk", "date_dim": "d_date_sk",
            "household_demographics": "hd_demo_sk",
            "promotion": "p_promo_sk", "store": "s_store_sk"}
PIN_SQL = {
    "store_sales": "select count(*), sum(ss_item_sk) + sum(cast(round("
                   "ss_ext_sales_price * 100) as bigint)) from store_sales",
    **{table: f"select count(*), sum({key}) from {table}"
       for table, key in _PIN_KEY.items()}}

Q3_MANUFACT, Q3_MONTH = 128, 11
Q7_GENDER, Q7_MARITAL, Q7_EDUCATION, Q7_YEAR = "M", "S", "College", 2000
Q96_HOUR, Q96_MINUTE, Q96_DEP_COUNT, Q96_STORE = 20, 30, 7, "ese"
LIMIT = 100


def pins(sf: float) -> dict:
    """{table: {"rows", "pin_sum"}} as ``PIN_SQL`` asks the served
    tables: the sum of the surrogate key; for ``store_sales`` the sum
    of ``ss_item_sk`` plus the sum of ``ss_ext_sales_price`` in cents,
    so that the copy is held on a measure and not only on keys."""
    out = {}
    for table, key in _PIN_KEY.items():
        k = getattr(rows, table)(sf)[key]
        out[table] = {"rows": len(k), "pin_sum": int(k.sum())}
    total = 0
    for ss in rows.store_sales_blocks(sf):
        total += int(ss["ss_item_sk"].sum()) + int(
            np.rint(ss["ss_ext_sales_price"] * 100).astype(np.int64).sum())
    out["store_sales"] = {"rows": rows.table_rows("store_sales", sf),
                          "pin_sum": total}
    return out


def _by_key(keys: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """The mask ``keep`` of a dimension's rows, addressed by the
    surrogate key itself."""
    mask = np.zeros(int(keys.max()) + 1, bool)
    mask[keys[keep]] = True
    return mask


def _group_sums(groups: np.ndarray, n: int, values: np.ndarray, dtype):
    out = np.zeros(n, dtype)
    np.add.at(out, groups, values.astype(dtype))
    return out


class Answers:
    """Answers of the classes in ``want`` (``q3``, ``q7``, ``q96``:
    TPC-DS's, not TPC-H's) at scale factor ``sf``; ``answer(name)``
    gives the rows."""

    def __init__(self, sf: float, want, dtype=np.float64):
        self.sf = sf
        self.want = set(want)
        unknown = self.want - {"q3", "q7", "q96"}
        if unknown:
            raise KeyError(f"the reference has no answer for {unknown}")
        self.dtype = np.dtype(dtype).type
        self._q3, self._q7, self._q96 = [], [], 0
        self._masks()
        for ss in rows.store_sales_blocks(sf):
            for cls in sorted(self.want):
                getattr(self, "_fold_" + cls)(ss)

    def _masks(self) -> None:
        sf = self.sf
        d = rows.date_dim(sf)
        self._year = np.zeros(int(d["d_date_sk"].max()) + 1, np.int64)
        self._year[d["d_date_sk"]] = d["d_year"]
        self._november = _by_key(d["d_date_sk"], d["d_moy"] == Q3_MONTH)
        self._year_2000 = _by_key(d["d_date_sk"], d["d_year"] == Q7_YEAR)
        i = self._item = rows.item(sf)
        self._manufact = _by_key(i["i_item_sk"],
                                 i["i_manufact_id"] == Q3_MANUFACT)
        cd = rows.customer_demographics(sf)
        self._cd = _by_key(cd["cd_demo_sk"],
                           (cd["cd_gender"] == Q7_GENDER)
                           & (cd["cd_marital_status"] == Q7_MARITAL)
                           & (cd["cd_education_status"] == Q7_EDUCATION))
        p = rows.promotion(sf)
        self._promo = _by_key(p["p_promo_sk"],
                              (p["p_channel_email"] == "N")
                              | (p["p_channel_event"] == "N"))
        t = rows.time_dim(sf)
        self._time = _by_key(t["t_time_sk"], (t["t_hour"] == Q96_HOUR)
                             & (t["t_minute"] >= Q96_MINUTE))
        hd = rows.household_demographics(sf)
        self._hd = _by_key(hd["hd_demo_sk"],
                           hd["hd_dep_count"] == Q96_DEP_COUNT)
        s = rows.store(sf)
        self._store = _by_key(s["s_store_sk"],
                              s["s_store_name"] == Q96_STORE)

    # every item joins (ss_item_sk is never NULL and item is unfiltered
    # in q7); the other keys join where they are not NULL and the
    # dimension's filter kept their row
    def _fold_q3(self, ss) -> None:
        hit = (ss["ss_sold_date_sk_valid"]
               & self._november[ss["ss_sold_date_sk"]]
               & self._manufact[ss["ss_item_sk"]])
        self._q3.append((self._year[ss["ss_sold_date_sk"][hit]],
                         ss["ss_item_sk"][hit],
                         ss["ss_ext_sales_price"][hit]))

    def _fold_q7(self, ss) -> None:
        hit = (ss["ss_cdemo_sk_valid"] & self._cd[ss["ss_cdemo_sk"]]
               & ss["ss_promo_sk_valid"] & self._promo[ss["ss_promo_sk"]]
               & ss["ss_sold_date_sk_valid"]
               & self._year_2000[ss["ss_sold_date_sk"]])
        self._q7.append([ss[k][hit] for k in (
            "ss_item_sk", "ss_quantity", "ss_list_price", "ss_coupon_amt",
            "ss_sales_price")])

    def _fold_q96(self, ss) -> None:
        hit = (self._time[ss["ss_sold_time_sk"]]
               & ss["ss_hdemo_sk_valid"] & self._hd[ss["ss_hdemo_sk"]]
               & ss["ss_store_sk_valid"] & self._store[ss["ss_store_sk"]])
        self._q96 += int(hit.sum())

    # ---- the answers, in the shape the client returns them -------------
    def q3(self):
        """group by d_year, i_brand_id, i_brand (the brand's name is a
        function of its id); order by d_year, sum_agg desc, i_brand_id;
        limit 100."""
        year, item_sk, price = (np.concatenate(x) for x in zip(*self._q3))
        brand_id = self._item["i_brand_id"][item_sk - 1]
        keys, group = np.unique(np.stack([year, brand_id], axis=1), axis=0,
                                return_inverse=True)
        sums = _group_sums(group.reshape(-1), len(keys), price, self.dtype)
        order = np.lexsort((keys[:, 1], -sums, keys[:, 0]))[:LIMIT]
        return [[int(keys[g, 0]), int(keys[g, 1]),
                 rows.brand(keys[g, 1]), float(sums[g])] for g in order]

    def q7(self):
        """group by i_item_id (one id an item in this generator, in the
        order of i_item_sk); four averages, each its sum over the
        group's count; order by i_item_id; limit 100."""
        item_sk, *lanes = (np.concatenate(x) for x in zip(*self._q7))
        sks, group = np.unique(item_sk, return_inverse=True)
        sks, n = sks[:LIMIT], min(LIMIT, len(sks))
        first = group < n
        count = np.bincount(group[first], minlength=n).astype(self.dtype)
        avgs = [_group_sums(group[first], n, lane[first], self.dtype)
                / count for lane in lanes]
        return [[rows.item_id(sk)] + [float(a[g]) for a in avgs]
                for g, sk in enumerate(sks)]

    def q96(self):
        return [[self._q96]]

    def answer(self, name: str):
        return getattr(self, name)()
