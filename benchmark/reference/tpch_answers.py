"""The plain reference: TPC-H q1, q3 and q6 (validation parameters) in
numpy over ``tpch_rows``, in the shape the client returns rows.

Independent of the program: no import of ``trino_tpu``, no table, lane
or dictionary the program made. Orders are streamed in chunks (a chunk
of orders holds exactly its own lineitems, so the q3 join closes inside
a chunk), so sf10 needs no more host memory than sf1.

``dtype`` is the precision of the DOUBLE lanes and of every sum over
them. The configuration states DOUBLE (float64); the control of the
output check runs the same reference in float32, the nearest precision
below it, and has to come out as not correct.
"""

import datetime

import numpy as np

from . import tpch_rows as rows
from .pins import pins  # noqa: F401  (the rehearsal's wanted data pins)

ORDERS_PER_CHUNK = 500_000
EPOCH = datetime.date(1970, 1, 1)

Q1_CUTOFF = rows.days(1998, 12, 1) - 90
Q3_SEGMENT = "BUILDING"
Q3_DATE = rows.days(1995, 3, 15)
Q6_FROM = rows.days(1994, 1, 1)
Q6_TO = rows.days(1995, 1, 1)


def _group_sums(values, groups, n_groups, dtype):
    # numpy's pairwise sum: the float64 reference is then good to a few
    # ulps, well under the gap any limit is set on
    return np.array([np.sum(values[groups == g], dtype=dtype)
                     for g in range(n_groups)], dtype=dtype)


class Answers:
    """Answers of the queries in ``want`` (names ``q1``, ``q3``, ``q6``)
    at scale factor ``sf``; ``answer(name)`` gives the rows."""

    def __init__(self, sf: float, want, dtype=np.float64):
        self.sf = sf
        self.want = set(want)
        unknown = self.want - {"q1", "q3", "q6"}
        if unknown:
            raise KeyError(f"the reference has no answer for {unknown}")
        self.dtype = np.dtype(dtype).type
        self.n_lineitem = 0
        self._q1_sums = np.zeros((6, 5), self.dtype)
        self._q1_counts = np.zeros(6, np.int64)
        self._q6 = self.dtype(0)
        self._q3_keys = []
        self._q3_rev = []
        self._run()

    def _run(self) -> None:
        building = None
        if "q3" in self.want:
            c = rows.customer(self.sf)
            building = np.zeros(len(c["c_custkey"]) + 1, bool)
            building[c["c_custkey"][c["c_mktsegment"] == Q3_SEGMENT]] = True
        n_orders = rows.table_rows("orders", self.sf)
        for lo in range(0, n_orders, ORDERS_PER_CHUNK):
            hi = min(lo + ORDERS_PER_CHUNK, n_orders)
            idx = np.arange(lo + 1, hi + 1, dtype=np.int64)
            li = rows.lineitem(idx, self.sf)
            self.n_lineitem += len(li["l_orderkey"])
            for k in ("l_quantity", "l_extendedprice", "l_discount",
                      "l_tax"):
                li[k] = li[k].astype(self.dtype)
            if "q6" in self.want:
                self._fold_q6(li)
            if "q1" in self.want:
                self._fold_q1(li)
            if "q3" in self.want:
                self._fold_q3(rows.orders(idx, self.sf), building, li)

    def _fold_q6(self, li) -> None:
        # SQL decimal literals are exact: 0.06 - 0.01 is 0.05 and
        # 0.06 + 0.01 is 0.07 (binary doubles would give 0.0699...)
        disc = li["l_discount"]
        m = ((li["l_shipdate"] >= Q6_FROM) & (li["l_shipdate"] < Q6_TO)
             & (disc >= self.dtype(0.05)) & (disc <= self.dtype(0.07))
             & (li["l_quantity"] < 24))
        self._q6 = self.dtype(self._q6 + np.sum(
            li["l_extendedprice"][m] * disc[m], dtype=self.dtype))

    def _fold_q1(self, li) -> None:
        m = li["l_shipdate"] <= Q1_CUTOFF
        group = (li["l_returnflag"] * 2 + li["l_linestatus"])[m]
        one = self.dtype(1)
        price = li["l_extendedprice"][m]
        disc = li["l_discount"][m]
        disc_price = price * (one - disc)
        lanes = (li["l_quantity"][m], price, disc_price,
                 disc_price * (one + li["l_tax"][m]), disc)
        for i, lane in enumerate(lanes):
            self._q1_sums[:, i] += _group_sums(lane, group, 6, self.dtype)
        self._q1_counts += np.bincount(group, minlength=6)

    def _fold_q3(self, o, building, li) -> None:
        sel = building[o["o_custkey"]] & (o["o_orderdate"] < Q3_DATE)
        o_key = o["o_orderkey"][sel]          # ascending
        if not len(o_key):
            return
        pos = np.minimum(np.searchsorted(o_key, li["l_orderkey"]),
                         len(o_key) - 1)
        hit = (li["l_shipdate"] > Q3_DATE) & (o_key[pos] == li["l_orderkey"])
        volume = (li["l_extendedprice"]
                  * (self.dtype(1) - li["l_discount"]))[hit]
        # one order has at most 7 lines: sum them in line order
        rev = np.zeros(len(o_key), self.dtype)
        np.add.at(rev, pos[hit], volume)
        has = np.bincount(pos[hit], minlength=len(o_key)) > 0
        self._q3_keys.append(np.stack(
            [o_key[has], o["o_orderdate"][sel][has],
             o["o_shippriority"][sel][has]], axis=1))
        self._q3_rev.append(rev[has])

    # ---- the answers, in the shape the client returns them -------------
    def q6(self):
        return [[float(self._q6)]]

    def q1(self):
        out = []
        for g in range(6):
            n = int(self._q1_counts[g])
            if not n:
                continue
            s = self._q1_sums[g]
            cnt = self.dtype(n)
            out.append([str(rows.RETURNFLAGS[g // 2]),
                        str(rows.LINESTATUS[g % 2]),
                        float(s[0]), float(s[1]), float(s[2]), float(s[3]),
                        float(s[0] / cnt), float(s[1] / cnt),
                        float(s[4] / cnt), n])
        return sorted(out, key=lambda r: (r[0], r[1]))

    def q3(self):
        keys = np.concatenate(self._q3_keys)
        rev = np.concatenate(self._q3_rev)
        order = np.lexsort((keys[:, 1], -rev))[:10]
        return [[int(keys[i, 0]), float(rev[i]),
                 (EPOCH + datetime.timedelta(days=int(keys[i, 1])))
                 .isoformat(), int(keys[i, 2])] for i in order]

    def answer(self, name: str):
        return getattr(self, name)()
