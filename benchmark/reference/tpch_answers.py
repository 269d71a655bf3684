"""The plain reference: TPC-H q1, q3 and q6 in numpy over ``tpch_rows``,
in the shape the client returns rows, for any set of substitution
parameters (``tpch_params``; the validation parameters where none are
given).

Independent of the program: no import of ``trino_tpu``, no table, lane
or dictionary the program made. Orders are streamed in chunks (a chunk
of orders holds exactly its own lineitems, so the q3 join closes inside
a chunk), so sf10 needs no more host memory than sf1. Every wanted
(class, parameters) pair is folded into each chunk: one pass over the
generated rows answers them all, and each pair's arithmetic is what it
would be alone.

``dtype`` is the precision of the DOUBLE lanes and of every sum over
them. The configuration states DOUBLE (float64); the control of the
output check runs the same reference in float32, the nearest precision
below it, and has to come out as not correct.
"""

import datetime

import numpy as np

from . import tpch_params
from . import tpch_rows as rows
from .pins import pins  # noqa: F401  (the rehearsal's wanted data pins)

ORDERS_PER_CHUNK = 500_000
EPOCH = datetime.date(1970, 1, 1)
Q1_END = rows.days(1998, 12, 1)
CLASSES = ("q1", "q3", "q6")


def _day(iso: str) -> int:
    return rows.days(*map(int, iso.split("-")))


def pair(want):
    """(class, parameter tuple) of ``want``: a bare class name stands for
    the class at its validation parameters."""
    if isinstance(want, str):
        return want, tpch_params.validation(want)
    cls, params = want
    return cls, tuple(params)


class Answers:
    """Answers of the (class, parameters) pairs in ``want`` (classes
    ``q1``, ``q3``, ``q6``; a bare name is the class at its validation
    parameters) at scale factor ``sf``; ``answer(name, params=None)``
    gives the rows."""

    def __init__(self, sf: float, want, dtype=np.float64):
        self.sf = sf
        self.pairs = {pair(w) for w in want}
        unknown = {c for c, _p in self.pairs} - set(CLASSES)
        if unknown:
            raise KeyError(f"the reference has no answer for {unknown}")
        self.dtype = np.dtype(dtype).type
        self.n_lineitem = 0
        of = {c: sorted(p for k, p in self.pairs if k == c) for c in CLASSES}
        self._q1 = {p: (np.zeros((6, 5), self.dtype), np.zeros(6, np.int64))
                    for p in of["q1"]}
        self._q6 = {p: self.dtype(0) for p in of["q6"]}
        self._q3 = {p: ([], []) for p in of["q3"]}
        self._run()

    def _needs_orders(self) -> bool:
        return bool(self._q3)

    def _run(self) -> None:
        self._segments = {}
        if self._q3:
            c = rows.customer(self.sf)
            for seg in {p[0] for p in self._q3}:
                mask = np.zeros(len(c["c_custkey"]) + 1, bool)
                mask[c["c_custkey"][c["c_mktsegment"] == seg]] = True
                self._segments[seg] = mask
        n_orders = rows.table_rows("orders", self.sf)
        for lo in range(0, n_orders, ORDERS_PER_CHUNK):
            hi = min(lo + ORDERS_PER_CHUNK, n_orders)
            idx = np.arange(lo + 1, hi + 1, dtype=np.int64)
            li = rows.lineitem(idx, self.sf)
            self.n_lineitem += len(li["l_orderkey"])
            for k in ("l_quantity", "l_extendedprice", "l_discount",
                      "l_tax"):
                li[k] = li[k].astype(self.dtype)
            self._fold(li, rows.orders(idx, self.sf)
                       if self._needs_orders() else None)

    def _fold(self, li, o) -> None:
        for params in self._q6:
            self._fold_q6(li, params)
        if self._q1:
            self._fold_q1(li)
        if self._q3:
            self._fold_q3(o, li)

    def _fold_q6(self, li, params) -> None:
        start, discount, quantity = params
        lo = _day(start)
        hi = rows.days(int(start[:4]) + 1, int(start[5:7]), int(start[8:]))
        # SQL decimal literals are exact: 0.06 - 0.01 is 0.05 and
        # 0.06 + 0.01 is 0.07 (binary doubles would give 0.0699...); so
        # the bounds are taken in whole cents, each the float nearest its
        # decimal
        cents = round(float(discount) * 100)
        disc = li["l_discount"]
        m = ((li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
             & (disc >= self.dtype((cents - 1) / 100))
             & (disc <= self.dtype((cents + 1) / 100))
             & (li["l_quantity"] < int(quantity)))
        self._q6[params] = self.dtype(self._q6[params] + np.sum(
            li["l_extendedprice"][m] * disc[m], dtype=self.dtype))

    def _fold_q1(self, li) -> None:
        # each group's rows once, in row order; a cutoff then masks them:
        # the same rows in the same order as masking first, so the same
        # sums (numpy's pairwise sum: the float64 reference is good to a
        # few ulps, well under the gap any limit is set on)
        one = self.dtype(1)
        price, disc = li["l_extendedprice"], li["l_discount"]
        disc_price = price * (one - disc)
        lanes = (li["l_quantity"], price, disc_price,
                 disc_price * (one + li["l_tax"]), disc)
        group = li["l_returnflag"] * 2 + li["l_linestatus"]
        by_group = []
        for g in range(6):
            at = np.flatnonzero(group == g)
            by_group.append((li["l_shipdate"][at],
                             [lane[at] for lane in lanes]))
        for (delta,), (sums, counts) in self._q1.items():
            cutoff = Q1_END - int(delta)
            for g, (ship, group_lanes) in enumerate(by_group):
                m = ship <= cutoff
                for i, lane in enumerate(group_lanes):
                    sums[g, i] += np.sum(lane[m], dtype=self.dtype)
                counts[g] += int(np.count_nonzero(m))

    def _fold_q3(self, o, li) -> None:
        o_key = o["o_orderkey"]               # ascending, the whole chunk
        pos = np.searchsorted(o_key, li["l_orderkey"])
        volume = li["l_extendedprice"] * (self.dtype(1) - li["l_discount"])
        for (segment, date), (keys, revs) in self._q3.items():
            day = _day(date)
            sel = (self._segments[segment][o["o_custkey"]]
                   & (o["o_orderdate"] < day))
            hit = sel[pos] & (li["l_shipdate"] > day)
            at = pos[hit]
            # one order has at most 7 lines: sum them in line order
            rev = np.zeros(len(o_key), self.dtype)
            np.add.at(rev, at, volume[hit])
            has = np.bincount(at, minlength=len(o_key)) > 0
            if not has.any():
                continue
            keys.append(np.stack([o_key[has], o["o_orderdate"][has],
                                  o["o_shippriority"][has]], axis=1))
            revs.append(rev[has])

    # ---- the answers, in the shape the client returns them -------------
    def q6(self, params):
        return [[float(self._q6[params])]]

    def q1(self, params):
        sums, counts = self._q1[params]
        out = []
        for g in range(6):
            n = int(counts[g])
            if not n:
                continue
            s = sums[g]
            cnt = self.dtype(n)
            out.append([str(rows.RETURNFLAGS[g // 2]),
                        str(rows.LINESTATUS[g % 2]),
                        float(s[0]), float(s[1]), float(s[2]), float(s[3]),
                        float(s[0] / cnt), float(s[1] / cnt),
                        float(s[4] / cnt), n])
        return sorted(out, key=lambda r: (r[0], r[1]))

    def _q3_ranked(self, params):
        keys, revs = self._q3[params]
        if not keys:
            return np.zeros((0, 3), np.int64), np.zeros(0, self.dtype), []
        keys, rev = np.concatenate(keys), np.concatenate(revs)
        return keys, rev, np.lexsort((keys[:, 1], -rev))

    def q3(self, params):
        keys, rev, order = self._q3_ranked(params)
        return [[int(keys[i, 0]), float(rev[i]),
                 (EPOCH + datetime.timedelta(days=int(keys[i, 1])))
                 .isoformat(), int(keys[i, 2])] for i in order[:10]]

    def q3_ties(self, params=None) -> int:
        """How many rows of q3's answer share both sort keys (revenue,
        o_orderdate) with another row ranked up to one past the LIMIT:
        where it is not 0 the order among them, or which of them is
        kept, is the program's to choose."""
        keys, rev, order = self._q3_ranked(
            pair("q3" if params is None else ("q3", params))[1])
        top = [(rev[i], keys[i, 1]) for i in order[:11]]
        return sum(1 for i, k in enumerate(top[:10])
                   if k in top[:i] + top[i + 1:])

    def answer(self, name: str, params=None):
        return getattr(self, name)(pair(name if params is None
                                        else (name, params))[1])
