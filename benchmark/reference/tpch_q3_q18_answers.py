"""The plain reference of ``tpch_sf10_q3_q18_1chip``: TPC-H q3 and q18
(validation parameters) in numpy over ``tpch_rows``, in the shape the
client returns rows.

Independent of the program: no import of ``trino_tpu``. q3 is
``tpch_answers``' own fold; q18 is computed here from the same chunks
of orders (a chunk of orders holds exactly its own lineitems, so the
per-order sums close inside a chunk and SF 10 needs no more host memory
than SF 1):

    per order   sum(l_quantity)
                o_totalprice = round(sum(l_extendedprice * (1 + l_tax)
                                         * (1 - l_discount)), 2)
                (what the program's generator derives the orders lane
                from: the lines are added in line order)
    kept        the orders whose sum(l_quantity) is over 300
    answer      the first 100 by (o_totalprice desc, o_orderdate), with
                c_name = 'Customer#%09d' of o_custkey

The five group keys of the SQL are functions of the order, so one
answer row is one order and its sum(l_quantity) is the order's.

``dtype`` is the precision of the DOUBLE lanes and of every sum over
them; the control runs float32 and has to come out not correct, on
``o_totalprice`` and on q3's revenue (``sum(l_quantity)`` is a sum of
whole numbers up to 350 and cannot differ).
"""

import datetime

import numpy as np

from . import tpch_answers
from . import tpch_rows as rows
from .pins import pins  # noqa: F401  (the rehearsal's wanted data pins)

Q18_QUANTITY = 300
Q18_LIMIT = 100
EPOCH = datetime.date(1970, 1, 1)


class Answers(tpch_answers.Answers):
    """Answers of ``q3`` and ``q18`` at scale factor ``sf``. ``quantity``
    is q18's substitution parameter (cl. 2.4.18.3: 300; a test at a
    small scale, where no order passes 300, lowers it)."""

    def __init__(self, sf: float, want, dtype=np.float64,
                 quantity: int = Q18_QUANTITY):
        unknown = set(want) - {"q3", "q18"}
        if unknown:
            raise KeyError(f"the reference has no answer for {unknown}")
        self._q18_quantity = quantity
        self._q18 = []          # per chunk: the kept orders' columns
        self._want18 = "q18" in want
        super().__init__(sf, [c for c in want if c != "q18"], dtype)

    def _needs_orders(self) -> bool:
        return self._want18 or super()._needs_orders()

    def _fold(self, li, o) -> None:
        super()._fold(li, o)
        if self._want18:
            self._fold_q18(o, li)

    def _fold_q18(self, o, li) -> None:
        o_key = o["o_orderkey"]               # ascending, one an order
        pos = np.searchsorted(o_key, li["l_orderkey"])
        one = self.dtype(1)
        price = (li["l_extendedprice"] * (one + li["l_tax"])
                 * (one - li["l_discount"]))
        qty = np.zeros(len(o_key), self.dtype)
        total = np.zeros(len(o_key), self.dtype)
        # np.add.at adds in index order: an order's lines in line order
        np.add.at(qty, pos, li["l_quantity"])
        np.add.at(total, pos, price)
        keep = qty > self.dtype(self._q18_quantity)
        self._q18.append({
            "o_custkey": o["o_custkey"][keep],
            "o_orderkey": o_key[keep],
            "o_orderdate": o["o_orderdate"][keep],
            "o_totalprice": np.round(total[keep], 2),
            "sum_qty": qty[keep]})

    def q18(self):
        t = {k: np.concatenate([c[k] for c in self._q18])
             for k in self._q18[0]}
        order = np.lexsort((t["o_orderdate"],
                            -t["o_totalprice"]))[:Q18_LIMIT]
        return [["Customer#%09d" % int(t["o_custkey"][i]),
                 int(t["o_custkey"][i]), int(t["o_orderkey"][i]),
                 (EPOCH + datetime.timedelta(
                     days=int(t["o_orderdate"][i]))).isoformat(),
                 float(t["o_totalprice"][i]), float(t["sum_qty"][i])]
                for i in order]

    def answer(self, name: str, params=None):
        # q18's one parameter is the constructor's ``quantity``
        return self.q18() if name == "q18" else super().answer(name, params)
