"""q1 (scan + grouped sums): share of the HBM roofline, bound by bytes
(nothing is pushed into q1's scan: it delivers the table)."""

from ._roofline import share_pct

CLASS = "q1"


def read(run):
    return share_pct(run, CLASS)
