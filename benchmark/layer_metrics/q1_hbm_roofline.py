"""q1 (scan + grouped sums): share of the HBM roofline, bound by bytes."""

from ._roofline import share_pct


def read(run):
    return share_pct(run, "q1")
