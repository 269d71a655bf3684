"""Share of the window's grouped-aggregation input lanes that went
through the DENSE form (``ops/groupby.py``: one integer key whose live
values span less than the dense bound, group id = key - least key, sums
by scatter; no sort, no gather): growth of
``trino_tpu_groupby_lanes_total{form="dense"}`` over that of every
form, %. The counter grows by the input capacity of each grouped
aggregation a traced query ran, in a program it dispatched or eagerly,
under the form that ran (packed, dense, sort), so the share is by
lanes, not by aggregations: q18's subquery over lineitem at 2^26 lanes
outweighs the final groupings (2^19 and 2^13 lanes), which keep the
sort form. A program without the counter
(one older than the dense form) gives ``None``."""

from ._phases import growth

FAMILY = "trino_tpu_groupby_lanes_total"


def read(run):
    keys = [k for k in run.engine_after if k.startswith(FAMILY + "{")]
    total = sum(growth(run, k) for k in keys)
    if not keys or total <= 0:
        return None
    dense = sum(growth(run, k) for k in keys if 'form="dense"' in k)
    return 100.0 * dense / total
