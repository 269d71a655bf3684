"""A pushed-down constraint met with fresh literals: its compacted
lanes DERIVED from the table's resident base lanes by one program,
not regenerated split by split.

Every conjunct that compares a column with a literal is pushed into
the scan (planner/optimizer.py ``push_into_scan``) and the scan cache
keeps the lanes it leaves per constraint (exec/executor.py
``read_table_cached``). A constraint SHAPE is what stays when the
literals go: the same domains on the same columns, the same bound
kinds and inclusivity. A connector's scan cache fills each constraint
as before until one shape meets a second distinct set of literals.
From then on the table's base lanes are made resident once (the
unconstrained entry, widened to the union of the lanes the shape's
entries deliver and the constrained columns) and every miss is ONE
compiled program a shape, ``scan_derive``: the mask from the bounds,
given as arguments, then the stable compaction, then one counted read
of the rows it kept; the lanes are then cut (``scan_prefix``) to the
capacity of the shape's first copy, the fill's (the bucket of its rows
on a table of many splits, the split's own on a table of one), or to
the bucket of the rows where they outgrow it. The rows and their order
are the fill's, and one capacity serves a shape, so a derived copy and
a filled copy stand in for each other and the programs above them
compile once. The compaction
is ``ops/compact.py compact_in_place``, by shifts: at sf1's 2^23
lineitem lanes a gather-based one (``compact_batch``) held a derive on
the chip for about 0.95 s.

A bound that is a number its lane holds exactly is a slot of a vector
a dtype; any other bound stays baked in the shape. (No string reaches
a scan: planner/optimizer.py ``_domain_pushable`` keeps a string
conjunct in the plan, where its literal is a dictionary code slot of
the program, exec/literals.py.)
"""

from __future__ import annotations

from dataclasses import replace
from typing import Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..columnar import Batch
from ..ops.compact import compact_in_place
from ..predicate import TupleDomain
from .literals import slot_dtype, value_slot_type


def _slot_value(v, dtype: str):
    """``v`` in the lane dtype, or None where it is not a plain number
    the lane holds exactly."""
    if isinstance(v, bool) or not isinstance(v, (int, float, np.number)):
        return None
    got = np.asarray(v, dtype=np.dtype(dtype))[()]
    return got if got == v else None


def constraint_shape(constraint) -> Optional[Tuple[tuple, tuple]]:
    """``(shape, values)`` of a pushed-down TupleDomain: the shape keys
    the derive program, the values are its bounds in slot order as
    ``(dtype, value)``. None where no bound is a slot (nothing to
    derive anew)."""
    if constraint is None or constraint.is_none or constraint.is_all():
        return None
    shape, values = [], []
    for col, dom in constraint.domains:
        slots = []
        dtype = (slot_dtype(dom.type) if value_slot_type(dom.type)
                 else None)
        kinds = []
        for r in dom.ranges:
            for v in ((r.low,) if r.low is not None else ()) + (
                    (r.high,) if r.high is not None else ()):
                got = None if dtype is None else _slot_value(v, dtype)
                slots.append(got)
            kinds.append((r.low is not None, r.low_inclusive,
                          r.high is not None, r.high_inclusive))
        if dom.is_all or not slots or any(s is None for s in slots):
            shape.append(("baked", col, dom))
            continue
        shape.append(("slots", col, dtype, dom.null_allowed,
                      tuple(kinds)))
        values.extend((dtype, s) for s in slots)
    if not values:
        return None
    return tuple(shape), tuple(values)


def bound_vectors(values: Sequence[Tuple[str, object]]) -> Dict[str, object]:
    """The bounds as one device vector a dtype (exec/literals.py)."""
    from .literals import _on_device
    per: Dict[str, list] = {}
    for dtype, v in values:
        per.setdefault(dtype, []).append(v)
    return {dt: _on_device(dt, tuple(vals))
            for dt, vals in sorted(per.items())}


def make_derive_program(shape, keep: Sequence[str]):
    """``fn(base, bounds) -> (lanes, rows)``: the rows of ``base`` the
    constraint of ``shape`` keeps, with the bounds from ``bounds``,
    compacted in order at the base's capacity; ``keep`` the lanes
    returned."""
    keep = tuple(keep)

    def fn(base: Batch, bounds):
        from ..connectors.tpch_device import constraint_mask
        mask = base.row_valid()
        at: Dict[str, int] = {}
        for ent in shape:
            if ent[0] == "baked":
                _, col, dom = ent
                mask = mask & constraint_mask(
                    Batch({col: base.columns[col]}, base.num_rows),
                    TupleDomain(((col, dom),)))
                continue
            _, col, dtype, null_allowed, kinds = ent
            c = base.columns[col]
            data = jnp.asarray(c.data)
            m = jnp.zeros(data.shape, bool)
            for has_lo, lo_incl, has_hi, hi_incl in kinds:
                rm = jnp.ones(data.shape, bool)
                for has, incl, low in ((has_lo, lo_incl, True),
                                       (has_hi, hi_incl, False)):
                    if not has:
                        continue
                    i = at.get(dtype, 0)
                    at[dtype] = i + 1
                    v = bounds[dtype][i]
                    rm = rm & ((data >= v if incl else data > v) if low
                               else (data <= v if incl else data < v))
                m = m | rm
            if c.valid is not None:
                valid = jnp.asarray(c.valid)
                m = (m & valid) | (~valid & null_allowed)
            mask = mask & m
        out = compact_in_place(
            Batch({k: base.columns[k] for k in keep}, base.num_rows),
            mask)
        return out, out.num_rows_device()

    return fn


def make_prefix_program(cap: int):
    """``fn(batch) -> batch``: the first ``cap`` slots of every lane (a
    derived copy cut to its shape's capacity)."""

    def fn(b: Batch) -> Batch:
        cols = {}
        for name, c in b.columns.items():
            cols[name] = replace(c, **{
                part: jnp.asarray(getattr(c, part))[:cap]
                for part in ("data", "valid", "data2")
                if getattr(c, part) is not None})
        return Batch(cols, b.num_rows)

    return fn
