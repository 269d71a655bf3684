"""A canonical program's literals, bound as its arguments.

Reference parity: a prepared statement's parameters
(sql/planner/ParameterRewriter.java) — the compiled unit does not
change with the values bound to it. Here the compiled unit is an XLA
program, and its identity is the canonical key of exec/progkey.py: the
canonicalizer puts a typed slot, ``rex.Param``, where a comparison or
an arithmetic call has a literal VALUE, so two texts that differ only
in literals share one key, one trace and one compiled program. What
fixes a shape or a dictionary stays a constant of the key: LIMIT and
TopN counts, IN lists, LIKE patterns, interval units, anything a trace
needs concrete.

- A ``Const`` of a one-lane fixed-width type becomes a slot holding its
  lane value (a short DECIMAL's unscaled integer, a DATE's days).
- A column-free subtree (``date '1998-12-01' - interval '90' day``,
  ``cast(0.06 - 0.01 as double)``) becomes ONE slot whose value is
  computed HERE, on the host, at binding (``host_fold``): the chip's
  float64 is a pair of float32 with an approximate division, so the
  device would not reproduce the double a baked constant folds to, and
  a discount bound has to land exactly on the lanes' whole cents. A
  subtree ``host_fold`` cannot compute stays baked, as before.
- A varchar literal compared for (in)equality with a dictionary-coded
  input lane is bound as its CODE in that lane's dictionary (-1 where
  absent), looked up on the host for each batch bound.

The values travel as ONE vector a dtype (``LITERAL_SLOTS`` wide, padded
with zeros), on the input batch of the program call (``BoundBatch``, a
``Batch`` whose pytree carries the vectors); the program's wrapper
(``progkey.named_jit``) takes them off and evaluates every ``Param``
from them (``literal_scope``). Vectors are kept on the device per value
set, so a repeated set costs no transfer. A program with more literals
than ``LITERAL_SLOTS`` bakes the rest: ``system.runtime.nodes``'
``program_literal_slots`` states the width.
"""

from __future__ import annotations

import contextvars
import datetime
import functools
import threading
from collections import OrderedDict
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from ..columnar import Batch
from ..rex import Call, Cast, Const, Param, RowExpr
from ..types import (BOOLEAN, DecimalType, IntervalDayTime,
                     IntervalYearMonth, TimestampTZType, Type,
                     is_integral, is_string)

# the width of a program's literal vector: a constant of the code
LITERAL_SLOTS = 16


def slot_dtype(t: Type) -> str:
    """The vector a slot of type ``t`` lives in (a varchar slot holds a
    dictionary code)."""
    return "int32" if is_string(t) else np.dtype(t.np_dtype).name


def value_slot_type(t: Type) -> bool:
    """A type whose literal can be a value slot: one fixed-width lane,
    not a boolean, a string, a zoned timestamp or a long decimal."""
    if t.np_dtype is None or t.lanes != 1 or t is BOOLEAN \
            or is_string(t) or isinstance(t, TimestampTZType):
        return False
    return not isinstance(t, DecimalType) or t.is_short


def decimal_unscaled(value, t: DecimalType) -> int:
    """A DECIMAL literal's unscaled integer at ``t.scale``: exact for a
    string (a float round trip would corrupt literals beyond 2^53, as in
    q34-style wide-decimal comparisons; prec=80 because the default
    28-digit context rounds DECIMAL(38) magnitudes)."""
    if isinstance(value, int):
        return value * 10 ** t.scale
    if isinstance(value, str):
        from decimal import Context, Decimal, ROUND_HALF_UP
        return int(Decimal(value).scaleb(t.scale, Context(prec=80))
                   .to_integral_value(rounding=ROUND_HALF_UP))
    return int(round(float(value) * (10 ** t.scale)))


def const_lane_value(e: Const):
    """The lane value ``_const_column`` would broadcast for ``e``."""
    t = e.type
    if isinstance(t, DecimalType):
        return np.int64(decimal_unscaled(e.value, t))
    return np.asarray(e.value, dtype=t.np_dtype)[()]


# ---- host folding of column-free subtrees --------------------------------

_EPOCH = datetime.date(1970, 1, 1).toordinal()


def _add_months(days: int, months: int) -> int:
    """ops/datetime.py ``add_months`` on the host: end-of-month
    clamping, proleptic Gregorian."""
    d = datetime.date.fromordinal(days + _EPOCH)
    t = d.year * 12 + (d.month - 1) + months
    ny, nm = t // 12, t % 12 + 1
    first_next = (datetime.date(ny + 1, 1, 1) if nm == 12
                  else datetime.date(ny, nm + 1, 1))
    length = (first_next - datetime.date(ny, nm, 1)).days
    return datetime.date(ny, nm, min(d.day, length)).toordinal() - _EPOCH


def _lane(t: Type):
    return np.dtype(t.np_dtype).type


def host_fold(e: RowExpr):
    """The lane value of a column-free subtree, computed on the host
    with the arithmetic ``exec/expr.py`` gives it, or None where this
    folder does not know the call (the subtree then stays baked)."""
    try:
        return _folded(e)
    except TypeError:       # an unhashable constant: not a slot
        return None


@functools.lru_cache(maxsize=4096)
def _folded(e: RowExpr):
    try:
        with np.errstate(all="ignore"):
            return _fold(e)
    except (ValueError, OverflowError, TypeError, ArithmeticError):
        return None


def _fold(e: RowExpr):
    if isinstance(e, Const):
        if e.value is None or not value_slot_type(e.type):
            return None
        return const_lane_value(e)
    if not value_slot_type(e.type):
        return None
    if isinstance(e, Cast):
        return _fold_cast(e)
    if not isinstance(e, Call):
        return None
    args = [_fold(a) for a in e.args]
    if any(v is None for v in args):
        return None
    t = e.type
    if e.fn == "negate" and len(args) == 1:
        return (-args[0]).astype(_lane(t)) if not isinstance(
            t, DecimalType) else np.int64(-args[0])
    if len(args) != 2:
        return None
    a, b = args
    ta, tb = e.args[0].type, e.args[1].type
    if e.fn in ("date_add_interval", "date_sub_interval"):
        days, iv = int(a), int(b)
        if e.fn == "date_sub_interval":
            iv = -iv
        if tb is IntervalYearMonth:
            return np.int32(_add_months(days, iv))
        if tb is IntervalDayTime:
            return np.int32(days + iv // 86400000)
        return None
    if e.fn in ("decimal_+", "decimal_-") and isinstance(t, DecimalType):
        sa = ta.scale if isinstance(ta, DecimalType) else 0
        sb = tb.scale if isinstance(tb, DecimalType) else 0
        if not (isinstance(ta, DecimalType) or is_integral(ta)) or not (
                isinstance(tb, DecimalType) or is_integral(tb)) \
                or t.scale < max(sa, sb):
            return None
        da = int(a) * 10 ** (t.scale - sa)
        db = int(b) * 10 ** (t.scale - sb)
        return np.int64(da + db if e.fn == "decimal_+" else da - db)
    if e.fn in ("+", "-", "*") and not isinstance(t, DecimalType) \
            and a.dtype == b.dtype == np.dtype(t.np_dtype):
        out = a + b if e.fn == "+" else a - b if e.fn == "-" else a * b
        return out.astype(_lane(t))
    return None


def _fold_cast(e: Cast):
    v = _fold(e.arg)
    if v is None:
        return None
    s, t = e.arg.type, e.type
    if s == t:
        return v
    if isinstance(s, DecimalType):
        if t.name == "double":
            return np.float64(v) / (10.0 ** s.scale)
        if t.name == "real":
            return (np.float64(v) / (10.0 ** s.scale)).astype(np.float32)
        return None
    if is_integral(s) and t.name in ("double", "real", "bigint",
                                     "integer", "smallint", "tinyint"):
        return np.asarray(v).astype(_lane(t))[()]
    if s.name == "double" and t.name == "real":
        return np.float32(v)
    return None


# ---- the bound batch -----------------------------------------------------

@dataclass(frozen=True)
class BoundBatch(Batch):
    """A program's input batch with its literal vectors
    (``{dtype: array[LITERAL_SLOTS]}``) and how many slots they bind.
    Only a program's wrapper reads them: whatever the program builds
    from its input is a plain ``Batch``."""
    literals: Optional[Dict[str, object]] = None
    bound: int = 0


def _bound_flatten(b: BoundBatch):
    names = tuple(b.columns.keys())
    return ((tuple(b.columns[n] for n in names), b.num_rows, b.literals),
            (names, b.bound))


def _bound_unflatten(aux, children):
    names, bound = aux
    cols, num_rows, literals = children
    return BoundBatch(dict(zip(names, cols)), num_rows, literals, bound)


jax.tree_util.register_pytree_node(BoundBatch, _bound_flatten,
                                   _bound_unflatten)

_BOUND: contextvars.ContextVar = contextvars.ContextVar(
    "trino_tpu_literals", default=None)


def current_literals():
    """The literal vectors of the program being traced or run."""
    return _BOUND.get()


@contextmanager
def literal_scope(literals):
    """Evaluate ``Param`` slots from ``literals`` inside the block
    (traced values inside a jitted program, device arrays eagerly)."""
    if literals is None:
        yield
        return
    token = _BOUND.set(literals)
    try:
        yield
    finally:
        _BOUND.reset(token)


def unbind(args: Sequence) -> Tuple[tuple, Optional[dict]]:
    """``args`` with every ``BoundBatch`` made a plain ``Batch``, and
    the literal vectors they carried (one binding a call)."""
    literals = None
    out = []
    for a in args:
        if isinstance(a, BoundBatch):
            literals = a.literals
            a = Batch(a.columns, a.num_rows)
        out.append(a)
    return tuple(out), literals


def call_bound(fn, *args):
    """Call ``fn`` (a function of canonical nodes) eagerly on bound
    arguments."""
    args, literals = unbind(args)
    with literal_scope(literals):
        return fn(*args)


def bound_count(args: Sequence) -> int:
    """The literals bound to a program call (the ``args`` attr of its
    dispatch span)."""
    return sum(a.bound for a in args if isinstance(a, BoundBatch))


# ---- slots and their values ----------------------------------------------

@dataclass(frozen=True)
class LiteralSlot:
    """One slot of a canonical program: its vector and position, and
    where its value comes from: ``expr`` (a Const or a column-free
    subtree, folded on the host) or, for a dictionary code, ``code_of``
    (the ORIGINAL input symbol whose dictionary codes ``expr``'s
    string)."""
    dtype: str
    index: int
    expr: RowExpr
    code_of: Optional[str] = None


_DEVICE_LOCK = threading.Lock()
_DEVICE: "OrderedDict[tuple, object]" = OrderedDict()
_DEVICE_ENTRIES = 1024

def _on_device(dtype: str, values: tuple):
    """One literal vector on the device, kept per value set."""
    key = (dtype, values)
    with _DEVICE_LOCK:
        got = _DEVICE.get(key)
        if got is not None:
            _DEVICE.move_to_end(key)
            return got
    vec = np.zeros((LITERAL_SLOTS,), dtype=np.dtype(dtype))
    vec[:len(values)] = values
    arr = jax.device_put(vec)
    with _DEVICE_LOCK:
        _DEVICE[key] = arr
        while len(_DEVICE) > _DEVICE_ENTRIES:
            _DEVICE.popitem(last=False)
    return arr


class LiteralBinding:
    """The values of a program's slots for one plan: folded once; the
    dictionary codes looked up per batch (every split may carry its
    own dictionary)."""

    __slots__ = ("slots", "fixed")

    def __init__(self, slots: Sequence[LiteralSlot]) -> None:
        self.slots = tuple(slots)
        self.fixed = {s: host_fold(s.expr) for s in self.slots
                      if s.code_of is None}

    def vectors(self, b: Batch) -> Dict[str, object]:
        per: Dict[str, list] = {}
        for s in self.slots:
            if s.code_of is None:
                v = self.fixed[s]
            else:
                col = b.columns.get(s.code_of)
                d = None if col is None else col.dictionary
                v = -1 if d is None else d.code_of(s.expr.value)
            per.setdefault(s.dtype, []).append(v)
        return {dt: _on_device(dt, tuple(vals))
                for dt, vals in sorted(per.items())}

    def bind(self, b: Batch, renamed: Batch) -> Batch:
        """``renamed`` (``b`` under canonical names) carrying the
        vectors of ``b``'s binding."""
        if not self.slots:
            return renamed
        return BoundBatch(renamed.columns, renamed.num_rows,
                          self.vectors(b), len(self.slots))


def param_value(p: Param):
    """Slot ``p``'s scalar in the current literal vectors."""
    lits = _BOUND.get()
    if lits is None or p.dtype not in lits:
        return None
    return lits[p.dtype][p.index]
