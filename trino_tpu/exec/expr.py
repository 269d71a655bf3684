"""Row-expression evaluation over Batches.

Reference parity: the compiled PageProcessor loop — sql/gen/
PageFunctionCompiler.java:101 + ExpressionInterpreter.java. Here every
rex node lowers to jnp ops over whole column lanes; jax.jit traces the
enclosing pipeline into one fused XLA program (SURVEY.md §7.2), which is
the TPU analog of Trino generating one bytecode class per expression.

String strategy ("strings on TPU", SURVEY.md §7 hard part 2): scalar
string functions evaluate host-side over the column's *dictionary values*
(small), producing a device gather table; per-row work on the TPU is just
integer code gathers. Functions of multiple string columns fall back to
host row materialization.

Three-valued logic: every eval returns a Column (value lane + validity
lane); AND/OR implement Kleene truth tables explicitly.
"""

from __future__ import annotations

import re
from dataclasses import replace as dc_replace
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Batch, Column, StringDictionary
from ..ops.datetime import (add_months, date_trunc_days, extract_field)
from ..obs.metrics import EXPR_CONSTANT_SUBTREES
from ..rex import (Call, CaseExpr, Cast, Const, InputRef, Param,
                   RowExpr, expr_volatile, walk)
from ..types import (BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER, REAL, UNKNOWN,
                     VARCHAR, CharType, DecimalType, IntervalDayTime,
                     IntervalYearMonth, TimestampType, Type, VarcharType,
                     is_integral, is_numeric, is_string)


class EvalError(Exception):
    pass


def eval_expr(e: RowExpr, batch: Batch) -> Column:
    if isinstance(e, InputRef):
        return batch.column(e.name)
    if isinstance(e, Const):
        return _const_column(e, batch.capacity)
    if isinstance(e, Param):
        return _param_column(e, batch)
    if batch.capacity > 1 and _constant_subtree(e):
        return _eval_constant(e, batch.capacity)
    if isinstance(e, Cast):
        return _eval_cast(e, batch)
    if isinstance(e, CaseExpr):
        return _eval_case(e, batch)
    if isinstance(e, Call):
        return _eval_call(e, batch)
    raise EvalError(f"cannot evaluate {type(e).__name__}")


def eval_predicate(e: RowExpr, batch: Batch) -> jax.Array:
    """Boolean mask: TRUE rows only (NULL -> excluded), ANDed with
    liveness."""
    col = eval_expr(e, batch)
    m = jnp.asarray(col.data).astype(bool)
    if col.valid is not None:
        m = m & jnp.asarray(col.valid)
    return m & batch.row_valid()


# --------------------------------------------------------------------------
# constant subtrees: evaluated once, at one row
# --------------------------------------------------------------------------

_ONE_ROW = Batch({"": Column(BOOLEAN, np.zeros((1,), dtype=bool))}, 1)


def _plain_lane(t: Type) -> bool:
    """One fixed-width value lane (+ validity): no dictionary, no
    ``data2``, no children."""
    return (t in (BOOLEAN, DATE) or isinstance(t, TimestampType)
            or (is_numeric(t) and t.lanes == 1))


def _constant_subtree(e: RowExpr) -> bool:
    """A call, cast or case that reads no column (nor a lambda's
    parameter: an InputRef too), holds no volatile call and yields a
    plain lane: every row gets the same value."""
    return (_plain_lane(e.type)
            and not any(isinstance(x, InputRef) for x in walk(e))
            and not expr_volatile(e))


def _eval_constant(e: RowExpr, cap: int) -> Column:
    """The same handlers over a one-row batch, the row broadcast to
    ``cap``. Under jit the compiler folds the one-row chain into a
    literal, which it does not do through per-row int64 division
    (q6's ``date + interval '1' year``: 4,331 flops a row before)."""
    col = eval_expr(e, _ONE_ROW)
    EXPR_CONSTANT_SUBTREES.inc_at(
        (e.fn if isinstance(e, Call)
         else "cast" if isinstance(e, Cast) else "case",))
    valid = (None if col.valid is None
             else jnp.broadcast_to(jnp.asarray(col.valid), (cap,)))
    return Column(col.type, jnp.broadcast_to(_lane(col), (cap,)), valid)


def _param_column(e: Param, batch: Batch) -> Column:
    """A literal slot (exec/literals.py) broadcast to the batch: its
    value from the program's literal vectors; a varchar slot is a code
    in the dictionary of the lane it is compared with."""
    from .literals import param_value
    v = param_value(e)
    if v is None:
        raise EvalError(f"literal slot {e} evaluated outside its program")
    dt = np.int32 if e.code_of is not None else e.type.np_dtype
    data = jnp.broadcast_to(jnp.asarray(v).astype(dt), (batch.capacity,))
    dictionary = (batch.column(e.code_of).dictionary
                  if e.code_of is not None else None)
    return Column(e.type, data, None, dictionary)


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _const_column(e: Const, cap: int) -> Column:
    t = e.type
    if e.value is None:
        from ..types import ArrayType, MapType, RowType
        if isinstance(t, (ArrayType, MapType, RowType)):
            from ..columnar import column_from_pylist, pad_batch
            col = column_from_pylist([None], t)
            return pad_batch(Batch({"c": col}, 1), cap).column("c")
        if is_string(t):
            d, _ = StringDictionary.from_strings([])
            return Column(t, jnp.zeros((cap,), jnp.int32),
                          jnp.zeros((cap,), dtype=bool), d)
        base = t if t != UNKNOWN else BOOLEAN
        dt = base.np_dtype or np.dtype(np.int64)
        return Column(t, jnp.zeros((cap,), dtype=dt),
                      jnp.zeros((cap,), dtype=bool))
    if is_string(t):
        d = StringDictionary(np.asarray([e.value], dtype=object))
        return Column(t, jnp.zeros((cap,), dtype=jnp.int32), None, d)
    from ..types import TimestampTZType
    if isinstance(t, TimestampTZType):
        ms, off = (e.value if isinstance(e.value, tuple)
                   else (e.value, 0))
        return Column(t, jnp.full((cap,), ms, jnp.int64), None,
                      data2=jnp.full((cap,), off, jnp.int64))
    if isinstance(t, DecimalType):
        from .literals import decimal_unscaled
        q = decimal_unscaled(e.value, t)
        if not t.is_short:
            lo = q & ((1 << 64) - 1)
            lo = lo - (1 << 64) if lo >= (1 << 63) else lo
            return Column(t, jnp.full((cap,), lo, jnp.int64), None,
                          data2=jnp.full((cap,), q >> 64, jnp.int64))
        return Column(t, jnp.full((cap,), q, dtype=jnp.int64), None)
    dt = t.np_dtype
    return Column(t, jnp.full((cap,), e.value, dtype=dt), None)


def _lane(col: Column) -> jax.Array:
    return jnp.asarray(col.data)


def _merge_valid(*cols: Column) -> Optional[jax.Array]:
    v = None
    for c in cols:
        if c.valid is None:
            continue
        cv = jnp.asarray(c.valid)
        v = cv if v is None else (v & cv)
    return v


def _dict_transform(col: Column, fn: Callable[[str], object],
                    out_type: Type) -> Column:
    """Host-evaluate fn over dictionary values; device code lanes are
    reused (possibly remapped through a new dictionary)."""
    vals = col.dictionary.values
    out = [fn(str(v)) for v in vals]
    if is_string(out_type) \
            or getattr(out_type, "name", "") == "varbinary":
        # varbinary rides the dictionary-string lanes (latin-1-decoded
        # raw bytes), same as varchar
        d, codes = StringDictionary.from_strings(out)
        table = jnp.asarray(codes.astype(np.int32))
        data = jnp.take(table, _lane(col), mode="clip")
        valid = col.valid
        nulls = np.asarray([v is None for v in out], dtype=bool)
        if nulls.any():
            nv = ~jnp.take(jnp.asarray(nulls), _lane(col), mode="clip")
            valid = nv if valid is None else (jnp.asarray(valid) & nv)
        return Column(out_type, data, valid, d)
    # numeric/boolean result: value table gather
    nulls = np.asarray([v is None for v in out], dtype=bool)
    dt = out_type.np_dtype
    tbl = np.asarray([0 if v is None else v for v in out], dtype=dt)
    data = jnp.take(jnp.asarray(tbl), _lane(col), mode="clip")
    valid = col.valid
    if nulls.any():
        nv = ~jnp.take(jnp.asarray(nulls), _lane(col), mode="clip")
        valid = nv if valid is None else (jnp.asarray(valid) & nv)
    return Column(out_type, data, valid)


def _parse_long_decimal_dict(col: Column, t, safe: bool) -> Column:
    """varchar -> DECIMAL(p>18): parse the dictionary host-side into
    128-bit quantized values, emit (lo, hi) gather tables. The single
    -lane _dict_transform overflows int64 here (round-4 verdict repro).
    Reference: spi/type/Decimals.java parse + Int128 representation."""
    from decimal import (Context as _DC, Decimal as _D, InvalidOperation,
                         ROUND_HALF_UP as _RHU)
    from ..ops.int128 import split_const
    ctx = _DC(prec=80)
    los, his, nulls = [], [], []
    for v in col.dictionary.values:
        try:
            q = int(_D(str(v).strip()).scaleb(t.scale, ctx)
                    .to_integral_value(rounding=_RHU))
            lo, hi = split_const(q)
            los.append(lo)
            his.append(hi)
            nulls.append(False)
        except (InvalidOperation, ValueError, OverflowError):
            if not safe:
                raise EvalError(f"Cannot cast '{v}' to {t}") from None
            los.append(0)
            his.append(0)
            nulls.append(True)
    codes = _lane(col)
    lo = jnp.take(jnp.asarray(np.asarray(los, np.int64)), codes,
                  mode="clip")
    hi = jnp.take(jnp.asarray(np.asarray(his, np.int64)), codes,
                  mode="clip")
    valid = col.valid
    nulls = np.asarray(nulls, dtype=bool)
    if nulls.any():
        nv = ~jnp.take(jnp.asarray(nulls), codes, mode="clip")
        valid = nv if valid is None else (jnp.asarray(valid) & nv)
    return Column(t, lo, valid, data2=hi)


def _materialize_strings(col: Column, n: Optional[int] = None) -> List:
    codes = np.asarray(col.data)
    valid = (None if col.valid is None else np.asarray(col.valid))
    out = []
    if col.dictionary is None:
        # dictionary-less (e.g. an all-NULL UNKNOWN constant): only
        # invalid rows are representable as strings -> None
        for i in range(len(codes) if n is None else n):
            out.append(None if valid is None or not valid[i]
                       else str(codes[i]))
        return out
    vals = col.dictionary.values
    for i in range(len(codes) if n is None else n):
        if valid is not None and not valid[i]:
            out.append(None)
        else:
            out.append(str(vals[int(codes[i])]))
    return out


def _row_string_fn(cols: List[Column], fn, out_type: Type) -> Column:
    """Host row-wise fallback for multi-string-column functions."""
    mats = [_materialize_strings(c) for c in cols]
    out = []
    for row in zip(*mats):
        out.append(None if any(v is None for v in row) else fn(*row))
    d, codes = StringDictionary.from_strings(out)
    valid = np.asarray([o is not None for o in out], dtype=bool)
    return Column(out_type, jnp.asarray(codes), None
                  if valid.all() else jnp.asarray(valid), d)


# --------------------------------------------------------------------------
# CASE
# --------------------------------------------------------------------------

def _eval_case(e: CaseExpr, batch: Batch) -> Column:
    branches = [(eval_expr(c, batch), eval_expr(v, batch))
                for c, v in e.whens]
    default = (eval_expr(e.default, batch) if e.default is not None
               else _const_column(Const(None, e.type), batch.capacity))
    if is_string(e.type):
        # unify dictionaries across branches
        cols = [v for _, v in branches] + [default]
        merged = None
        remaps = []
        for c in cols:
            if merged is None:
                merged = c.dictionary
                remaps.append(np.arange(len(merged), dtype=np.int32))
            else:
                merged, _, ro = merged.merge(c.dictionary)
                remaps.append(ro)
        cols = [dc_replace(c, data=jnp.take(jnp.asarray(rm), _lane(c),
                                            mode="clip"),
                           dictionary=merged)
                for c, rm in zip(cols, remaps)]
        branches = [(b[0], c) for b, c in zip(branches, cols[:-1])]
        default = cols[-1]
    taken = jnp.zeros((batch.capacity,), dtype=bool)
    data = _lane(default)
    valid = (jnp.ones((batch.capacity,), bool) if default.valid is None
             else jnp.asarray(default.valid))
    for cond, val in branches:
        c_true = _lane(cond).astype(bool)
        if cond.valid is not None:
            c_true = c_true & jnp.asarray(cond.valid)
        sel = c_true & ~taken
        data = jnp.where(sel, _lane(val).astype(data.dtype), data)
        v = (jnp.ones_like(valid) if val.valid is None
             else jnp.asarray(val.valid))
        valid = jnp.where(sel, v, valid)
        taken = taken | c_true
    return Column(e.type, data, None if _always_true(valid) else valid,
                  default.dictionary if is_string(e.type) else None)


def _always_true(v) -> bool:
    return False  # device value; keep the lane (cheap)


# --------------------------------------------------------------------------
# casts
# --------------------------------------------------------------------------

def _eval_cast(e: Cast, batch: Batch) -> Column:
    src = eval_expr(e.arg, batch)
    return cast_column(src, e.type, e.safe)


def cast_column(src: Column, t: Type, safe: bool = False) -> Column:
    s = src.type
    if s == t:
        return src
    if s == UNKNOWN:
        out = _const_column(Const(None, t), src.capacity)
        return out
    from ..types import ArrayType, MapType, RowType
    if isinstance(t, RowType) and isinstance(s, RowType):
        if len(t.fields) != len(s.fields):
            raise EvalError(f"cannot cast {s} to {t}")
        kids = tuple(cast_column(c, ft, safe)
                     for c, (_, ft) in zip(src.children, t.fields))
        return dc_replace(src, type=t, children=kids)
    if isinstance(t, ArrayType) and isinstance(s, ArrayType):
        return dc_replace(src, type=t,
                          elements=cast_column(src.elements, t.element,
                                               safe))
    if isinstance(t, MapType) and isinstance(s, MapType):
        return dc_replace(
            src, type=t,
            elements=cast_column(src.elements, t.key, safe),
            elements2=cast_column(src.elements2, t.value, safe))
    from ..types import HyperLogLogType, VARBINARY as _VB

    def _stringy(x):
        return is_string(x) or x is _VB or x.name == "varbinary"
    if isinstance(s, HyperLogLogType) and _stringy(t):
        # cast(hll as varbinary/varchar): base64 of this engine's dense
        # framing (ops/hll.py — shared with client result encoding)
        from ..ops.hll import sketches_to_base64
        out = sketches_to_base64(jax.device_get(src.data),
                                 jax.device_get(src.data2),
                                 np.asarray(
                                     jax.device_get(src.elements.data)),
                                 s.bucket_bits)
        dct, codes = StringDictionary.from_strings(out)
        return Column(t, jnp.asarray(codes), src.valid, dct)
    if isinstance(t, HyperLogLogType) and _stringy(s):
        import base64 as _b64
        from ..ops.hll import deserialize_registers, entries_from_dense
        from ..types import INTEGER as _INT
        pool, pool_b, bad = [], [], np.zeros(
            len(src.dictionary.values), bool)
        for i, v in enumerate(src.dictionary.values):
            try:
                regs = deserialize_registers(_b64.b64decode(v))
                pool.append(entries_from_dense(regs))
                pool_b.append(int(regs.shape[0]).bit_length() - 1)
            except Exception as ex:
                if not safe:
                    raise EvalError(
                        f"cannot cast to hyperloglog: {ex}")
                pool.append(np.zeros((0,), np.int32))
                pool_b.append(-1)
                bad[i] = True
        real_b = sorted({b for b in pool_b if b >= 0})
        if len(real_b) > 1:
            raise EvalError(
                "cannot cast a column mixing HyperLogLog precisions "
                f"(bucket bits {real_b})")
        bbits = real_b[0] if real_b else t.bucket_bits
        lens = np.asarray([p.shape[0] for p in pool], np.int64)
        offs = np.cumsum(lens) - lens
        flat = (np.concatenate(pool) if pool
                else np.zeros((0,), np.int32))
        from ..config import capacity_for as _cfor
        pad = _cfor(max(int(flat.shape[0]), 1))
        flat = np.pad(flat, (0, pad - flat.shape[0]))
        codes = jnp.asarray(src.data).astype(jnp.int64)
        starts = jnp.take(jnp.asarray(offs), codes, mode="clip")
        lns = jnp.take(jnp.asarray(lens), codes, mode="clip")
        valid = src.valid
        if bad.any():
            ok = jnp.take(jnp.asarray(~bad), codes, mode="clip")
            valid = ok if valid is None else jnp.asarray(valid) & ok
        return Column(HyperLogLogType(bbits), starts, valid, None,
                      lns, Column(_INT, jnp.asarray(flat)))
    # string source -> parse host-side over dictionary
    if is_string(s) and not is_string(t):
        if isinstance(t, DecimalType) and not t.is_short:
            return _parse_long_decimal_dict(src, t, safe)
        return _dict_transform(src, _parser_for(t, safe), t)
    if is_string(t):
        if is_string(s):
            return dc_replace(src, type=t)
        return _to_varchar(src, t)
    d = _lane(src)
    if isinstance(s, DecimalType):
        if src.data2 is not None:
            # fold the Int128 hi lane in: value = hi*2^64 + u64(lo)
            # (float64 rounding is inherent in a cast to double)
            lo = d.astype(jnp.float64)
            lo = jnp.where(d < 0, lo + 2.0 ** 64, lo)
            sv = (jnp.asarray(src.data2).astype(jnp.float64)
                  * 2.0 ** 64 + lo) / (10.0 ** s.scale)
        else:
            sv = d.astype(jnp.float64) / (10.0 ** s.scale)
        if t.name == "double":
            return Column(t, sv, src.valid)
        if t.name == "real":
            return Column(t, sv.astype(jnp.float32), src.valid)
        if is_integral(t):
            if src.data2 is not None:
                from ..ops import int128 as i128
                lo, _hi = i128.rescale(d.astype(jnp.int64),
                                       jnp.asarray(src.data2)
                                       .astype(jnp.int64), -s.scale)
                return Column(t, lo.astype(t.np_dtype), src.valid)
            return Column(t, _round_half_up(sv).astype(t.np_dtype),
                          src.valid)
        if isinstance(t, DecimalType):
            shift = t.scale - s.scale
            if shift == 0 and t.is_short == s.is_short:
                # precision-only change: keep both Int128 lanes intact
                return dc_replace(src, type=t)
            if src.data2 is not None or not t.is_short:
                from ..ops import int128 as i128
                lo = d.astype(jnp.int64)
                hi = (jnp.asarray(src.data2).astype(jnp.int64)
                      if src.data2 is not None else i128.sign_extend(lo))
                lo, hi = i128.rescale(lo, hi, shift)
                if t.is_short:
                    # in-range values fit the low lane exactly; the
                    # reference raises on overflow, we wrap (documented
                    # in ops/int128.py)
                    return Column(t, lo, src.valid)
                return Column(t, lo, src.valid, data2=hi)
            if shift >= 0:
                nd = d * (10 ** shift)
            else:
                nd = _div_round_half_up(d, 10 ** (-shift))
            return Column(t, nd, src.valid)
        if t is BOOLEAN:
            return Column(t, d != 0, src.valid)
    if isinstance(t, DecimalType):
        if is_integral(s) or s is BOOLEAN:
            if not t.is_short:
                from ..ops import int128 as i128
                lo = d.astype(jnp.int64)
                lo, hi = i128.rescale(lo, i128.sign_extend(lo), t.scale)
                return Column(t, lo, src.valid, data2=hi)
            return Column(t, d.astype(jnp.int64) * (10 ** t.scale),
                          src.valid)
        # float -> decimal, HALF_UP
        scaled = d.astype(jnp.float64) * (10.0 ** t.scale)
        if not t.is_short:
            from ..ops import int128 as i128
            rounded = (jnp.sign(scaled)
                       * jnp.floor(jnp.abs(scaled) + 0.5))
            lo, hi = i128.from_double(rounded)
            return Column(t, lo, src.valid, data2=hi)
        return Column(t, _round_half_up(scaled), src.valid)
    if t.name in ("double", "real"):
        return Column(t, d.astype(t.np_dtype), src.valid)
    if is_integral(t):
        if s.name in ("double", "real"):
            return Column(t, _round_half_up(d.astype(jnp.float64))
                          .astype(t.np_dtype), src.valid)
        return Column(t, d.astype(t.np_dtype), src.valid)
    if t is BOOLEAN:
        return Column(t, d.astype(bool), src.valid)
    if t is DATE and isinstance(s, TimestampType):
        unit = 10 ** (3 - 0) if s.precision == 3 else 10 ** 3
        ms = d  # millis
        return Column(t, jnp.floor_divide(ms, 86400000).astype(jnp.int32),
                      src.valid)
    if isinstance(t, TimestampType) and s is DATE:
        return Column(t, d.astype(jnp.int64) * 86400000, src.valid)
    from ..types import TimestampTZType
    if isinstance(t, TimestampTZType):
        if isinstance(s, TimestampType):       # UTC interpretation
            return Column(t, d.astype(jnp.int64), src.valid,
                          data2=jnp.zeros((src.capacity,), jnp.int64))
        if s is DATE:
            return Column(t, d.astype(jnp.int64) * 86400000, src.valid,
                          data2=jnp.zeros((src.capacity,), jnp.int64))
        if isinstance(s, TimestampTZType):
            return dc_replace(src, type=t)
    if isinstance(s, TimestampTZType):
        local = _tz_local_millis(src)
        if isinstance(t, TimestampType):
            return Column(t, local, src.valid)
        if t is DATE:
            return Column(t, jnp.floor_divide(local, 86400000),
                          src.valid)
    raise EvalError(f"unsupported cast {s} -> {t}")


def _round_half_up(x: jax.Array) -> jax.Array:
    return (jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5)).astype(jnp.int64)


def _div_round_half_up(x: jax.Array, q: int) -> jax.Array:
    sign = jnp.sign(x)
    ax = jnp.abs(x)
    return (sign * ((ax + q // 2) // q)).astype(jnp.int64)


def _parser_for(t: Type, safe: bool):
    import datetime

    def parse(v: str):
        try:
            if t is DATE:
                d = datetime.date.fromisoformat(v.strip())
                return d.toordinal() - datetime.date(1970, 1, 1).toordinal()
            if is_integral(t):
                return int(v.strip())
            if t.name in ("double", "real"):
                return float(v)
            if t is BOOLEAN:
                return v.strip().lower() in ("true", "t", "1")
            if isinstance(t, DecimalType):
                from decimal import Decimal
                q = Decimal(v.strip()).scaleb(t.scale)
                return int(q.to_integral_value())
            if isinstance(t, TimestampType):
                from ..types import iso_timestamp_millis
                return iso_timestamp_millis(v)
            from ..types import TimestampTZType as _TTZ
            if isinstance(t, _TTZ):
                from ..types import iso_timestamp_tz
                ms, off = iso_timestamp_tz(v)
                # single int lane from _dict_transform: encode the
                # UTC instant (offset recovered as 0 — fixed-offset
                # display is normalized to UTC on this path)
                return ms if off is None else ms
            from ..types import TimeType as _TT
            if isinstance(t, _TT):
                from ..types import iso_time_millis
                return iso_time_millis(v)
        except (ValueError, ArithmeticError):
            if safe:
                return None
            raise EvalError(f"Cannot cast '{v}' to {t}") from None
        raise EvalError(f"unsupported cast varchar -> {t}")

    return parse


def _to_varchar(src: Column, t: Type) -> Column:
    s = src.type
    n = src.capacity
    data = np.asarray(src.data)
    valid = None if src.valid is None else np.asarray(src.valid)
    hi_arr = (np.asarray(src.data2)
              if src.data2 is not None and isinstance(s, DecimalType)
              else None)
    out = []
    for i in range(n):
        if valid is not None and not valid[i]:
            out.append(None)
            continue
        v = data[i]
        if s is DATE:
            import datetime
            out.append(str(datetime.date.fromordinal(
                int(v) + datetime.date(1970, 1, 1).toordinal())))
        elif isinstance(s, DecimalType):
            q = int(v)
            if hi_arr is not None:
                q = (int(hi_arr[i]) << 64) + (q & ((1 << 64) - 1))
            if s.scale:
                sign = "-" if q < 0 else ""
                q = abs(q)
                out.append(f"{sign}{q // 10**s.scale}."
                           f"{q % 10**s.scale:0{s.scale}d}")
            else:
                out.append(str(q))
        elif s is BOOLEAN or s.name == "boolean":
            out.append("true" if v else "false")
        elif s.name in ("double", "real"):
            out.append(repr(float(v)))
        elif s.name.endswith("with time zone"):
            import datetime
            off = (int(np.asarray(src.data2)[i])
                   if src.data2 is not None else 0)
            local = (datetime.datetime(1970, 1, 1)
                     + datetime.timedelta(
                         milliseconds=int(v) + off * 60000))
            sign = "+" if off >= 0 else "-"
            out.append(local.strftime("%Y-%m-%d %H:%M:%S.%f")[:-3]
                       + f" {sign}{abs(off) // 60:02d}:"
                         f"{abs(off) % 60:02d}")
        elif s.name.startswith("timestamp"):
            import datetime
            local = (datetime.datetime(1970, 1, 1)
                     + datetime.timedelta(milliseconds=int(v)))
            out.append(local.strftime("%Y-%m-%d %H:%M:%S.%f")[:-3])
        elif s.name.startswith("time("):
            ms = int(v) % 86400000
            out.append(f"{ms // 3600000:02d}:{(ms // 60000) % 60:02d}"
                       f":{(ms // 1000) % 60:02d}.{ms % 1000:03d}")
        else:
            out.append(str(int(v)))
    d, codes = StringDictionary.from_strings(out)
    nv = np.asarray([o is not None for o in out], dtype=bool)
    return Column(t, jnp.asarray(codes),
                  None if nv.all() else jnp.asarray(nv), d)


# --------------------------------------------------------------------------
# calls
# --------------------------------------------------------------------------

def _eval_call(e: Call, batch: Batch) -> Column:
    fn = e.fn
    h = _DISPATCH.get(fn)
    if h is not None:
        return h(e, batch)
    raise EvalError(f"no evaluator for function '{fn}'")


# ---- boolean logic (Kleene) ----------------------------------------------

def _bool_parts(c: Column):
    d = _lane(c).astype(bool)
    v = (jnp.ones_like(d) if c.valid is None else jnp.asarray(c.valid))
    return d, v


def _and(e, batch):
    a, b = (eval_expr(x, batch) for x in e.args)
    ad, av = _bool_parts(a)
    bd, bv = _bool_parts(b)
    data = ad & bd
    # NULL unless either side is definite FALSE
    false_a = av & ~ad
    false_b = bv & ~bd
    valid = (av & bv) | false_a | false_b
    return Column(BOOLEAN, data & valid, valid)


def _or(e, batch):
    a, b = (eval_expr(x, batch) for x in e.args)
    ad, av = _bool_parts(a)
    bd, bv = _bool_parts(b)
    true_a = av & ad
    true_b = bv & bd
    data = true_a | true_b
    valid = (av & bv) | true_a | true_b
    return Column(BOOLEAN, data, valid)


def _not(e, batch):
    a = eval_expr(e.args[0], batch)
    return Column(BOOLEAN, ~_lane(a).astype(bool), a.valid)


def _is_null(e, batch):
    a = eval_expr(e.args[0], batch)
    live = batch.row_valid()
    if a.valid is None:
        return Column(BOOLEAN, jnp.zeros((batch.capacity,), bool), None)
    return Column(BOOLEAN, ~jnp.asarray(a.valid) & live, None)


# ---- comparisons ---------------------------------------------------------

def _align_string_codes(a: Column, b: Column):
    if a.dictionary is b.dictionary:
        return _lane(a), _lane(b), a.dictionary
    merged, ra, rb = a.dictionary.merge(b.dictionary)
    da = jnp.take(jnp.asarray(ra), _lane(a), mode="clip")
    db = jnp.take(jnp.asarray(rb), _lane(b), mode="clip")
    return da, db, merged


def _cmp(op: str):
    def h(e, batch):
        a = eval_expr(e.args[0], batch)
        b = eval_expr(e.args[1], batch)
        valid = _merge_valid(a, b)
        if is_string(a.type):
            if op in ("=", "<>"):
                da, db, _ = _align_string_codes(a, b)
                eq = da == db
                data = eq if op == "=" else ~eq
            else:
                ra = a.dictionary.rank_codes()
                if b.dictionary is a.dictionary:
                    rb_t = ra
                else:
                    merged, ma, mb = a.dictionary.merge(b.dictionary)
                    ranks = merged.rank_codes()
                    da = jnp.take(jnp.asarray(ranks[ma]), _lane(a),
                                  mode="clip")
                    db = jnp.take(jnp.asarray(ranks[mb]), _lane(b),
                                  mode="clip")
                    data = _cmp_lanes(op, da, db)
                    return Column(BOOLEAN, data, valid)
                da = jnp.take(jnp.asarray(ra), _lane(a), mode="clip")
                db = jnp.take(jnp.asarray(ra), _lane(b), mode="clip")
                data = _cmp_lanes(op, da, db)
            return Column(BOOLEAN, data, valid)
        da, db = _lane(a), _lane(b)
        if isinstance(a.type, DecimalType) and (a.data2 is not None
                                                or b.data2 is not None):
            data = _cmp_int128(op, a, b)
        else:
            data = _cmp_lanes(op, da, db)
        return Column(BOOLEAN, data, valid)

    return h


def _cmp_int128(op, a: Column, b: Column):
    """Two's-complement 128-bit comparison over (hi, lo) lanes: signed
    on the high word, unsigned on the low (the sign-bit-flip trick
    turns int64 order into uint64 order — the TPU path has no native
    u64 compare). A side without a hi lane sign-extends its low word.
    Reference: Int128Math/Decimal comparisons in spi/type/Decimals."""
    lo_a = jnp.asarray(a.data).astype(jnp.int64)
    lo_b = jnp.asarray(b.data).astype(jnp.int64)
    hi_a = (jnp.asarray(a.data2).astype(jnp.int64)
            if a.data2 is not None else lo_a >> 63)
    hi_b = (jnp.asarray(b.data2).astype(jnp.int64)
            if b.data2 is not None else lo_b >> 63)
    sbit = jnp.int64(-(2 ** 63))
    ua, ub = lo_a ^ sbit, lo_b ^ sbit
    if op in ("=", "<>"):
        eq = (hi_a == hi_b) & (lo_a == lo_b)
        return eq if op == "=" else ~eq
    lt = (hi_a < hi_b) | ((hi_a == hi_b) & (ua < ub))
    if op == "<":
        return lt
    if op == ">=":
        return ~lt
    gt = (hi_a > hi_b) | ((hi_a == hi_b) & (ua > ub))
    return gt if op == ">" else ~gt


def _cmp_lanes(op, da, db):
    if op == "=":
        return da == db
    if op == "<>":
        return da != db
    if op == "<":
        return da < db
    if op == "<=":
        return da <= db
    if op == ">":
        return da > db
    return da >= db


def _is_distinct_from(e, batch):
    a = eval_expr(e.args[0], batch)
    b = eval_expr(e.args[1], batch)
    live = batch.row_valid()
    av = (live if a.valid is None else jnp.asarray(a.valid) & live)
    bv = (live if b.valid is None else jnp.asarray(b.valid) & live)
    if is_string(a.type):
        da, db, _ = _align_string_codes(a, b)
    else:
        da, db = _lane(a), _lane(b)
    neq = da != db
    data = (av != bv) | (av & bv & neq)
    return Column(BOOLEAN, data, None)


# ---- arithmetic ----------------------------------------------------------

def _arith(op: str):
    def h(e, batch):
        a = eval_expr(e.args[0], batch)
        b = eval_expr(e.args[1], batch)
        valid = _merge_valid(a, b)
        da, db = _lane(a), _lane(b)
        if op == "+":
            data = da + db
        elif op == "-":
            data = da - db
        elif op == "*":
            data = da * db
        elif op == "/":
            if is_integral(e.type):
                sign = jnp.sign(da) * jnp.sign(db)
                data = sign * (jnp.abs(da) //
                               jnp.maximum(jnp.abs(db), 1))
                data = data.astype(da.dtype)
            else:
                data = da / db
        elif op == "%":
            if is_integral(e.type):
                m = jnp.abs(da) % jnp.maximum(jnp.abs(db), 1)
                data = (jnp.sign(da) * m).astype(da.dtype)
            else:
                data = jnp.where(db != 0, jnp.fmod(da, db), jnp.nan)
        return Column(e.type, data.astype(e.type.np_dtype), valid)

    return h


def _decimal_arith(op: str):
    def h(e, batch):
        a = eval_expr(e.args[0], batch)
        b = eval_expr(e.args[1], batch)
        t: DecimalType = e.type
        if (a.data2 is not None) or (b.data2 is not None) or not t.is_short:
            return _decimal_arith_128(op, a, b, t)
        sa = a.type.scale if isinstance(a.type, DecimalType) else 0
        sb = b.type.scale if isinstance(b.type, DecimalType) else 0
        da = _lane(a).astype(jnp.int64)
        db = _lane(b).astype(jnp.int64)
        valid = _merge_valid(a, b)
        if op in ("+", "-"):
            da = da * (10 ** (t.scale - sa))
            db = db * (10 ** (t.scale - sb))
            data = da + db if op == "+" else da - db
        elif op == "*":
            data = da * db
            shift = sa + sb - t.scale
            if shift > 0:
                data = _div_round_half_up(data, 10 ** shift)
        elif op == "/":
            # result scale t.scale: (a/b) * 10^ts = a*10^(ts - sa + sb) / b
            shift = t.scale - sa + sb
            num = da * (10 ** max(shift, 0))
            den = jnp.where(db == 0, 1, db)
            q = num.astype(jnp.float64) / den.astype(jnp.float64)
            if shift < 0:
                q = q / (10 ** (-shift))
            data = _round_half_up(q)
        elif op == "%":
            data = jnp.where(db != 0, da % jnp.where(db == 0, 1, db), 0)
        return Column(t, data, valid)

    return h


def _decimal_arith_128(op: str, a: Column, b: Column,
                       t: "DecimalType") -> Column:
    """Exact Int128 decimal arithmetic over (lo, hi) lanes.
    Reference: spi/type/UnscaledDecimal128Arithmetic.java:42 (add /
    multiply / rescale on Int128, HALF_UP rounding)."""
    from ..ops import int128 as i128
    sa = a.type.scale if isinstance(a.type, DecimalType) else 0
    sb = b.type.scale if isinstance(b.type, DecimalType) else 0
    valid = _merge_valid(a, b)

    def lanes(c):
        lo = _lane(c).astype(jnp.int64)
        hi = (jnp.asarray(c.data2).astype(jnp.int64)
              if c.data2 is not None else i128.sign_extend(lo))
        return lo, hi

    alo, ahi = lanes(a)
    blo, bhi = lanes(b)
    if op in ("+", "-"):
        alo, ahi = i128.rescale(alo, ahi, t.scale - sa)
        blo, bhi = i128.rescale(blo, bhi, t.scale - sb)
        lo, hi = (i128.add128(alo, ahi, blo, bhi) if op == "+"
                  else i128.sub128(alo, ahi, blo, bhi))
    elif op == "*":
        lo, hi = i128.mul128(alo, ahi, blo, bhi)
        lo, hi = i128.rescale(lo, hi, t.scale - sa - sb)
    elif op == "/":
        # (a/b) at scale t.scale: round(a * 10^(t.scale - sa + sb) / b)
        shift = t.scale - sa + sb
        alo, ahi = i128.rescale(alo, ahi, max(shift, 0))
        blo, bhi = i128.rescale(blo, bhi, max(-shift, 0))
        zero = (blo == 0) & (bhi == 0)
        blo_s = jnp.where(zero, 1, blo)
        lo, hi = i128.div128_round_half_up_pair(alo, ahi, blo_s, bhi)
        valid = (~zero if valid is None else valid & ~zero)
    else:  # %
        # operands must agree on the result scale before the divmod
        # (150@s2 mod 30@s1 is 0.20, not the dimensionally-true 2.00)
        alo, ahi = i128.rescale(alo, ahi, t.scale - sa)
        blo, bhi = i128.rescale(blo, bhi, t.scale - sb)
        zero = (blo == 0) & (bhi == 0)
        blo_s = jnp.where(zero, 1, blo)
        _, _, lo, hi = i128.divmod128_trunc(alo, ahi, blo_s, bhi)
        valid = (~zero if valid is None else valid & ~zero)
    if t.is_short:
        return Column(t, lo, valid)
    return Column(t, lo, valid, data2=hi)


def _negate(e, batch):
    a = eval_expr(e.args[0], batch)
    if a.data2 is not None and isinstance(a.type, DecimalType):
        from ..ops import int128 as i128
        lo, hi = i128.neg128(_lane(a).astype(jnp.int64),
                             jnp.asarray(a.data2).astype(jnp.int64))
        return dc_replace(a, data=lo, data2=hi, type=e.type)
    return dc_replace(a, data=-_lane(a), type=e.type)


# ---- scalar math ---------------------------------------------------------

def _unary_np(fn):
    def h(e, batch):
        a = eval_expr(e.args[0], batch)
        return Column(e.type, fn(_lane(a).astype(jnp.float64))
                      .astype(e.type.np_dtype), a.valid)
    return h


def _abs(e, batch):
    a = eval_expr(e.args[0], batch)
    if a.data2 is not None and isinstance(a.type, DecimalType):
        from ..ops import int128 as i128
        lo, hi = i128.abs128(_lane(a).astype(jnp.int64),
                             jnp.asarray(a.data2).astype(jnp.int64))
        return dc_replace(a, data=lo, data2=hi)
    return dc_replace(a, data=jnp.abs(_lane(a)))


def _round(e, batch):
    a = eval_expr(e.args[0], batch)
    t = a.type
    if isinstance(t, DecimalType):
        if a.data2 is not None:
            if len(e.args) == 2:
                arg1 = e.args[1]
                if not isinstance(arg1, Const) or arg1.value is None:
                    raise EvalError(
                        "round(decimal, n) requires a literal n")
                n = int(arg1.value)
            else:
                n = 0
            if n >= t.scale:
                return a
            if t.scale - n > 38:
                # 10^(scale-n) exceeds 128 bits: every value rounds to 0
                z = jnp.zeros_like(_lane(a).astype(jnp.int64))
                return Column(t, z, a.valid, data2=z)
            from ..ops import int128 as i128
            lo = _lane(a).astype(jnp.int64)
            hi = jnp.asarray(a.data2).astype(jnp.int64)
            lo, hi = i128.rescale(lo, hi, -(t.scale - n))
            lo, hi = i128.rescale(lo, hi, t.scale - n)
            return Column(t, lo, a.valid, data2=hi)
        # digits must be a constant for a static result scale
        # (reference: round(decimal, n) with literal n — the common
        # SQL shape; a per-row digit lane has no fixed output type)
        if len(e.args) == 2:
            arg1 = e.args[1]
            if not isinstance(arg1, Const) or arg1.value is None:
                raise EvalError(
                    "round(decimal, n) requires a literal n")
            n = int(arg1.value)
        else:
            n = 0
        d = _lane(a).astype(jnp.int64)
        if n >= t.scale:
            return a
        if t.scale - n > 18:
            # divisor would overflow int64; every int64-lane value
            # rounds to 0 at that magnitude (Trino returns 0 here)
            return Column(t, jnp.zeros_like(d), a.valid)
        div = 10 ** (t.scale - n)
        rounded = _div_round_half_up(d, div) * div
        return Column(t, rounded, a.valid)
    if is_integral(t):
        return a
    if len(e.args) == 2:
        dcol = eval_expr(e.args[1], batch)
        dd = _lane(dcol).astype(jnp.int64)
        scale = jnp.power(10.0, dd.astype(jnp.float64))
    else:
        scale = 1.0
    d = _lane(a).astype(jnp.float64)
    data = jnp.sign(d) * jnp.floor(jnp.abs(d) * scale + 0.5) / scale
    return Column(t, data.astype(t.np_dtype), a.valid)


def _floorceil(which):
    def h(e, batch):
        a = eval_expr(e.args[0], batch)
        t = a.type
        if is_integral(t):
            return a
        d = _lane(a).astype(jnp.float64)
        data = jnp.floor(d) if which == "floor" else jnp.ceil(d)
        return Column(t, data.astype(t.np_dtype), a.valid)
    return h


def _truncate(e, batch):
    a = eval_expr(e.args[0], batch)
    d = _lane(a).astype(jnp.float64)
    return Column(a.type, jnp.trunc(d).astype(a.type.np_dtype), a.valid)


def _sign(e, batch):
    a = eval_expr(e.args[0], batch)
    return Column(a.type, jnp.sign(_lane(a)).astype(a.type.np_dtype),
                  a.valid)


def _power(e, batch):
    a = eval_expr(e.args[0], batch)
    b = eval_expr(e.args[1], batch)
    return Column(DOUBLE, jnp.power(_lane(a).astype(jnp.float64),
                                    _lane(b).astype(jnp.float64)),
                  _merge_valid(a, b))


def _mod(e, batch):
    return _arith("%")(e, batch)


def _greatest_least(which):
    def h(e, batch):
        cols = [eval_expr(a, batch) for a in e.args]
        data = _lane(cols[0])
        for c in cols[1:]:
            d = _lane(c)
            data = jnp.maximum(data, d) if which == "greatest" \
                else jnp.minimum(data, d)
        return Column(e.type, data, _merge_valid(*cols))
    return h


# ---- conditionals --------------------------------------------------------

def _coalesce(e, batch):
    cols = [eval_expr(a, batch) for a in e.args]
    if is_string(e.type):
        merged = None
        remapped = []
        for c in cols:
            if merged is None:
                merged = c.dictionary
                remapped.append(_lane(c))
            else:
                merged, _, ro = merged.merge(c.dictionary)
                remapped.append(jnp.take(jnp.asarray(ro), _lane(c),
                                         mode="clip"))
        data = remapped[-1]
        valid = (jnp.ones((batch.capacity,), bool)
                 if cols[-1].valid is None else jnp.asarray(cols[-1].valid))
        for c, d in zip(reversed(cols[:-1]), reversed(remapped[:-1])):
            v = (jnp.ones_like(valid) if c.valid is None
                 else jnp.asarray(c.valid))
            data = jnp.where(v, d, data)
            valid = v | valid
        return Column(e.type, data, valid, merged)
    data = _lane(cols[-1])
    valid = (jnp.ones((batch.capacity,), bool) if cols[-1].valid is None
             else jnp.asarray(cols[-1].valid))
    for c in reversed(cols[:-1]):
        v = (jnp.ones((batch.capacity,), bool) if c.valid is None
             else jnp.asarray(c.valid))
        data = jnp.where(v, _lane(c).astype(data.dtype), data)
        valid = v | valid
    return Column(e.type, data, valid)


def _nullif(e, batch):
    a = eval_expr(e.args[0], batch)
    b = eval_expr(e.args[1], batch)
    if is_string(a.type):
        da, db, _ = _align_string_codes(a, b)
    else:
        da, db = _lane(a), _lane(b)
    both = _merge_valid(a, b)
    eq = (da == db) if both is None else ((da == db) & both)
    av = (jnp.ones((batch.capacity,), bool) if a.valid is None
          else jnp.asarray(a.valid))
    return dc_replace(a, valid=av & ~eq)


def _if(e, batch):
    c = eval_expr(e.args[0], batch)
    case = CaseExpr(((e.args[0], e.args[1]),), e.args[2], e.type)
    return _eval_case(case, batch)


def _try(e, batch):
    try:
        return eval_expr(e.args[0], batch)
    except EvalError:
        return _const_column(Const(None, e.type), batch.capacity)


# ---- strings -------------------------------------------------------------

def like_to_regex(pattern: str, escape: Optional[str] = None) -> str:
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if escape and ch == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return "".join(out)


def _like(e, batch):
    a = eval_expr(e.args[0], batch)
    pat = e.args[1]
    if not isinstance(pat, Const):
        raise EvalError("LIKE pattern must be constant")
    esc = None
    if len(e.args) > 2:
        if not isinstance(e.args[2], Const):
            raise EvalError("LIKE escape must be constant")
        esc = e.args[2].value
    rx = re.compile(like_to_regex(str(pat.value), esc), re.DOTALL)
    return _dict_transform(a, lambda v: rx.fullmatch(v) is not None,
                           BOOLEAN)


def _regexp_like(e, batch):
    a = eval_expr(e.args[0], batch)
    pat = e.args[1]
    if not isinstance(pat, Const):
        raise EvalError("regexp pattern must be constant")
    rx = re.compile(str(pat.value))
    return _dict_transform(a, lambda v: rx.search(v) is not None, BOOLEAN)


def _string_unary(fn):
    def h(e, batch):
        a = eval_expr(e.args[0], batch)
        return _dict_transform(a, fn, e.type)
    return h


def _length(e, batch):
    a = eval_expr(e.args[0], batch)
    if isinstance(a.type, CharType):
        return _dict_transform(a, lambda v: a.type.length, BIGINT)
    return _dict_transform(a, len, BIGINT)


def _substr(e, batch):
    a = eval_expr(e.args[0], batch)
    rest = [eval_expr(x, batch) for x in e.args[1:]]
    if all(isinstance(x, Const) for x in e.args[1:]):
        start = int(e.args[1].value)
        ln = int(e.args[2].value) if len(e.args) > 2 else None

        def f(v: str):
            i = start - 1 if start > 0 else len(v) + start
            return v[i:] if ln is None else v[i:i + ln]
        return _dict_transform(a, f, e.type)
    # dynamic start/length: host row fallback
    starts = np.asarray(rest[0].data)
    lens = np.asarray(rest[1].data) if len(rest) > 1 else None
    mats = _materialize_strings(a)
    out = []
    for i, v in enumerate(mats):
        if v is None:
            out.append(None)
            continue
        st = int(starts[i])
        j = st - 1 if st > 0 else len(v) + st
        out.append(v[j:] if lens is None else v[j:j + int(lens[i])])
    d, codes = StringDictionary.from_strings(out)
    nv = np.asarray([o is not None for o in out], dtype=bool)
    return Column(e.type, jnp.asarray(codes),
                  None if nv.all() else jnp.asarray(nv), d)


def _concat(e, batch):
    cols = [eval_expr(a, batch) for a in e.args]
    n_dyn = sum(1 for c, a in zip(cols, e.args)
                if not isinstance(a, Const))
    if n_dyn <= 1:
        # single dynamic column: dictionary transform with const parts
        parts = [(c if isinstance(a, Const) else None, a)
                 for c, a in zip(cols, e.args)]
        dyn_idx = next((i for i, a in enumerate(e.args)
                        if not isinstance(a, Const)), None)
        if dyn_idx is None:
            s = "".join(str(a.value) for a in e.args)
            return _const_column(Const(s, VARCHAR), batch.capacity)
        pre = "".join(str(a.value) for a in e.args[:dyn_idx])
        post = "".join(str(a.value) for a in e.args[dyn_idx + 1:])
        return _dict_transform(cols[dyn_idx],
                               lambda v: pre + v + post, e.type)
    return _row_string_fn(cols, lambda *vs: "".join(vs), e.type)


def _strpos(e, batch):
    a = eval_expr(e.args[0], batch)
    pat = e.args[1]
    if not isinstance(pat, Const):
        raise EvalError("strpos needle must be constant")
    needle = str(pat.value)
    return _dict_transform(a, lambda v: v.find(needle) + 1, BIGINT)


def _replace(e, batch):
    a = eval_expr(e.args[0], batch)
    if not all(isinstance(x, Const) for x in e.args[1:]):
        raise EvalError("replace search/replacement must be constant")
    search = str(e.args[1].value)
    repl = str(e.args[2].value) if len(e.args) > 2 else ""
    return _dict_transform(a, lambda v: v.replace(search, repl), e.type)


def _starts_with(e, batch):
    a = eval_expr(e.args[0], batch)
    pat = e.args[1]
    if not isinstance(pat, Const):
        raise EvalError("starts_with prefix must be constant")
    p = str(pat.value)
    return _dict_transform(a, lambda v: v.startswith(p), BOOLEAN)


def _split_part(e, batch):
    a = eval_expr(e.args[0], batch)
    if not all(isinstance(x, Const) for x in e.args[1:]):
        raise EvalError("split_part arguments must be constant")
    delim = str(e.args[1].value)
    idx = int(e.args[2].value)

    def f(v: str):
        parts = v.split(delim)
        return parts[idx - 1] if 1 <= idx <= len(parts) else None
    return _dict_transform(a, f, e.type)


def _pad(which):
    def h(e, batch):
        a = eval_expr(e.args[0], batch)
        size = int(e.args[1].value)
        fill = str(e.args[2].value) if len(e.args) > 2 else " "

        def f(v: str):
            if len(v) >= size:
                return v[:size]
            padn = size - len(v)
            p = (fill * padn)[:padn]
            return p + v if which == "lpad" else v + p
        return _dict_transform(a, f, e.type)
    return h


# ---- datetime ------------------------------------------------------------

def _tz_local_millis(a: Column) -> jax.Array:
    """UTC instant lane + per-value offset minutes -> local millis."""
    ms = _lane(a).astype(jnp.int64)
    if a.data2 is not None:
        ms = ms + jnp.asarray(a.data2).astype(jnp.int64) * 60000
    return ms


def _extract(field: str):
    def h(e, batch):
        from ..types import TimestampTZType
        a = eval_expr(e.args[0], batch)
        if a.type is DATE:
            days = _lane(a).astype(jnp.int64)
        elif isinstance(a.type, TimestampType):
            days = jnp.floor_divide(_lane(a), 86400000)
        elif isinstance(a.type, TimestampTZType):
            days = jnp.floor_divide(_tz_local_millis(a), 86400000)
        else:
            raise EvalError(f"{field}() requires date/timestamp")
        return Column(BIGINT, extract_field(days, field), a.valid)
    return h


def _time_field(field: str):
    def h(e, batch):
        from ..types import TimeType, TimestampTZType
        a = eval_expr(e.args[0], batch)
        if isinstance(a.type, TimestampTZType):
            ms = jnp.mod(_tz_local_millis(a), 86400000)
        elif not isinstance(a.type, (TimestampType, TimeType)):
            return Column(BIGINT, jnp.zeros((batch.capacity,), jnp.int64),
                          a.valid)
        else:
            ms = jnp.mod(_lane(a), 86400000)
        if field == "hour":
            v = ms // 3600000
        elif field == "minute":
            v = (ms // 60000) % 60
        elif field == "second":
            v = (ms // 1000) % 60
        else:
            v = ms % 1000
        return Column(BIGINT, v.astype(jnp.int64), a.valid)
    return h


def _date_interval(op: str):
    def h(e, batch):
        a = eval_expr(e.args[0], batch)
        b = eval_expr(e.args[1], batch)
        days = _lane(a).astype(jnp.int64)
        valid = _merge_valid(a, b)
        iv = _lane(b).astype(jnp.int64)
        if op == "-":
            iv = -iv
        if e.args[1].type is IntervalYearMonth:
            data = add_months(days, iv)
        else:
            data = days + jnp.floor_divide(iv, 86400000)
        return Column(DATE, data.astype(jnp.int32), valid)
    return h


def _ts_interval(op: str):
    def h(e, batch):
        a = eval_expr(e.args[0], batch)
        b = eval_expr(e.args[1], batch)
        ms = _lane(a).astype(jnp.int64)
        iv = _lane(b).astype(jnp.int64)
        if op == "-":
            iv = -iv
        valid = _merge_valid(a, b)
        if e.args[1].type is IntervalYearMonth:
            days = jnp.floor_divide(ms, 86400000)
            tod = ms - days * 86400000
            data = add_months(days, iv) * 86400000 + tod
        else:
            data = ms + iv
        return Column(e.type, data, valid)
    return h


def _date_diff_days(e, batch):
    a = eval_expr(e.args[0], batch)
    b = eval_expr(e.args[1], batch)
    return Column(BIGINT, _lane(a).astype(jnp.int64)
                  - _lane(b).astype(jnp.int64), _merge_valid(a, b))


def _date_trunc(e, batch):
    unit = e.args[0]
    if not isinstance(unit, Const):
        raise EvalError("date_trunc unit must be constant")
    a = eval_expr(e.args[1], batch)
    u = str(unit.value).lower()
    if a.type is DATE:
        return Column(DATE, date_trunc_days(
            _lane(a).astype(jnp.int64), u).astype(jnp.int32), a.valid)
    if isinstance(a.type, TimestampType):
        ms = _lane(a).astype(jnp.int64)
        if u in ("year", "quarter", "month", "week", "day"):
            days = jnp.floor_divide(ms, 86400000)
            return Column(a.type,
                          date_trunc_days(days, u) * 86400000, a.valid)
        q = {"hour": 3600000, "minute": 60000, "second": 1000}[u]
        return Column(a.type, (ms // q) * q, a.valid)
    raise EvalError("date_trunc requires date/timestamp")


def _date_diff(e, batch):
    unit = e.args[0]
    if not isinstance(unit, Const):
        raise EvalError("date_diff unit must be constant")
    u = str(unit.value).lower()
    a = eval_expr(e.args[1], batch)
    b = eval_expr(e.args[2], batch)
    valid = _merge_valid(a, b)

    def days_of(c):
        if c.type is DATE:
            return _lane(c).astype(jnp.int64)
        return jnp.floor_divide(_lane(c), 86400000)

    if u == "day":
        return Column(BIGINT, days_of(b) - days_of(a), valid)
    if u in ("month", "year", "quarter", "week"):
        from ..ops.datetime import civil_from_days
        ya, ma, da_ = civil_from_days(days_of(a))
        yb, mb, db_ = civil_of = civil_from_days(days_of(b))
        months = (yb * 12 + mb) - (ya * 12 + ma)
        months = months - (db_ < da_)
        if u == "month":
            return Column(BIGINT, months, valid)
        if u == "quarter":
            return Column(BIGINT, months // 3, valid)
        if u == "year":
            return Column(BIGINT, months // 12, valid)
        return Column(BIGINT, (days_of(b) - days_of(a)) // 7, valid)
    q = {"hour": 3600000, "minute": 60000, "second": 1000,
         "millisecond": 1}[u]
    return Column(BIGINT, (_lane(b) - _lane(a)) // q, valid)


def _date_add(e, batch):
    unit = e.args[0]
    if not isinstance(unit, Const):
        raise EvalError("date_add unit must be constant")
    u = str(unit.value).lower()
    n = eval_expr(e.args[1], batch)
    a = eval_expr(e.args[2], batch)
    valid = _merge_valid(n, a)
    nn = _lane(n).astype(jnp.int64)
    if a.type is DATE:
        days = _lane(a).astype(jnp.int64)
        if u == "day":
            out = days + nn
        elif u == "week":
            out = days + nn * 7
        elif u in ("month", "quarter", "year"):
            mult = {"month": 1, "quarter": 3, "year": 12}[u]
            out = add_months(days, nn * mult)
        else:
            raise EvalError(f"date_add('{u}') on date not supported")
        return Column(DATE, out.astype(jnp.int32), valid)
    ms = _lane(a).astype(jnp.int64)
    q = {"day": 86400000, "hour": 3600000, "minute": 60000,
         "second": 1000, "millisecond": 1, "week": 7 * 86400000}.get(u)
    if q is not None:
        return Column(a.type, ms + nn * q, valid)
    days = jnp.floor_divide(ms, 86400000)
    tod = ms - days * 86400000
    mult = {"month": 1, "quarter": 3, "year": 12}[u]
    return Column(a.type, add_months(days, nn * mult) * 86400000 + tod,
                  valid)


# ---- float predicates ----------------------------------------------------

def _geo_call(which):
    """Geospatial dispatch into ops/geo.py (vectorized point lanes).
    Numeric arguments (coordinates) coerce to DOUBLE — a DECIMAL
    literal's scaled-integer lane must not leak into geometry math."""
    def h(e, batch):
        from ..ops import geo
        from ..types import GEOMETRY as _G, is_numeric as _isnum
        args = [eval_expr(a, batch) for a in e.args]
        args = [cast_column(a, DOUBLE)
                if a.type is not _G and _isnum(a.type)
                and a.type is not DOUBLE else a
                for a in args]
        try:
            if which == "point":
                return geo.point_column(*args)
            if which == "x":
                return geo.st_x(args[0])
            if which == "y":
                return geo.st_y(args[0])
            if which == "distance":
                return geo.st_distance(*args)
            if which == "fromtext":
                return geo.geometry_from_text(args[0])
            if which == "astext":
                return geo.as_text(args[0])
            if which == "contains":
                return geo.st_contains(*args)
            return geo.great_circle_distance(*args)
        except ValueError as ex:
            raise EvalError(str(ex)) from ex
    return h


def _float_pred(fn):
    def h(e, batch):
        a = eval_expr(e.args[0], batch)
        return Column(BOOLEAN, fn(_lane(a).astype(jnp.float64)), a.valid)
    return h


# ---- unix time + MySQL-style datetime formatting -------------------------
# (operator/scalar/DateTimeFunctions.java: from_unixtime, to_unixtime,
# date_format, date_parse — format codes are the MySQL set)

_MYSQL_FMT = {"Y": "%Y", "y": "%y", "m": "%m", "c": "%m", "d": "%d",
              "e": "%d", "H": "%H", "k": "%H", "h": "%I", "I": "%I",
              "i": "%M", "s": "%S", "S": "%S", "f": "%f", "p": "%p",
              "W": "%A", "a": "%a", "b": "%b", "M": "%B", "j": "%j",
              "T": "%H:%M:%S", "%": "%%"}


def _mysql_to_py_format(fmt: str) -> str:
    out = []
    i = 0
    while i < len(fmt):
        ch = fmt[i]
        if ch == "%" and i + 1 < len(fmt):
            code = fmt[i + 1]
            if code not in _MYSQL_FMT:
                # fail loudly rather than emit plausible wrong output
                raise EvalError(
                    f"unsupported datetime format code '%{code}'")
            out.append(_MYSQL_FMT[code])
            i += 2
        else:
            out.append(ch)
            i += 1
    return "".join(out)


def _const_str(e) -> str:
    from ..rex import Const as _Const
    if not isinstance(e, _Const) or e.value is None:
        raise EvalError("format string must be a constant")
    return str(e.value)


def _from_unixtime(e, batch):
    a = eval_expr(e.args[0], batch)
    ms = jnp.round(_lane(a).astype(jnp.float64) * 1000.0) \
        .astype(jnp.int64)
    return Column(e.type, ms, a.valid)


def _to_unixtime(e, batch):
    a = eval_expr(e.args[0], batch)
    return Column(DOUBLE, _lane(a).astype(jnp.float64) / 1000.0, a.valid)


def _date_format(e, batch):
    import datetime as _dt
    a = eval_expr(e.args[0], batch)
    pyfmt = _mysql_to_py_format(_const_str(e.args[1]))
    ms = np.asarray(a.data).astype(np.int64)   # host materialization
    if a.type is DATE or a.type.name == "date":
        ms = ms * 86400000
    # skip invalid slots: they hold arbitrary sentinels (e.g. the
    # int64 min/max identities of window aggregates) that overflow
    # timedelta
    ok = (np.ones(ms.shape, bool) if a.valid is None
          else np.asarray(a.valid))
    epoch = _dt.datetime(1970, 1, 1)
    out = [(epoch + _dt.timedelta(milliseconds=int(v))).strftime(pyfmt)
           if k else "" for v, k in zip(ms, ok)]
    dic, codes = StringDictionary.from_strings(out)
    return Column(e.type, jnp.asarray(codes), a.valid, dic)


def _date_parse(e, batch):
    import datetime as _dt
    a = eval_expr(e.args[0], batch)
    pyfmt = _mysql_to_py_format(_const_str(e.args[1]))
    epoch = _dt.datetime(1970, 1, 1)

    def parse(v: str):
        try:
            dt = _dt.datetime.strptime(v, pyfmt)
        except ValueError:
            return None
        return int((dt - epoch).total_seconds() * 1000)

    return _dict_transform(a, parse, e.type)


# ---- JSON (operator/scalar/JsonFunctions.java; JSON values travel as
# varchar — the reference's JSON type is a thin wrapper over a slice) ---

_JSON_TOKEN = None


def _json_path_tokens(path: str):
    """Tokenize a JSONPath subset: $.field, $.a.b, $[0], $.a[2].b —
    the shapes JsonExtract.java's generated extractors cover. Raises
    on anything else (the reference's INVALID_FUNCTION_ARGUMENT for
    unsupported paths, never silent misreads)."""
    import re as _re
    tok_re = _re.compile(r"\.([A-Za-z_][A-Za-z0-9_]*)|\[(\d+)\]")
    toks = []
    i = 0
    while i < len(path):
        m = tok_re.match(path, i)
        if m is None:
            raise EvalError(f"invalid JSON path: '${path}'")
        toks.append(m.groups())
        i = m.end()
    return toks


def _json_path_get(doc, toks):
    cur = doc
    for name, idx in toks:
        if name:
            if not isinstance(cur, dict) or name not in cur:
                return None
            cur = cur[name]
        else:
            i = int(idx)
            if not isinstance(cur, list) or i >= len(cur):
                return None
            cur = cur[i]
    return cur


def _json_fn(kind: str):
    def h(e, batch):
        import json as _json
        a = eval_expr(e.args[0], batch)
        path = _const_str(e.args[1]) if len(e.args) > 1 else "$"
        if not path.startswith("$"):
            raise EvalError(f"invalid JSON path: {path}")
        toks = _json_path_tokens(path[1:])

        def f(v: str):
            try:
                doc = _json.loads(v)
            except ValueError:
                return None
            got = _json_path_get(doc, toks)
            if kind == "scalar":
                if got is None or isinstance(got, (dict, list)):
                    return None
                if isinstance(got, bool):
                    return "true" if got else "false"
                return str(got)
            if kind == "extract":
                return None if got is None else _json.dumps(got)
            if kind == "array_length":
                return len(got) if isinstance(got, list) else None
            if kind == "size":
                if got is None:
                    return None
                return len(got) if isinstance(got, (list, dict)) else 0
            return None
        return _dict_transform(a, f, e.type)
    return h


# ---- arrays --------------------------------------------------------------
# spi/block/ArrayBlock redesigned: per-row (start, length) lanes over a
# flat elements Column (columnar.py Column.elements)

def _array_ctor(e, batch):
    from ..types import is_string as _isstr
    items = [eval_expr(a, batch) for a in e.args]
    if items[0].elements is not None or items[0].children is not None:
        # nested ARRAY/MAP/ROW elements: pools merged host-side
        from .complex import array_ctor_complex
        return array_ctor_complex(e, items, batch)
    k = len(items)
    cap = batch.capacity
    dic = None
    if _isstr(items[0].type):
        dic = items[0].dictionary
        remaps = []
        for it in items:
            dic, _, ro = dic.merge(it.dictionary)
            remaps.append(ro)
        # earlier codes stay stable under later merges (merge appends)
        lanes = [jnp.take(jnp.asarray(rm),
                          jnp.asarray(it.data).astype(jnp.int32),
                          mode="clip")
                 for it, rm in zip(items, remaps)]
    else:
        lanes = [jnp.asarray(it.data) for it in items]
    flat = jnp.stack(lanes, axis=1).reshape(-1)
    valid_flat = None
    if any(it.valid is not None for it in items):
        vl = [jnp.ones((cap,), bool) if it.valid is None
              else jnp.asarray(it.valid) for it in items]
        valid_flat = jnp.stack(vl, axis=1).reshape(-1)
    d2 = None
    if any(it.data2 is not None for it in items):
        l2 = [jnp.zeros((cap,), jnp.int64) if it.data2 is None
              else jnp.asarray(it.data2) for it in items]
        d2 = jnp.stack(l2, axis=1).reshape(-1)
    elements = Column(e.type.element, flat, valid_flat, dic, d2)
    start = jnp.arange(cap, dtype=jnp.int64) * k
    length = jnp.full((cap,), k, jnp.int64)
    return Column(e.type, start, None, None, length, elements)


def _cardinality(e, batch):
    a = eval_expr(e.args[0], batch)
    from ..types import HyperLogLogType
    if isinstance(a.type, HyperLogLogType):
        # cardinality(hll): the HLL estimator over each row's sparse
        # entries (reference: operator/scalar/HyperLogLogFunctions.java)
        from ..ops.hll import estimate_from_sparse
        est = estimate_from_sparse(jnp.asarray(a.data),
                                   jnp.asarray(a.data2),
                                   jnp.asarray(a.elements.data),
                                   a.type.bucket_bits)
        return Column(BIGINT, est, a.valid)
    if a.elements is None:
        raise EvalError("cardinality requires an array or map")
    return Column(BIGINT, jnp.asarray(a.data2).astype(jnp.int64),
                  a.valid)


def _empty_approx_set(e, batch):
    """Constant empty HLL sketch per row (HyperLogLogFunctions.java):
    zero sparse entries. Bucket bits match approx_set's default so
    merge(coalesce(approx_set(x), empty_approx_set())) type-checks."""
    from ..ops.hll import APPROX_SET_BUCKET_BITS
    from ..types import HyperLogLogType, INTEGER
    cap = batch.capacity
    empty = Column(INTEGER, jnp.zeros((8,), jnp.int32))
    return Column(HyperLogLogType(APPROX_SET_BUCKET_BITS),
                  jnp.zeros((cap,), jnp.int64), None,
                  None, jnp.zeros((cap,), jnp.int64), empty)


def _element_at(e, batch):
    from ..types import MapType
    if isinstance(e.args[0].type, MapType):
        from .complex import _map_element_at
        return _map_element_at(e, batch)
    a = eval_expr(e.args[0], batch)
    i = eval_expr(e.args[1], batch)
    if a.elements is None:
        raise EvalError("element_at requires an array")
    idx = jnp.asarray(i.data).astype(jnp.int64)
    length = jnp.asarray(a.data2).astype(jnp.int64)
    # 1-based; negative indexes from the end (reference element_at);
    # out of range -> NULL
    pos = jnp.where(idx < 0, length + idx, idx - 1)
    inrange = (pos >= 0) & (pos < length)
    flat_idx = jnp.asarray(a.data).astype(jnp.int64) + \
        jnp.clip(pos, 0, jnp.maximum(length - 1, 0))
    el = a.elements
    edata = jnp.take(jnp.asarray(el.data), flat_idx, mode="clip")
    valid = inrange
    for v in (a.valid, i.valid):
        if v is not None:
            valid = valid & jnp.asarray(v)
    if el.valid is not None:
        valid = valid & jnp.take(jnp.asarray(el.valid), flat_idx,
                                 mode="clip")
    d2 = (None if el.data2 is None
          else jnp.take(jnp.asarray(el.data2), flat_idx, mode="clip"))
    return Column(el.type, edata, valid, el.dictionary, d2)


# ---- dispatch table ------------------------------------------------------

_DISPATCH: Dict[str, Callable] = {
    "and": _and, "or": _or, "not": _not, "is_null": _is_null,
    "is_distinct_from": _is_distinct_from,
    "=": _cmp("="), "<>": _cmp("<>"), "<": _cmp("<"), "<=": _cmp("<="),
    ">": _cmp(">"), ">=": _cmp(">="),
    "+": _arith("+"), "-": _arith("-"), "*": _arith("*"),
    "/": _arith("/"), "%": _arith("%"),
    "decimal_+": _decimal_arith("+"), "decimal_-": _decimal_arith("-"),
    "decimal_*": _decimal_arith("*"), "decimal_/": _decimal_arith("/"),
    "decimal_%": _decimal_arith("%"),
    "negate": _negate, "abs": _abs, "round": _round,
    "floor": _floorceil("floor"), "ceil": _floorceil("ceil"),
    "ceiling": _floorceil("ceil"), "truncate": _truncate, "sign": _sign,
    "sqrt": _unary_np(jnp.sqrt), "cbrt": _unary_np(jnp.cbrt),
    "exp": _unary_np(jnp.exp), "ln": _unary_np(jnp.log),
    "log2": _unary_np(jnp.log2), "log10": _unary_np(jnp.log10),
    "sin": _unary_np(jnp.sin), "cos": _unary_np(jnp.cos),
    "tan": _unary_np(jnp.tan), "asin": _unary_np(jnp.arcsin),
    "acos": _unary_np(jnp.arccos), "atan": _unary_np(jnp.arctan),
    "sinh": _unary_np(jnp.sinh), "cosh": _unary_np(jnp.cosh),
    "tanh": _unary_np(jnp.tanh),
    "degrees": _unary_np(jnp.degrees), "radians": _unary_np(jnp.radians),
    "power": _power, "pow": _power, "mod": _mod,
    "greatest": _greatest_least("greatest"),
    "least": _greatest_least("least"),
    "is_nan": _float_pred(jnp.isnan),
    "st_point": _geo_call("point"), "st_x": _geo_call("x"),
    "st_y": _geo_call("y"), "st_distance": _geo_call("distance"),
    "st_geometryfromtext": _geo_call("fromtext"),
    "st_astext": _geo_call("astext"),
    "st_contains": _geo_call("contains"),
    "great_circle_distance": _geo_call("gcd"),
    "is_finite": _float_pred(jnp.isfinite),
    "is_infinite": _float_pred(jnp.isinf),
    "coalesce": _coalesce, "nullif": _nullif, "if": _if, "try": _try,
    "like": _like, "regexp_like": _regexp_like,
    "lower": _string_unary(str.lower), "upper": _string_unary(str.upper),
    "trim": _string_unary(str.strip), "ltrim": _string_unary(str.lstrip),
    "rtrim": _string_unary(str.rstrip),
    "reverse": _string_unary(lambda v: v[::-1]),
    "length": _length, "substring": _substr, "substr": _substr,
    "concat": _concat, "strpos": _strpos, "position": _strpos,
    "replace": _replace, "starts_with": _starts_with,
    "split_part": _split_part, "lpad": _pad("lpad"), "rpad": _pad("rpad"),
    "year": _extract("year"), "month": _extract("month"),
    "quarter": _extract("quarter"), "week": _extract("week"),
    "day": _extract("day"), "day_of_month": _extract("day"),
    "day_of_week": _extract("day_of_week"), "dow": _extract("day_of_week"),
    "day_of_year": _extract("day_of_year"), "doy": _extract("day_of_year"),
    "hour": _time_field("hour"), "minute": _time_field("minute"),
    "second": _time_field("second"), "millisecond":
        _time_field("millisecond"),
    "date_add_interval": _date_interval("+"),
    "date_sub_interval": _date_interval("-"),
    "ts_add_interval": _ts_interval("+"),
    "ts_sub_interval": _ts_interval("-"),
    "date_diff_days": _date_diff_days,
    "date_trunc": _date_trunc, "date_diff": _date_diff,
    "date_add": _date_add,
    "$array": _array_ctor, "cardinality": _cardinality,
    "empty_approx_set": _empty_approx_set,
    "element_at": _element_at,
    "from_unixtime": _from_unixtime, "to_unixtime": _to_unixtime,
    "date_format": _date_format, "date_parse": _date_parse,
    "json_extract_scalar": _json_fn("scalar"),
    "json_extract": _json_fn("extract"),
    "json_array_length": _json_fn("array_length"),
    "json_size": _json_fn("size"),
}

# --------------------------------------------------------------------------
# bitwise / crypto / URL / misc scalar breadth
# (operator/scalar/BitwiseFunctions.java, VarbinaryFunctions.java
#  digests, UrlFunctions.java, MathFunctions 2-arg forms)
# --------------------------------------------------------------------------

def _bitwise(op):
    def f(e, batch):
        a = eval_expr(e.args[0], batch)
        b = eval_expr(e.args[1], batch)
        x = _lane(a).astype(jnp.int64)
        y = _lane(b).astype(jnp.int64)
        if op == "and":
            d = x & y
        elif op == "or":
            d = x | y
        elif op == "xor":
            d = x ^ y
        elif op == "lshift":
            d = x << y
        else:
            d = x >> y
        return Column(BIGINT, d, _merge_valid(a, b))
    return f


def _bitwise_not(e, batch):
    a = eval_expr(e.args[0], batch)
    return Column(BIGINT, ~_lane(a).astype(jnp.int64), a.valid)


def _bit_count(e, batch):
    a = eval_expr(e.args[0], batch)
    bits = eval_expr(e.args[1], batch) if len(e.args) > 1 else None
    x = _lane(a).astype(jnp.int64).view(jnp.uint64)
    nbits = (jnp.asarray(bits.data).astype(jnp.int64)
             if bits is not None else jnp.int64(64))
    # mask to the low n bits (sign extension counts for negatives)
    mask = jnp.where(nbits >= 64, jnp.uint64(0xFFFFFFFFFFFFFFFF),
                     (jnp.uint64(1) << nbits.astype(jnp.uint64))
                     - jnp.uint64(1))
    v = x & mask
    cnt = jnp.zeros(v.shape, jnp.int64)
    for shift in range(0, 64, 8):
        byte = ((v >> jnp.uint64(shift)) &
                jnp.uint64(0xFF)).astype(jnp.int32)
        tbl = jnp.asarray([bin(i).count("1") for i in range(256)],
                          jnp.int64)
        cnt = cnt + jnp.take(tbl, byte)
    valid = a.valid
    if bits is not None:
        valid = _merge_valid(a, bits)
    return Column(BIGINT, cnt, valid)


def _xxh64_py(data: bytes, seed: int = 0) -> int:
    """Reference xxHash64 (public domain algorithm), used when the
    native serde library is absent."""
    P1, P2, P3 = (0x9E3779B185EBCA87, 0xC2B2AE3D27D4EB4F,
                  0x165667B19E3779F9)
    P4, P5 = 0x85EBCA77C2B2AE63, 0x27D4EB2F165667C5
    M = 0xFFFFFFFFFFFFFFFF

    def rotl(x, r):
        return ((x << r) | (x >> (64 - r))) & M
    n = len(data)
    i = 0
    if n >= 32:
        v1 = (seed + P1 + P2) & M
        v2 = (seed + P2) & M
        v3 = seed & M
        v4 = (seed - P1) & M
        while i + 32 <= n:
            for j, vv in enumerate((v1, v2, v3, v4)):
                lane = int.from_bytes(data[i + j * 8:i + j * 8 + 8],
                                      "little")
                vv = (vv + lane * P2) & M
                vv = (rotl(vv, 31) * P1) & M
                if j == 0:
                    v1 = vv
                elif j == 1:
                    v2 = vv
                elif j == 2:
                    v3 = vv
                else:
                    v4 = vv
            i += 32
        h = (rotl(v1, 1) + rotl(v2, 7) + rotl(v3, 12)
             + rotl(v4, 18)) & M
        for vv in (v1, v2, v3, v4):
            vv = (rotl((vv * P2) & M, 31) * P1) & M
            h = (((h ^ vv) * P1) + P4) & M
    else:
        h = (seed + P5) & M
    h = (h + n) & M
    while i + 8 <= n:
        lane = int.from_bytes(data[i:i + 8], "little")
        k = (rotl((lane * P2) & M, 31) * P1) & M
        h = ((rotl(h ^ k, 27) * P1) + P4) & M
        i += 8
    if i + 4 <= n:
        lane = int.from_bytes(data[i:i + 4], "little")
        h = ((rotl(h ^ ((lane * P1) & M), 23) * P2) + P3) & M
        i += 4
    while i < n:
        h = (rotl(h ^ ((data[i] * P5) & M), 11) * P1) & M
        i += 1
    h ^= h >> 33
    h = (h * P2) & M
    h ^= h >> 29
    h = (h * P3) & M
    h ^= h >> 32
    return h


def _digest(algo):
    def f(e, batch):
        import hashlib
        a = eval_expr(e.args[0], batch)
        return _dict_transform(
            a, lambda v: hashlib.new(algo, v.encode()).hexdigest(),
            e.type)
    return f


def _crc32(e, batch):
    import zlib
    a = eval_expr(e.args[0], batch)
    return _dict_transform(a, lambda v: zlib.crc32(v.encode()), BIGINT)


def _xxhash64_fn(e, batch):
    a = eval_expr(e.args[0], batch)
    from ..serde import _load_native
    lib = _load_native()

    def h(v: str) -> int:
        raw = v.encode()
        u = (int(lib.tt_xxh64(raw, len(raw), 0)) if lib is not None
             else _xxh64_py(raw))
        return u - (1 << 64) if u >= (1 << 63) else u
    return _dict_transform(a, h, BIGINT)


def _to_hex(e, batch):
    a = eval_expr(e.args[0], batch)
    from ..types import is_string as _iss
    if _iss(a.type):
        return _dict_transform(
            a, lambda v: v.encode().hex().upper(), e.type)
    d = _lane(a).astype(jnp.int64)
    # bigint -> 16-digit hex via host transform on unique-ish lanes is
    # wasteful; do it columnar on host
    vals = np.asarray(d)
    out = [format(int(v) & ((1 << 64) - 1), "X") for v in vals]
    dct, codes = StringDictionary.from_strings(out)
    return Column(e.type, jnp.asarray(codes), a.valid, dct)


def _from_hex(e, batch):
    a = eval_expr(e.args[0], batch)
    return _dict_transform(
        a, lambda v: bytes.fromhex(v).decode("utf-8", "replace"),
        e.type)


def _url_part(which):
    def f(e, batch):
        from urllib.parse import urlsplit
        a = eval_expr(e.args[0], batch)

        def g(v: str):
            try:
                u = urlsplit(v)
            except ValueError:
                return None
            if which == "protocol":
                return u.scheme or None
            if which == "host":
                return u.hostname
            if which == "port":
                return u.port
            if which == "path":
                return u.path
            if which == "query":
                return u.query or None
            return u.fragment or None
        return _dict_transform(a, g, e.type)
    return f


def _url_extract_parameter(e, batch):
    from urllib.parse import parse_qs, urlsplit
    if not isinstance(e.args[1], Const):
        raise EvalError("url_extract_parameter: name must be constant")
    a = eval_expr(e.args[0], batch)
    name = e.args[1].value

    def g(v: str):
        try:
            qs = parse_qs(urlsplit(v).query,
                          keep_blank_values=True)
        except ValueError:
            return None
        vals = qs.get(name)
        return vals[0] if vals else None
    return _dict_transform(a, g, e.type)


def _url_codec(which):
    def f(e, batch):
        from urllib.parse import quote_plus, unquote_plus
        a = eval_expr(e.args[0], batch)
        fn = quote_plus if which == "encode" else unquote_plus
        return _dict_transform(a, fn, e.type)
    return f


def _translate(e, batch):
    if not (isinstance(e.args[1], Const) and isinstance(e.args[2],
                                                        Const)):
        raise EvalError("translate: from/to must be constants")
    a = eval_expr(e.args[0], batch)
    table = {}
    f_s, t_s = e.args[1].value, e.args[2].value
    for i, ch in enumerate(f_s):
        table[ord(ch)] = t_s[i] if i < len(t_s) else None
    return _dict_transform(a, lambda v: v.translate(table), e.type)


def _log_b(e, batch):
    a = eval_expr(e.args[0], batch)
    b = eval_expr(e.args[1], batch)
    d = jnp.log(_lane(b).astype(jnp.float64)) / \
        jnp.log(_lane(a).astype(jnp.float64))
    return Column(DOUBLE, d, _merge_valid(a, b))


def _const_double(val):
    def f(e, batch):
        return Column(DOUBLE, jnp.full((batch.capacity,), val,
                                       jnp.float64), None)
    return f


def _random_fn(e, batch):
    cap = batch.capacity
    if e.args:
        n = eval_expr(e.args[0], batch)
        bound = np.asarray(_lane(n))
        vals = np.random.randint(
            0, np.maximum(bound.astype(np.int64), 1))
        return Column(BIGINT, jnp.asarray(vals), n.valid)
    return Column(DOUBLE, jnp.asarray(np.random.uniform(size=cap)), None)


def _atan2(e, batch):
    a = eval_expr(e.args[0], batch)
    b = eval_expr(e.args[1], batch)
    d = jnp.arctan2(_lane(a).astype(jnp.float64),
                    _lane(b).astype(jnp.float64))
    return Column(DOUBLE, d, _merge_valid(a, b))


def _chr(e, batch):
    a = eval_expr(e.args[0], batch)
    vals = np.asarray(_lane(a)).astype(np.int64)
    out = [chr(int(v)) if 0 <= v < 0x110000 else "" for v in vals]
    dct, codes = StringDictionary.from_strings(out)
    return Column(e.type, jnp.asarray(codes), a.valid, dct)


def _codepoint(e, batch):
    a = eval_expr(e.args[0], batch)
    return _dict_transform(
        a, lambda v: ord(v[0]) if v else None, BIGINT)


def _concat_ws(e, batch):
    """concat_ws(sep, s1, s2, ...): NULL args are skipped; a NULL
    separator yields NULL (reference: ConcatWsFunction.java)."""
    cols = [eval_expr(a, batch) for a in e.args]
    mats = [_materialize_strings(c) for c in cols]
    out = []
    for row in zip(*mats):
        sep = row[0]
        out.append(None if sep is None
                   else sep.join(v for v in row[1:] if v is not None))
    dct, codes = StringDictionary.from_strings(out)
    valid = np.asarray([o is not None for o in out], dtype=bool)
    return Column(e.type, jnp.asarray(codes),
                  None if valid.all() else jnp.asarray(valid), dct)


def _java_format_value(spec: str, conv: str, v):
    """One %-directive of Java String.format, via Python's format
    mini-language (subset: flags - 0 ,  width, precision; conversions
    s d f e x o b)."""
    grouping = "," in spec
    spec = spec.replace(",", "")
    align = ""
    if spec.startswith("-"):
        align = "<"
        spec = spec[1:]
    py = align + spec
    if conv in ("d", "x", "o"):
        if conv == "d":
            return format(int(v), py + (",d" if grouping else "d"))
        return format(int(v), py + conv)
    if conv in ("f", "e", "g"):
        return format(float(v), py + ("," if grouping else "") + conv)
    if conv == "b":
        return "true" if v else "false"
    return format(str(v), py + "s")


def _format_fn(e, batch):
    if not isinstance(e.args[0], Const):
        raise EvalError("format: the format string must be constant")
    fmt = e.args[0].value
    import re as _re
    parts = _re.split(r"(%[-,0-9.]*[a-zA-Z]|%%)", fmt)
    cols = [eval_expr(a, batch) for a in e.args[1:]]
    from ..types import is_string as _iss
    mats = []
    for c in cols:
        if _iss(c.type):
            mats.append(_materialize_strings(c))
        else:
            d = np.asarray(c.data)
            valid = (np.ones(len(d), bool) if c.valid is None
                     else np.asarray(c.valid))
            if isinstance(c.type, DecimalType):
                hi = (None if c.data2 is None
                      else np.asarray(c.data2))
                scale = 10 ** c.type.scale

                def unscale(i):
                    v = int(d[i])
                    if hi is not None:
                        v = (int(hi[i]) << 64) | (v & ((1 << 64) - 1))
                    return v / scale
                mats.append([unscale(i) if valid[i] else None
                             for i in range(len(d))])
            else:
                mats.append([d[i].item() if valid[i] else None
                             for i in range(len(d))])
    out = []
    for row in zip(*mats) if mats else [()] * batch.capacity:
        ai = 0
        pieces = []
        bad = False
        for p in parts:
            if p == "%%":
                pieces.append("%")
            elif p.startswith("%") and len(p) > 1:
                v = row[ai] if ai < len(row) else None
                ai += 1
                if v is None:
                    bad = True
                    break
                pieces.append(_java_format_value(p[1:-1], p[-1], v))
            else:
                pieces.append(p)
        out.append(None if bad else "".join(pieces))
    dct, codes = StringDictionary.from_strings(out)
    valid = np.asarray([o is not None for o in out], dtype=bool)
    return Column(e.type, jnp.asarray(codes),
                  None if valid.all() else jnp.asarray(valid), dct)


def _levenshtein(a: str, b: str) -> int:
    if len(a) < len(b):
        a, b = b, a
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i]
        for j, cb in enumerate(b, 1):
            cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                           prev[j - 1] + (ca != cb)))
        prev = cur
    return prev[-1]


def _str_distance(kind):
    def f(e, batch):
        a = eval_expr(e.args[0], batch)
        b = eval_expr(e.args[1], batch)
        ma, mb = _materialize_strings(a), _materialize_strings(b)
        out = np.zeros(len(ma), np.int64)
        valid = np.ones(len(ma), bool)
        for i, (x, y) in enumerate(zip(ma, mb)):
            if x is None or y is None:
                valid[i] = False
            elif kind == "hamming":
                if len(x) != len(y):
                    raise EvalError("hamming_distance: strings must "
                                    "have the same length")
                out[i] = sum(c1 != c2 for c1, c2 in zip(x, y))
            else:
                out[i] = _levenshtein(x, y)
        return Column(BIGINT, jnp.asarray(out),
                      None if valid.all() else jnp.asarray(valid))
    return f


def _regexp_pattern(e, idx=1):
    if not isinstance(e.args[idx], Const):
        raise EvalError("regexp pattern must be constant")
    import re as _re
    return _re.compile(e.args[idx].value)


def _regexp_extract(e, batch):
    a = eval_expr(e.args[0], batch)
    pat = _regexp_pattern(e)
    group = 0
    if len(e.args) > 2:
        if not isinstance(e.args[2], Const):
            raise EvalError("regexp_extract: group must be constant")
        group = int(e.args[2].value)

    def g(v: str):
        m = pat.search(v)
        return None if m is None else m.group(group)
    return _dict_transform(a, g, e.type)


def _regexp_replace(e, batch):
    import re as _re
    a = eval_expr(e.args[0], batch)
    pat = _regexp_pattern(e)
    repl = ""
    if len(e.args) > 2:
        if not isinstance(e.args[2], Const):
            raise EvalError("regexp_replace: replacement must be "
                            "constant")
        # Java replacement syntax: $1 / ${name} -> Python \1 / \g<name>
        repl = _re.sub(r"\$\{(\w+)\}", r"\\g<\1>",
                       _re.sub(r"\$(\d+)", r"\\\1", e.args[2].value))
    return _dict_transform(a, lambda v: pat.sub(repl, v), e.type)


def _typeof(e, batch):
    t = str(e.args[0].type)
    dct, codes = StringDictionary.from_strings([t] * batch.capacity)
    return Column(e.type, jnp.asarray(codes), None, dct)


def _width_bucket(e, batch):
    x = eval_expr(e.args[0], batch)
    lo = eval_expr(e.args[1], batch)
    hi = eval_expr(e.args[2], batch)
    n = eval_expr(e.args[3], batch)
    xd = _lane(x).astype(jnp.float64)
    lod = _lane(lo).astype(jnp.float64)
    hid = _lane(hi).astype(jnp.float64)
    nd = _lane(n).astype(jnp.int64)
    width = (hid - lod) / nd
    fwd = jnp.clip(jnp.floor((xd - lod) / width).astype(jnp.int64) + 1,
                   0, nd + 1)
    rev = jnp.clip(jnp.floor((lod - xd) /
                             ((lod - hid) / nd)).astype(jnp.int64) + 1,
                   0, nd + 1)
    out = jnp.where(hid >= lod, fwd, rev)
    return Column(BIGINT, out, _merge_valid(x, lo, hi, n))


def _year_of_week(e, batch):
    """ISO 8601 week-year: the calendar year of the week's Thursday."""
    a = eval_expr(e.args[0], batch)
    if a.type is DATE:
        days = _lane(a).astype(jnp.int64)
    elif isinstance(a.type, TimestampType):
        days = jnp.floor_divide(_lane(a), 86400000)
    else:
        raise EvalError("year_of_week() requires date/timestamp")
    monday_idx = jnp.mod(days + 3, 7)          # 0 = Monday
    thursday = days - monday_idx + 3
    return Column(BIGINT, extract_field(thursday, "year"), a.valid)


def _current_date(e, batch):
    import time as _time
    days = int(_time.time() // 86400)
    return Column(e.type, jnp.full((batch.capacity,), days, jnp.int64),
                  None)


def _now_fn(e, batch):
    import time as _time
    ms = int(_time.time() * 1000)
    return Column(e.type, jnp.full((batch.capacity,), ms, jnp.int64),
                  None)


def _current_time_fn(e, batch):
    import time as _time
    ms = int(_time.time() * 1000) % 86400000
    return Column(e.type, jnp.full((batch.capacity,), ms, jnp.int64),
                  None)


def _date_fn(e, batch):
    a = eval_expr(e.args[0], batch)
    return cast_column(a, e.type)


def _normalize_fn(e, batch):
    import unicodedata
    a = eval_expr(e.args[0], batch)
    form = "NFC"
    if len(e.args) > 1:
        if not isinstance(e.args[1], Const):
            raise EvalError("normalize: form must be constant")
        form = str(e.args[1].value).upper()
    if form not in ("NFC", "NFD", "NFKC", "NFKD"):
        raise EvalError(f"normalize: invalid form {form}")
    return _dict_transform(
        a, lambda v: unicodedata.normalize(form, v), e.type)


_BASE_DIGITS = "0123456789abcdefghijklmnopqrstuvwxyz"


def _to_base(e, batch):
    a = eval_expr(e.args[0], batch)
    r = eval_expr(e.args[1], batch)
    vals = np.asarray(_lane(a)).astype(np.int64)
    radix = np.asarray(_lane(r)).astype(np.int64)
    out = []
    for v, rx in zip(vals, radix):
        rx = int(rx)
        if not 2 <= rx <= 36:
            raise EvalError("to_base: radix must be in [2, 36]")
        v = int(v)
        neg, v = v < 0, abs(v)
        digits = ""
        while True:
            digits = _BASE_DIGITS[v % rx] + digits
            v //= rx
            if v == 0:
                break
        out.append(("-" if neg else "") + digits)
    dct, codes = StringDictionary.from_strings(out)
    return Column(e.type, jnp.asarray(codes), _merge_valid(a, r), dct)


def _from_base(e, batch):
    a = eval_expr(e.args[0], batch)
    r = eval_expr(e.args[1], batch)
    if not isinstance(e.args[1], Const):
        raise EvalError("from_base: radix must be constant")
    radix = int(e.args[1].value)
    if not 2 <= radix <= 36:
        raise EvalError("from_base: radix must be in [2, 36]")
    return _dict_transform(a, lambda v: int(v, radix), BIGINT)


def _zone_offsets_for(zone: str, instants: np.ndarray) -> np.ndarray:
    """Per-value offset minutes for a zone string; IANA names resolve
    per instant (DST-correct), fixed offsets are constant."""
    from ..types import zone_offset_minutes
    z = zone.strip()
    if "/" not in z:
        return np.full(instants.shape, zone_offset_minutes(z), np.int64)
    import datetime
    from zoneinfo import ZoneInfo
    zi = ZoneInfo(z)
    epoch = datetime.datetime(1970, 1, 1,
                              tzinfo=datetime.timezone.utc)
    out = np.empty(instants.shape, np.int64)
    for i, v in enumerate(instants):
        off = (epoch + datetime.timedelta(milliseconds=int(v))
               ).astimezone(zi).utcoffset()
        out[i] = int(off.total_seconds() // 60)
    return out


def _at_timezone(e, batch):
    """AT TIME ZONE: same instant, new display zone (reference:
    operator/scalar/AtTimeZone.java)."""
    from ..types import TimestampTZType, TimestampType as _TT
    a = eval_expr(e.args[0], batch)
    if not isinstance(e.args[1], Const):
        raise EvalError("AT TIME ZONE: zone must be constant")
    zone = str(e.args[1].value)
    if isinstance(a.type, _TT):
        # plain timestamp: interpret as UTC instant
        a = dc_replace(a, type=TimestampTZType(a.type.precision),
                       data2=jnp.zeros((a.capacity,), jnp.int64))
    instants = np.asarray(a.data)
    offs = _zone_offsets_for(zone, instants)
    return dc_replace(a, data2=jnp.asarray(offs))


def _with_timezone(e, batch):
    """with_timezone(timestamp, zone): the wall-clock value read in
    that zone (instant shifts)."""
    from ..types import TimestampTZType
    a = eval_expr(e.args[0], batch)
    if not isinstance(e.args[1], Const):
        raise EvalError("with_timezone: zone must be constant")
    zone = str(e.args[1].value)
    local = np.asarray(a.data)
    offs = _zone_offsets_for(zone, local)  # approx for DST edges
    instant = local - offs * 60000
    return Column(TimestampTZType(getattr(a.type, "precision", 3)),
                  jnp.asarray(instant), a.valid,
                  data2=jnp.asarray(offs))


def _to_iso8601(e, batch):
    from ..types import TimestampTZType
    a = eval_expr(e.args[0], batch)
    import datetime
    epoch = datetime.datetime(1970, 1, 1)
    vals = np.asarray(a.data)
    out = []
    if a.type is DATE:
        d0 = datetime.date(1970, 1, 1).toordinal()
        for v in vals:
            out.append(datetime.date.fromordinal(int(v) + d0)
                       .isoformat())
    elif isinstance(a.type, TimestampTZType):
        offs = (np.asarray(a.data2) if a.data2 is not None
                else np.zeros(len(vals), np.int64))
        for v, o in zip(vals, offs):
            local = epoch + datetime.timedelta(
                milliseconds=int(v) + int(o) * 60000)
            sign = "+" if o >= 0 else "-"
            out.append(local.isoformat(timespec="milliseconds")
                       + f"{sign}{abs(int(o)) // 60:02d}:"
                         f"{abs(int(o)) % 60:02d}")
    else:
        for v in vals:
            out.append((epoch + datetime.timedelta(milliseconds=int(v))
                        ).isoformat(timespec="milliseconds"))
    dct, codes = StringDictionary.from_strings(out)
    return Column(VARCHAR, jnp.asarray(codes), a.valid, dct)


_DISPATCH_EXTRA = {
    "at_timezone": _at_timezone,
    "with_timezone": _with_timezone,
    "to_iso8601": _to_iso8601,
    "pi": _const_double(float(np.pi)),
    "e": _const_double(float(np.e)),
    "nan": _const_double(float("nan")),
    "infinity": _const_double(float("inf")),
    "random": _random_fn, "rand": _random_fn,
    "atan2": _atan2,
    "chr": _chr, "codepoint": _codepoint,
    "concat_ws": _concat_ws,
    "format": _format_fn,
    "hamming_distance": _str_distance("hamming"),
    "levenshtein_distance": _str_distance("levenshtein"),
    "regexp_extract": _regexp_extract,
    "regexp_replace": _regexp_replace,
    "typeof": _typeof,
    "width_bucket": _width_bucket,
    "year_of_week": _year_of_week, "yow": _year_of_week,
    "current_date": _current_date,
    "now": _now_fn, "current_timestamp": _now_fn,
    "localtimestamp": _now_fn,
    "current_time": _current_time_fn, "localtime": _current_time_fn,
    "date": _date_fn,
    "normalize": _normalize_fn,
    "to_base": _to_base, "from_base": _from_base,
    "bitwise_and": _bitwise("and"), "bitwise_or": _bitwise("or"),
    "bitwise_xor": _bitwise("xor"),
    "bitwise_left_shift": _bitwise("lshift"),
    "bitwise_right_shift": _bitwise("rshift"),
    "bitwise_not": _bitwise_not, "bit_count": _bit_count,
    "md5": _digest("md5"), "sha1": _digest("sha1"),
    "sha256": _digest("sha256"), "sha512": _digest("sha512"),
    "crc32": _crc32, "xxhash64": _xxhash64_fn,
    "to_hex": _to_hex, "from_hex": _from_hex,
    "url_extract_protocol": _url_part("protocol"),
    "url_extract_host": _url_part("host"),
    "url_extract_port": _url_part("port"),
    "url_extract_path": _url_part("path"),
    "url_extract_query": _url_part("query"),
    "url_extract_fragment": _url_part("fragment"),
    "url_extract_parameter": _url_extract_parameter,
    "url_encode": _url_codec("encode"),
    "url_decode": _url_codec("decode"),
    "translate": _translate,
    "log": _log_b,
}
_DISPATCH.update(_DISPATCH_EXTRA)


# complex-type (ARRAY/MAP/ROW) + higher-order functions evaluate
# host-side (see exec/complex.py module docstring for why)
from . import complex as _complex  # noqa: E402

for _name, _fn in _complex.DISPATCH.items():
    _DISPATCH.setdefault(_name, _fn)


# --------------------------------------------------------------------------
# round-4 scalar breadth: HMAC, binary codecs, joda datetime, bar charts,
# porter stemmer (reference: operator/scalar/{HmacFunctions,
# VarbinaryFunctions,DateTimeFunctions,ColorFunctions,WordStemFunction}.java)
# --------------------------------------------------------------------------

def _carried_bytes(typ) -> Callable[[str], bytes]:
    """varbinary values are carried as latin-1-decoded strings
    (_num_to_binary); varchar is real text -> utf-8."""
    if getattr(typ, "name", "") == "varbinary":
        return lambda s: s.encode("latin-1")
    return lambda s: s.encode()


def _hmac(algo):
    def f(e, batch):
        import hashlib
        import hmac as _hm
        a = eval_expr(e.args[0], batch)
        k = eval_expr(e.args[1], batch)
        vb = _carried_bytes(a.type)
        kb = _carried_bytes(k.type)
        return _row_string_fn(
            [a, k],
            lambda v, key: _hm.new(kb(key), vb(v),
                                   getattr(hashlib, algo)).hexdigest(),
            e.type)
    return f


def _retype_string(e, batch):
    """json_format / color / render: identity on the carried string,
    retyped (varbinary is a dictionary column like varchar)."""
    a = eval_expr(e.args[0], batch)
    if a.dictionary is None:
        return dc_replace(a, type=e.type)
    return Column(e.type, a.data, a.valid, a.dictionary)


def _to_utf8(e, batch):
    """varchar -> varbinary holding the text's REAL utf-8 bytes in the
    latin-1-decoded carried-string convention of _num_to_binary (so
    hmac_*/md5/length over the result see the actual byte sequence,
    including for non-latin-1 text)."""
    a = eval_expr(e.args[0], batch)
    if a.dictionary is None:      # all-NULL UNKNOWN constant
        return dc_replace(a, type=e.type)
    return _dict_transform(
        a, lambda s: s.encode("utf-8").decode("latin-1"), e.type)


def _from_utf8(e, batch):
    """varbinary (latin-1-carried raw bytes) -> varchar text, invalid
    sequences replaced with U+FFFD (reference
    VarbinaryFunctions.fromUtf8 default behavior)."""
    a = eval_expr(e.args[0], batch)
    if a.dictionary is None:      # all-NULL UNKNOWN constant
        return dc_replace(a, type=e.type)
    return _dict_transform(
        a, lambda s: s.encode("latin-1", errors="replace")
                      .decode("utf-8", errors="replace"), e.type)


def _json_parse(e, batch):
    import json as _json
    a = eval_expr(e.args[0], batch)

    def canon(v: str):
        try:
            return _json.dumps(_json.loads(v), separators=(",", ":"),
                               sort_keys=False)
        except ValueError:
            raise EvalError(f"Cannot convert value to JSON: '{v}'")
    return _dict_transform(a, canon, e.type)


def _num_to_binary(pack):
    def f(e, batch):
        a = eval_expr(e.args[0], batch)
        vals = np.asarray(a.data)
        valid = None if a.valid is None else np.asarray(a.valid)
        out = []
        for i in range(vals.shape[0]):
            if valid is not None and not valid[i]:
                out.append(None)
            else:
                out.append(pack(vals[i]).decode("latin-1"))
        d, codes = StringDictionary.from_strings(out)
        v = np.asarray([o is not None for o in out], bool)
        return Column(e.type, jnp.asarray(codes),
                      None if v.all() else jnp.asarray(v), d)
    return f


def _binary_to_num(unpack):
    def f(e, batch):
        a = eval_expr(e.args[0], batch)
        return _dict_transform(
            a, lambda s: unpack(s.encode("latin-1")), e.type)
    return f


def _bar_fn(e, batch):
    """bar(x, width): unicode block bar (reference renders ANSI color
    ramps; the bar geometry matches, color is omitted)."""
    a = eval_expr(e.args[0], batch)
    w = e.args[1]
    if not isinstance(w, Const) or w.value is None:
        raise EvalError("bar: width must be a constant")
    width = int(w.value)
    vals = np.asarray(a.data).astype(np.float64)
    valid = None if a.valid is None else np.asarray(a.valid)
    out = []
    for i in range(vals.shape[0]):
        if valid is not None and not valid[i]:
            out.append(None)
            continue
        x = min(max(float(vals[i]), 0.0), 1.0)
        n = int(round(x * width))
        out.append("█" * n + " " * (width - n))
    d, codes = StringDictionary.from_strings(out)
    v = np.asarray([o is not None for o in out], bool)
    return Column(e.type, jnp.asarray(codes),
                  None if v.all() else jnp.asarray(v), d)


_JODA_TOKENS = [
    ("yyyy", "%Y"), ("yyy", "%Y"), ("yy", "%y"), ("y", "%Y"),
    ("MMMM", "%B"), ("MMM", "%b"), ("MM", "%m"), ("M", "%m"),
    ("dd", "%d"), ("d", "%d"), ("EEEE", "%A"), ("EEE", "%a"),
    ("HH", "%H"), ("H", "%H"), ("hh", "%I"), ("h", "%I"),
    ("mm", "%M"), ("m", "%M"), ("ss", "%S"), ("s", "%S"),
    ("SSS", "%f"), ("a", "%p"), ("ZZ", "%z"), ("Z", "%z"),
]


def _joda_to_strptime(fmt: str) -> str:
    out = []
    i = 0
    while i < len(fmt):
        if fmt[i] == "'":
            j = fmt.find("'", i + 1)
            if j < 0:
                out.append(fmt[i + 1:])
                break
            out.append(fmt[i + 1:j].replace("%", "%%"))
            i = j + 1
            continue
        for tok, rep in _JODA_TOKENS:
            if fmt.startswith(tok, i):
                out.append(rep)
                i += len(tok)
                break
        else:
            out.append(fmt[i].replace("%", "%%"))
            i += 1
    return "".join(out)


def _parse_datetime(e, batch):
    import datetime as _dt
    a = eval_expr(e.args[0], batch)
    fe = e.args[1]
    if not isinstance(fe, Const) or fe.value is None:
        raise EvalError("parse_datetime: format must be a constant")
    fmt = _joda_to_strptime(str(fe.value))
    codes = np.asarray(a.data)
    valid = None if a.valid is None else np.asarray(a.valid)
    vals = a.dictionary.values if a.dictionary is not None else None
    data = np.zeros(codes.shape[0], np.int64)
    data2 = np.zeros(codes.shape[0], np.int64)
    ok = np.ones(codes.shape[0], bool)
    for i in range(codes.shape[0]):
        if valid is not None and not valid[i]:
            ok[i] = False
            continue
        s = str(vals[int(codes[i])]) if vals is not None else str(codes[i])
        # %f expects microseconds; joda SSS is millis — normalize
        try:
            t = _dt.datetime.strptime(s, fmt)
        except ValueError as ex:
            raise EvalError(f"parse_datetime: {ex}")
        off = t.utcoffset()
        offm = 0 if off is None else int(off.total_seconds() // 60)
        naive = t.replace(tzinfo=None)
        ms = int((naive - _dt.datetime(1970, 1, 1)).total_seconds()
                 * 1000)
        data[i] = ms - offm * 60000
        data2[i] = offm
    return Column(e.type, jnp.asarray(data),
                  None if ok.all() else jnp.asarray(ok), None,
                  jnp.asarray(data2))


def _format_datetime(e, batch):
    import datetime as _dt
    a = eval_expr(e.args[0], batch)
    fe = e.args[1]
    if not isinstance(fe, Const) or fe.value is None:
        raise EvalError("format_datetime: format must be a constant")
    fmt = _joda_to_strptime(str(fe.value))
    vals = np.asarray(a.data)
    offs = (np.asarray(a.data2) if a.data2 is not None
            else np.zeros(vals.shape[0], np.int64))
    valid = None if a.valid is None else np.asarray(a.valid)
    epoch = _dt.datetime(1970, 1, 1)
    from ..types import DATE as _DATE
    out = []
    for i in range(vals.shape[0]):
        if valid is not None and not valid[i]:
            out.append(None)
            continue
        if a.type is _DATE:
            t = _dt.datetime.fromordinal(
                int(vals[i]) + _dt.date(1970, 1, 1).toordinal())
        else:
            t = epoch + _dt.timedelta(
                milliseconds=int(vals[i]) + int(offs[i]) * 60000)
        # strftime %f prints micros; joda SSS is millis — substitute
        # into the FORMAT (digits only, cannot collide with other
        # directives) rather than find/replace on the formatted string
        row_fmt = fmt.replace("%f", f"{t.microsecond // 1000:03d}")
        out.append(t.strftime(row_fmt))
    d, codes = StringDictionary.from_strings(out)
    v = np.asarray([o is not None for o in out], bool)
    return Column(e.type, jnp.asarray(codes),
                  None if v.all() else jnp.asarray(v), d)


def _from_iso8601_date(e, batch):
    import datetime as _dt
    a = eval_expr(e.args[0], batch)
    d0 = _dt.date(1970, 1, 1).toordinal()
    return _dict_transform(
        a, lambda s: _dt.date.fromisoformat(s[:10]).toordinal() - d0,
        e.type)


def _from_iso8601_timestamp(e, batch):
    import datetime as _dt
    a = eval_expr(e.args[0], batch)
    codes = np.asarray(a.data)
    valid = None if a.valid is None else np.asarray(a.valid)
    vals = a.dictionary.values if a.dictionary is not None else None
    data = np.zeros(codes.shape[0], np.int64)
    data2 = np.zeros(codes.shape[0], np.int64)
    ok = np.ones(codes.shape[0], bool)
    for i in range(codes.shape[0]):
        if valid is not None and not valid[i]:
            ok[i] = False
            continue
        s = str(vals[int(codes[i])]) if vals is not None else str(codes[i])
        t = _dt.datetime.fromisoformat(s.replace("Z", "+00:00"))
        off = t.utcoffset()
        offm = 0 if off is None else int(off.total_seconds() // 60)
        naive = t.replace(tzinfo=None)
        data[i] = int((naive - _dt.datetime(1970, 1, 1)).total_seconds()
                      * 1000) - offm * 60000
        data2[i] = offm
    return Column(e.type, jnp.asarray(data),
                  None if ok.all() else jnp.asarray(ok), None,
                  jnp.asarray(data2))


def _last_day_of_month(e, batch):
    import calendar
    import datetime as _dt
    a = eval_expr(e.args[0], batch)
    vals = np.asarray(a.data)
    valid = None if a.valid is None else np.asarray(a.valid)
    d0 = _dt.date(1970, 1, 1).toordinal()
    from ..types import DATE as _DATE
    out = np.zeros(vals.shape[0], np.int64)
    for i in range(vals.shape[0]):
        if valid is not None and not valid[i]:
            continue
        if a.type is _DATE:
            d = _dt.date.fromordinal(int(vals[i]) + d0)
        else:
            d = (_dt.datetime(1970, 1, 1)
                 + _dt.timedelta(milliseconds=int(vals[i]))).date()
        last = calendar.monthrange(d.year, d.month)[1]
        out[i] = _dt.date(d.year, d.month, last).toordinal() - d0
    return Column(e.type, jnp.asarray(out), a.valid)


def _timezone_part(which):
    def f(e, batch):
        a = eval_expr(e.args[0], batch)
        offs = (jnp.asarray(a.data2) if a.data2 is not None
                else jnp.zeros(np.asarray(a.data).shape[0], jnp.int64))
        if which == "hour":
            data = jnp.sign(offs) * (jnp.abs(offs) // 60)
        else:
            data = jnp.sign(offs) * (jnp.abs(offs) % 60)
        return Column(BIGINT, data.astype(jnp.int64), a.valid)
    return f


_PORTER_V = "aeiou"


def _porter_stem(w: str) -> str:
    """Compact Porter stemmer (step 1 + common suffixes) — covers the
    usual analytics cases (plurals, -ing/-ed, -ation)."""
    if len(w) <= 2:
        return w
    w = w.lower()

    def meas(s):
        m, prev_v = 0, False
        for ch in s:
            v = ch in _PORTER_V
            if prev_v and not v:
                m += 1
            prev_v = v
        return m

    def has_vowel(s):
        return any(c in _PORTER_V for c in s)

    # step 1a
    if w.endswith("sses"):
        w = w[:-2]
    elif w.endswith("ies"):
        w = w[:-2]
    elif w.endswith("ss"):
        pass
    elif w.endswith("s"):
        w = w[:-1]
    # step 1b
    if w.endswith("eed"):
        if meas(w[:-3]) > 0:
            w = w[:-1]
    elif w.endswith("ed") and has_vowel(w[:-2]):
        w = w[:-2]
        w = _porter_fixup(w)
    elif w.endswith("ing") and has_vowel(w[:-3]):
        w = w[:-3]
        w = _porter_fixup(w)
    # step 1c
    if w.endswith("y") and has_vowel(w[:-1]):
        w = w[:-1] + "i"
    for suf, rep in (("ational", "ate"), ("tional", "tion"),
                     ("ization", "ize"), ("fulness", "ful"),
                     ("ousness", "ous"), ("iveness", "ive"),
                     ("biliti", "ble"), ("entli", "ent"),
                     ("ousli", "ous"), ("alli", "al"), ("eli", "e")):
        if w.endswith(suf) and meas(w[:-len(suf)]) > 0:
            w = w[:-len(suf)] + rep
            break
    return w


def _porter_fixup(w: str) -> str:
    if w.endswith(("at", "bl", "iz")):
        return w + "e"
    if (len(w) >= 2 and w[-1] == w[-2]
            and w[-1] not in "lsz" and w[-1] not in _PORTER_V):
        return w[:-1]
    return w


def _word_stem(e, batch):
    a = eval_expr(e.args[0], batch)
    return _dict_transform(a, _porter_stem, e.type)


def _unpack_be(nbytes, signed=True):
    def f(b: bytes):
        b = b[:nbytes].rjust(nbytes, b"\x00")
        return int.from_bytes(b, "big", signed=signed)
    return f


def _unpack_ieee(fmt):
    import struct

    def f(b: bytes):
        return struct.unpack(fmt, b[:8 if fmt == ">d" else 4])[0]
    return f


def _pack_fns():
    import struct
    return {
        "to_big_endian_64": lambda v: struct.pack(">q", int(v)),
        "to_big_endian_32": lambda v: struct.pack(">i", int(v)),
        "to_ieee754_64": lambda v: struct.pack(">d", float(v)),
        "to_ieee754_32": lambda v: struct.pack(">f", float(v)),
    }


_DISPATCH_R4 = {
    "hmac_md5": _hmac("md5"), "hmac_sha1": _hmac("sha1"),
    "hmac_sha256": _hmac("sha256"), "hmac_sha512": _hmac("sha512"),
    "to_utf8": _to_utf8, "from_utf8": _from_utf8,
    "json_format": _retype_string, "json_parse": _json_parse,
    "bar": _bar_fn,
    "color": _retype_string, "render": _retype_string,
    "parse_datetime": _parse_datetime,
    "format_datetime": _format_datetime,
    "from_iso8601_date": _from_iso8601_date,
    "from_iso8601_timestamp": _from_iso8601_timestamp,
    "last_day_of_month": _last_day_of_month,
    "timezone_hour": _timezone_part("hour"),
    "timezone_minute": _timezone_part("minute"),
    "word_stem": _word_stem,
    "from_big_endian_64": _binary_to_num(_unpack_be(8)),
    "from_big_endian_32": _binary_to_num(_unpack_be(4)),
    "from_ieee754_64": _binary_to_num(_unpack_ieee(">d")),
    "from_ieee754_32": _binary_to_num(_unpack_ieee(">f")),
}
for _n, _f in _pack_fns().items():
    _DISPATCH_R4[_n] = _num_to_binary(_f)
_DISPATCH.update(_DISPATCH_R4)


# --- quantile sketch accessors (TDigestFunctions/QuantileDigestFunctions) --

def _digest_lanes(col: Column):
    starts = np.asarray(col.data).astype(np.int64)
    lens = (np.zeros_like(starts) if col.data2 is None
            else np.asarray(col.data2).astype(np.int64))
    means = np.asarray(col.elements.data).astype(np.float64)
    weights = np.asarray(col.elements2.data).astype(np.float64)
    return starts, lens, means, weights


def _digest_result(col: Column, vals: np.ndarray, ok: np.ndarray,
                   out_type):
    from ..types import QDigestType, is_integral
    vt = (col.type.value_type
          if isinstance(col.type, QDigestType) else None)
    if vt is not None and is_integral(vt):
        data = np.round(vals).astype(np.int64)
        return Column(out_type, jnp.asarray(data),
                      None if ok.all() else jnp.asarray(ok))
    return Column(out_type, jnp.asarray(vals),
                  None if ok.all() else jnp.asarray(ok))


def _value_at_quantile(e, batch):
    from ..ops.digest import digest_quantile
    col = eval_expr(e.args[0], batch)
    qc = eval_expr(e.args[1], batch)
    starts, lens, means, weights = _digest_lanes(col)
    qs = np.asarray(qc.data).astype(np.float64)
    n = starts.shape[0]
    out = np.zeros(n, np.float64)
    ok = np.ones(n, bool)
    cvalid = None if col.valid is None else np.asarray(col.valid)
    for i in range(n):
        if (cvalid is not None and not cvalid[i]) or lens[i] == 0:
            ok[i] = False
            continue
        s, ln = starts[i], lens[i]
        out[i] = digest_quantile(means[s:s + ln], weights[s:s + ln],
                                 float(qs[i % qs.shape[0]]))
    return _digest_result(col, out, ok, e.type)


def _values_at_quantiles(e, batch):
    from ..ops.digest import digest_quantile
    from ..types import ArrayType
    col = eval_expr(e.args[0], batch)
    qarr = eval_expr(e.args[1], batch)
    starts, lens, means, weights = _digest_lanes(col)
    qoffs = np.asarray(qarr.data).astype(np.int64)
    qlens = np.asarray(qarr.data2).astype(np.int64)
    qvals = np.asarray(qarr.elements.data).astype(np.float64)
    n = starts.shape[0]
    cvalid = None if col.valid is None else np.asarray(col.valid)
    flat = []
    out_offs = np.zeros(n, np.int64)
    out_lens = np.zeros(n, np.int64)
    ok = np.ones(n, bool)
    for i in range(n):
        out_offs[i] = len(flat)
        if (cvalid is not None and not cvalid[i]) or lens[i] == 0:
            ok[i] = False
            continue
        s, ln = starts[i], lens[i]
        for j in range(int(qoffs[i]), int(qoffs[i] + qlens[i])):
            flat.append(digest_quantile(means[s:s + ln],
                                        weights[s:s + ln],
                                        float(qvals[j])))
        out_lens[i] = len(flat) - out_offs[i]
    cap = max(len(flat), 1)
    fd = np.zeros(cap, np.float64)
    fd[:len(flat)] = flat
    elem_t = e.type.element
    inner = _digest_result(col, fd, np.ones(cap, bool), elem_t)
    return Column(e.type, jnp.asarray(out_offs),
                  None if ok.all() else jnp.asarray(ok), None,
                  jnp.asarray(out_lens), inner)


def _quantile_at_value(e, batch):
    from ..ops.digest import digest_quantile_at_value
    col = eval_expr(e.args[0], batch)
    vc = eval_expr(e.args[1], batch)
    starts, lens, means, weights = _digest_lanes(col)
    vs = np.asarray(vc.data).astype(np.float64)
    n = starts.shape[0]
    out = np.zeros(n, np.float64)
    ok = np.ones(n, bool)
    cvalid = None if col.valid is None else np.asarray(col.valid)
    for i in range(n):
        if (cvalid is not None and not cvalid[i]) or lens[i] == 0:
            ok[i] = False
            continue
        s, ln = starts[i], lens[i]
        out[i] = digest_quantile_at_value(
            means[s:s + ln], weights[s:s + ln],
            float(vs[i % vs.shape[0]]))
    return Column(DOUBLE, jnp.asarray(out),
                  None if ok.all() else jnp.asarray(ok))


_DISPATCH.update({
    "value_at_quantile": _value_at_quantile,
    "values_at_quantiles": _values_at_quantiles,
    "quantile_at_value": _quantile_at_value,
})
