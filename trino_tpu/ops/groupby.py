"""Grouped aggregation — HashAggregationOperator, TPU style.

Reference parity: operator/HashAggregationOperator.java:49,381-413 with
MultiChannelGroupByHash.java:55 (open-addressing probe) and flat BigArray
accumulator state (operator/aggregation/, lib/trino-array). Redesign for
XLA (SURVEY.md §7.3): instead of a serial hash-probe loop, group rows by a
stable lexsort on the key lanes, derive segment ids from key-change
boundaries, and compute every accumulator with ``jax.ops.segment_*`` —
fully parallel, static shapes, no device hash table. Group cardinality is
data-dependent, so outputs are capacity-padded with a device num_groups.

Partial/final split (reference: AggregationNode PARTIAL/FINAL +
PushPartialAggregationThroughExchange rule) is expressed by running this
same kernel on partial states: every aggregate below declares a
``combine`` that is itself one of the supported segment ops.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import Batch, Column
from .hashing import equality_lanes
from .sort import stable_lexsort

_U64MAX = jnp.uint64(0xFFFFFFFFFFFFFFFF)

# The form each ``group_aggregate`` of the calling thread took (packed |
# dense | sort) with its input capacity, noted where it is CHOSEN: when
# a program is traced, or per call where it runs eagerly. A dispatcher
# that wants to say which form its program runs (exec/executor.py
# ``_jit_call``: the ``form`` of the span, ``trino_tpu_groupby_total``)
# collects the notes of the program's one trace and keeps them with it.
_NOTES = threading.local()


def note_form(form: str, lanes: int) -> None:
    notes = getattr(_NOTES, "forms", None)
    if notes is not None:
        notes.append((form, int(lanes)))


@contextlib.contextmanager
def noted_forms():
    """Collects ``(form, input capacity)`` of every ``group_aggregate``
    the calling thread chooses a form for inside the block."""
    before = getattr(_NOTES, "forms", None)
    _NOTES.forms = notes = []
    try:
        yield notes
    finally:
        _NOTES.forms = before


@dataclass(frozen=True)
class AggInput:
    """One aggregate over one input lane (or none, for count(*))."""
    kind: str          # sum | count | count_star | min | max | any_value
                       # | argmin | argmax | count_distinct | percentile
    input: Optional[str] = None   # column name; None for count_star
    mask: Optional[str] = None    # FILTER / mask column (boolean), optional
    output: str = "agg"
    param: Optional[float] = None  # percentile fraction for 'percentile'
    input2: Optional[str] = None   # comparator lane for argmin/argmax


# kinds whose partials combine with another single-lane segment op —
# these support the PARTIAL -> exchange -> FINAL plan split (reference:
# PushPartialAggregationThroughExchange); the rest (argmin/argmax,
# count_distinct, percentile) need all rows of a group co-located, i.e.
# repartition-BEFORE-aggregate
COMBINABLE_KINDS = {"sum": "sum", "count": "sum", "count_star": "sum",
                    "min": "min", "max": "max", "any_value": "any_value",
                    "bit_and": "bit_and", "bit_or": "bit_or"}


def _key_lanes(batch: Batch, key_names: Sequence[str],
               live: Optional[jax.Array] = None) -> List[jax.Array]:
    """Exact equality-preserving lanes; a null is its own group value
    (SQL GROUP BY treats NULLs as equal), encoded via a validity lane."""
    live = batch.row_valid() if live is None else live
    lanes: List[jax.Array] = [(~live).astype(jnp.uint64)]
    for name in key_names:
        col = batch.column(name)
        col_lanes = equality_lanes(col.data)
        if col.data2 is not None and not str(col.type.name).endswith(
                "with time zone"):
            # Int128 high lane participates in key equality; a
            # TIMESTAMP WITH TIME ZONE's zone lane does NOT (equality
            # is instant-based, reference TimestampWithTimeZoneType)
            col_lanes = col_lanes + equality_lanes(col.data2)
        if col.valid is not None:
            v = jnp.asarray(col.valid)
            lanes.append((~v).astype(jnp.uint64))
            col_lanes = [jnp.where(v, u, jnp.zeros_like(u))
                         for u in col_lanes]
        col_lanes = [jnp.where(live, u, _U64MAX + jnp.zeros_like(u))
                     for u in col_lanes]
        lanes.extend(col_lanes)
    return lanes


def _string_minmax_lane(col: Column, vals: jax.Array, kind: str):
    """(rank lane, identity, decode) for MIN/MAX over a dictionary
    column: reduce over collation ranks, decode the winning rank back
    to a code (codes are insertion-ordered, not collation-ordered)."""
    ranks = col.dictionary.rank_codes()
    code_by_rank = jnp.asarray(_invert_permutation(ranks))
    rvals = jnp.take(jnp.asarray(ranks), vals, mode="clip")
    ident = jnp.asarray(len(ranks) if kind == "min" else -1, rvals.dtype)

    def decode(data):
        return jnp.take(code_by_rank, jnp.clip(data, 0, len(ranks) - 1),
                        mode="clip").astype(jnp.int32)
    return rvals, ident, decode


def _identity_for(kind: str, dtype) -> jax.Array:
    if dtype == jnp.bool_:
        return jnp.asarray(kind == "min", dtype)
    if kind == "min":
        if dtype in (jnp.float32, jnp.float64):
            return jnp.asarray(jnp.inf, dtype)
        return jnp.asarray(jnp.iinfo(dtype).max, dtype)
    if kind == "max":
        if dtype in (jnp.float32, jnp.float64):
            return jnp.asarray(-jnp.inf, dtype)
        return jnp.asarray(jnp.iinfo(dtype).min, dtype)
    return jnp.asarray(0, dtype)


# largest packed key-domain the unrolled masked-reduction kernel will
# take on; beyond this the lexsort path wins (graph size / compile time)
FAST_DOMAIN_LIMIT = 64

_FAST_KINDS = {"sum", "count", "count_star", "min", "max", "any_value",
               "bit_and", "bit_or"}


def _static_domain(col: Column) -> Optional[int]:
    """Statically-known value domain [0, d): dictionary code range or
    bool. None when unknown (general ints/floats)."""
    if col.dictionary is not None:
        return len(col.dictionary)
    if jnp.asarray(col.data).dtype == jnp.bool_:
        return 2
    return None


def _packed_group_aggregate(batch: Batch, key_names: Sequence[str],
                            aggs: Sequence[AggInput], gcap: int,
                            live: Optional[jax.Array] = None,
                            clamp: bool = False) -> Optional[Batch]:
    """Small-static-domain GROUP BY: one packed int32 group id per row,
    every aggregate an unrolled per-group masked reduction (VPU-friendly,
    single fused pass over HBM)."""
    doms: List[int] = []
    kcols: List[Column] = []
    if not key_names:
        return None
    for name in key_names:
        c = batch.column(name)
        d = _static_domain(c)
        if d is None or c.data2 is not None:
            return None
        doms.append(d)
        kcols.append(c)
    nseg = 1
    for d in doms:
        nseg *= d + 1          # one extra slot per key for NULL
    if nseg > FAST_DOMAIN_LIMIT or nseg > gcap:
        return None
    if any(a.kind not in _FAST_KINDS for a in aggs):
        return None
    if clamp:
        # The packed domain bounds the group count, so the output needs
        # at most nseg slots — NOT the input capacity the default gcap
        # inherits. Without this, a 6-group q1 aggregation emits 8M-row
        # output lanes and the downstream sort lexsorts 8M slots for 4
        # live rows (measured: ~20s of the sf1 engine path). Callers
        # that pass an explicit groups_capacity are asserting a shape
        # contract (distributed shard exchanges) — never clamp those.
        from ..config import capacity_for
        gcap = min(gcap, capacity_for(nseg, minimum=1))

    cap = batch.capacity
    if live is None:
        live = batch.row_valid()
    packed = jnp.zeros((cap,), jnp.int32)
    for c, d in zip(kcols, doms):
        code = jnp.asarray(c.data).astype(jnp.int32)
        code = jnp.clip(code, 0, d - 1)
        if c.valid is not None:
            code = jnp.where(jnp.asarray(c.valid), code, d)
        packed = packed * (d + 1) + code

    # Pallas fast path (TPU): one fused one-hot-matmul pass computes
    # every float sum + count; other kinds keep the masked reductions
    from . import pallas_groupby as _pg
    pmode = _pg.mode()
    pallas_res: Dict[str, Column] = {}
    rest: List[AggInput] = list(aggs)
    counts = None
    if pmode:
        pallas_res, rest, counts = _pallas_packed_aggs(
            batch, aggs, packed, live, nseg, pmode)
    gmasks = ([live & (packed == g) for g in range(nseg)]
              if (rest or counts is None) else [])
    if counts is None:
        counts = jnp.stack([jnp.sum(m.astype(jnp.int64))
                            for m in gmasks])

    out_cols: Dict[str, Column] = {}
    # key columns decoded from the group index (after compaction below)
    exists = counts > 0
    num_groups = jnp.sum(exists.astype(jnp.int64))
    gidx = jnp.nonzero(exists, size=gcap, fill_value=nseg)[0]

    rem = gidx
    for name, c, d in zip(reversed(key_names), reversed(kcols),
                          reversed(doms)):
        code = (rem % (d + 1)).astype(jnp.int32)
        rem = rem // (d + 1)
        is_null = code >= d
        data = jnp.clip(code, 0, d - 1)
        if jnp.asarray(c.data).dtype == jnp.bool_:
            data = data.astype(jnp.bool_)
        valid = ~is_null if c.valid is not None else None
        out_cols[name] = Column(c.type, data, valid, c.dictionary)
    out_cols = {k: out_cols[k] for k in key_names}

    gidx_c = jnp.clip(gidx, 0, nseg - 1)
    rest_set = {id(a) for a in rest}
    for agg in aggs:
        if id(agg) in rest_set:
            res = _masked_agg(batch, agg, gmasks, live, nseg)
        else:
            res = pallas_res[agg.output]
        out_cols[agg.output] = _compact_groups(res, gidx_c)

    return Batch(out_cols, num_groups)


def _agg_row_mask(batch: Batch, agg: AggInput,
                  live: jax.Array) -> jax.Array:
    m = live
    if agg.mask is not None:
        mcol = batch.column(agg.mask)
        mm = jnp.asarray(mcol.data).astype(bool)
        if mcol.valid is not None:
            mm = mm & jnp.asarray(mcol.valid)
        m = m & mm
    return m


def _pallas_packed_aggs(batch: Batch, aggs: Sequence[AggInput],
                        packed: jax.Array, live: jax.Array, nseg: int,
                        mode: str):
    """Route float sums and counts through the pallas grouped-sum
    kernel (ops/pallas_groupby.py). Returns (results by output name as
    [nseg] Columns, remaining aggs, per-group live counts)."""
    from ..types import BIGINT
    from . import pallas_groupby as _pg

    lanes: List[jax.Array] = [live.astype(jnp.float64)]
    plans = []          # (agg, kind, value_idx, count_idx, col)
    rest: List[AggInput] = []
    for agg in aggs:
        if agg.kind in ("count_star", "count"):
            m = _agg_row_mask(batch, agg, live)
            col = None
            if agg.kind == "count":
                col = batch.column(agg.input)
                if col.valid is not None:
                    m = m & jnp.asarray(col.valid)
            plans.append((agg, "count", len(lanes), None, col))
            lanes.append(m.astype(jnp.float64))
            continue
        if agg.kind == "sum":
            col = batch.column(agg.input)
            vals = jnp.asarray(col.data)
            if col.data2 is None and vals.dtype in (jnp.float32,
                                                    jnp.float64):
                m = _agg_row_mask(batch, agg, live)
                if col.valid is not None:
                    m = m & jnp.asarray(col.valid)
                plans.append((agg, "sum", len(lanes), len(lanes) + 1,
                              col))
                lanes.append(jnp.where(m, vals.astype(jnp.float64),
                                       0.0))
                lanes.append(m.astype(jnp.float64))
                continue
        rest.append(agg)
    if not plans:
        return {}, list(aggs), None

    gid = jnp.where(live, packed, _pg.G_PAD).astype(jnp.int32)
    outs = _pg.grouped_sums(gid, lanes, nseg,
                            interpret=(mode == "interpret"))
    counts = jnp.round(outs[0]).astype(jnp.int64)
    results: Dict[str, Column] = {}
    for agg, kind, vi, ci, col in plans:
        if kind == "count":
            results[agg.output] = Column(
                BIGINT, jnp.round(outs[vi]).astype(jnp.int64), None)
        else:
            nvalid = jnp.round(outs[ci]).astype(jnp.int64)
            data = outs[vi]
            if jnp.asarray(col.data).dtype == jnp.float32:
                data = data.astype(jnp.float32)
            results[agg.output] = Column(_sum_type(col.type), data,
                                         nvalid > 0)
    return results, rest, counts


def _compact_groups(col: Column, gidx: jax.Array) -> Column:
    from dataclasses import replace as _replace
    data = jnp.take(jnp.asarray(col.data), gidx, mode="clip")
    valid = (None if col.valid is None
             else jnp.take(jnp.asarray(col.valid), gidx, mode="clip"))
    data2 = (None if col.data2 is None
             else jnp.take(jnp.asarray(col.data2), gidx, mode="clip"))
    return _replace(col, data=data, valid=valid, data2=data2)


def _masked_agg(batch: Batch, agg: AggInput, gmasks, live,
                nseg: int) -> Column:
    """One aggregate as nseg masked reductions -> [nseg] arrays."""
    from ..types import BIGINT, is_string

    if agg.mask is not None:
        mcol = batch.column(agg.mask)
        m = jnp.asarray(mcol.data).astype(bool)
        if mcol.valid is not None:
            m = m & jnp.asarray(mcol.valid)
        gmasks = [g & m for g in gmasks]

    if agg.kind == "count_star":
        data = jnp.stack([jnp.sum(g.astype(jnp.int64)) for g in gmasks])
        return Column(BIGINT, data, None)

    col = batch.column(agg.input)
    vals = jnp.asarray(col.data)
    if col.valid is not None:
        v = jnp.asarray(col.valid)
        gmasks = [g & v for g in gmasks]

    if agg.kind == "count":
        data = jnp.stack([jnp.sum(g.astype(jnp.int64)) for g in gmasks])
        return Column(BIGINT, data, None)

    nvalid = jnp.stack([jnp.sum(g.astype(jnp.int64)) for g in gmasks])
    group_valid = nvalid > 0

    if _wide_decimal_agg(col, agg.kind):
        return _int128_masked_agg(col, agg.kind, gmasks, group_valid)

    if agg.kind == "sum":
        acc_dtype = vals.dtype if vals.dtype in (
            jnp.float32, jnp.float64) else jnp.int64
        av = vals.astype(acc_dtype)
        zero = jnp.asarray(0, acc_dtype)
        data = jnp.stack(
            [jnp.sum(jnp.where(g, av, zero)) for g in gmasks])
        return Column(_sum_type(col.type), data, group_valid)

    if agg.kind in ("bit_and", "bit_or"):
        op = jnp.bitwise_and if agg.kind == "bit_and" else jnp.bitwise_or
        ident = jnp.asarray(-1 if agg.kind == "bit_and" else 0, jnp.int64)
        work = vals.astype(jnp.int64)
        data = jnp.stack(
            [jax.lax.reduce(jnp.where(g, work, ident), ident,
                            op, (0,)) for g in gmasks])
        return Column(BIGINT, data, group_valid)

    if agg.kind in ("min", "max"):
        red = jnp.min if agg.kind == "min" else jnp.max
        if is_string(col.type):
            rvals, ident, decode = _string_minmax_lane(col, vals,
                                                       agg.kind)
            data = decode(jnp.stack(
                [red(jnp.where(g, rvals, ident)) for g in gmasks]))
            return Column(col.type, data, group_valid,
                          dictionary=col.dictionary)
        as_bool = vals.dtype == jnp.bool_
        work = vals.astype(jnp.int32) if as_bool else vals
        ident = _identity_for(agg.kind, work.dtype)
        data = jnp.stack(
            [red(jnp.where(g, work, ident)) for g in gmasks])
        if as_bool:
            data = data.astype(jnp.bool_)
        return Column(col.type, data, group_valid)

    # any_value: first valid row per group
    cap = vals.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int64)
    firsts = jnp.stack(
        [jnp.min(jnp.where(g, pos, jnp.int64(cap))) for g in gmasks])
    from dataclasses import replace as _replace
    out = col.gather(jnp.clip(firsts, 0, cap - 1))
    return _replace(out, valid=group_valid)


# --------------------------------------------------------------------------
# the dense form: one integer key over a range the slots cover
# --------------------------------------------------------------------------

_DENSE_KINDS = {"sum", "count", "count_star", "min", "max"}


def dense_slots(capacity: int) -> int:
    """Slots of the dense form for an input of ``capacity`` lanes,
    static: the size the join's exact directory has for a build side of
    that capacity (32 slots a lane, at most 2^26: ops/join.py
    ``_directory_bits``). TPC-H's l_orderkey spans 4 x rows(orders) =
    60M at SF 10, under the 2^26 its 2^26-lane scan is given."""
    from .join import _directory_bits
    return 1 << _directory_bits(capacity)


def _plain_lane(col: Column) -> bool:
    return (col.data2 is None and col.dictionary is None
            and col.elements is None and col.children is None)


def dense_key_lane(col: Column) -> bool:
    """Static: may this column be the dense form's key? One plain
    integer lane."""
    return _plain_lane(col) and jnp.issubdtype(col.data.dtype, jnp.integer)


def dense_eligible(batch: Batch, key_names: Sequence[str],
                   aggs: Sequence[AggInput]) -> bool:
    """Static: ONE key column that is one plain integer lane (BIGINT,
    INTEGER, DATE ...: ops/join.py ``_integer_key``'s rule, less
    booleans and dictionary codes, which have a static domain and take
    the packed form), and aggregates a scatter computes: sum, count,
    count(*), min, max (avg arrives as sum and count) over plain
    numeric lanes — no strings, no booleans, no Int128 sums."""
    if len(key_names) != 1 or not dense_key_lane(
            batch.column(key_names[0])):
        return False
    for a in aggs:
        if a.kind not in _DENSE_KINDS:
            return False
        if a.kind in ("sum", "min", "max"):
            col = batch.column(a.input)
            if not _plain_lane(col) or col.data.dtype == jnp.bool_ \
                    or _wide_decimal_agg(col, a.kind):
                return False
    return True


def dense_key_range(batch: Batch, key_names: Sequence[str],
                    live: Optional[jax.Array] = None) -> jax.Array:
    """int64[5]: the least and the greatest non-NULL live key, how many
    there are, whether they ASCEND (no NULL among the live keys, each
    no less than the one before, the dead rows behind them all) and,
    where they do, the longest run of equal keys — what ONE counted
    read tells the host before it asks for the dense form
    (``dense_fits``, ``dense_keys``). Ascending keys keep a group's
    rows adjacent: short runs are added up row by row with no scatter
    at all (``_run_groups``), and a scatter of ascending indices needs
    no sort on the chip (its compiler sorts any others first)."""
    col = batch.column(key_names[0])
    live = batch.row_valid() if live is None else live
    usable = live
    if col.valid is not None:
        usable = usable & jnp.asarray(col.valid)
    key = jnp.asarray(col.data).astype(jnp.int64)
    info = jnp.iinfo(jnp.int64)
    lane = jnp.where(usable, key, info.max)
    n = jnp.sum(usable.astype(jnp.int64))
    ascend = jnp.all(lane[1:] >= lane[:-1]) \
        & (n == jnp.sum(live.astype(jnp.int64)))
    # a row's distance from the first row of its run of equal keys
    pos = jnp.arange(lane.shape[0], dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), lane[1:] != lane[:-1]])
    behind = pos - jax.lax.cummax(jnp.where(first, pos, 0))
    longest = jnp.max(jnp.where(usable, behind, -1)) + 1
    return jnp.stack([jnp.min(lane),
                      jnp.max(jnp.where(usable, key, info.min)), n,
                      ascend.astype(jnp.int64),
                      jnp.where(ascend, longest, 0).astype(jnp.int64)])


def dense_fits(key_range, capacity: int) -> bool:
    """Host: do the keys of ``key_range`` (``dense_key_range``, read)
    span less than the slots of a ``capacity``-lane input? One slot,
    the last, is the NULL key's group, so the greatest key has to land
    before it. Python integers: a span past 2^63 cannot wrap."""
    lo, hi, n = (int(v) for v in key_range[:3])
    return n > 0 and hi - lo < dense_slots(capacity) - 1


# the longest run of equal ascending keys that is added up row by row
# (``_run_groups``: one shifted pass a row of the run); past it the
# slots take over
_RUN_MAX = 64


class DenseKeys(NamedTuple):
    """What the host read of the key before it asked for the dense
    form: ``base``, the least key (an int64 scalar: DATA, an argument
    of the program), ``ascending`` and ``run`` (STATIC: a program is
    traced for one value of each): a power of two no less than the
    longest run of equal keys where they ascend in runs of at most
    ``_RUN_MAX``, else 0."""
    base: object
    ascending: bool = False
    run: int = 0


def dense_keys(key_range, capacity: int) -> Optional[DenseKeys]:
    """``DenseKeys`` from one read of ``dense_key_range``, or None
    where the span does not fit."""
    if not dense_fits(key_range, capacity):
        return None
    longest = int(key_range[4])
    run = 1 << (longest - 1).bit_length() if 0 < longest <= _RUN_MAX else 0
    return DenseKeys(jnp.int64(int(key_range[0])), bool(key_range[3]), run)


def _read_dense_keys(batch: Batch, key_names: Sequence[str], live):
    """An EAGER call's own counted read: ``DenseKeys`` where the dense
    form fits, else None. Inside a traced program nothing can be read:
    the caller made the read before (``dense``), or the general path
    runs."""
    if isinstance(batch.column(key_names[0]).data, jax.core.Tracer) \
            or isinstance(live, jax.core.Tracer) \
            or isinstance(batch.num_rows, jax.core.Tracer):
        return None
    from ..obs.trace import active_span
    with active_span("host_read", site="groupby_key_range"):
        key_range = jax.device_get(dense_key_range(batch, key_names,
                                                   live))
    return dense_keys(key_range, batch.capacity)


def dense_group_slots(batch: Batch, key_names: Sequence[str],
                      aggs: Sequence[AggInput], dense: DenseKeys,
                      live: Optional[jax.Array] = None,
                      spanned: Optional[jax.Array] = None):
    """The dense form before its compaction: ``(slots, exists)``. The
    batch ``slots`` has ``dense_slots(capacity)`` rows, ALL live: row g
    is the group of key ``dense.base + g`` (the last row the NULL
    key's), its aggregates scattered there row by row; ``exists[g]``
    says whether any live input row fell into it. A caller that filters
    the groups (a HAVING) masks ``exists`` further and compacts ONCE,
    what is left (``ops/compact.py compact_batch``);
    ``group_aggregate`` compacts ``exists`` as it is. Null semantics
    are the general path's: NULL keys are one group, sum/min/max over
    no non-NULL input are NULL, count is 0.

    Where the read found the keys ascending in short runs
    (``dense.run``), a group's rows are adjacent and the SAME pair is
    made in row space, with no slot and no scatter (``_run_groups``:
    ``slots`` is then the input's own rows, ``exists`` the last row of
    each run): on the chip a scatter of 2^26 32-bit updates takes 0.59
    s and one of 64-bit updates (a DOUBLE or BIGINT sum) 8.5-9.3 s,
    the row-by-row adds a few milliseconds (PERF.md §6, PR 36).

    ``spanned`` says which rows' keys the host's read covered (default:
    the live ones). A row that is spanned and not live (a fused filter
    dropped it) keeps its OWN slot and adds nothing there, so that keys
    the read found ascending stay ascending indices
    (``dense.ascending``: the scatters then promise sorted indices, and
    the chip's compiler sorts nothing); every other row's update goes
    past the end and is dropped."""
    cap = batch.capacity
    n_slots = dense_slots(cap)
    kcol = batch.column(key_names[0])
    live = batch.row_valid() if live is None else live
    spanned = live if spanned is None else spanned
    if dense.run:
        return _run_groups(batch, kcol, key_names[0], aggs, dense.run,
                           live, spanned)
    slot = jnp.asarray(kcol.data).astype(jnp.int64) \
        - jnp.asarray(dense.base, jnp.int64)
    if kcol.valid is not None:
        slot = jnp.where(jnp.asarray(kcol.valid), slot, n_slots - 1)
    slot = jnp.where(spanned, slot, n_slots).astype(jnp.int32)

    def scatter(init, updates, op: str = "add"):
        return getattr(init.at[slot], op)(
            updates, mode="drop", indices_are_sorted=dense.ascending)

    def count_of(mask):
        return scatter(jnp.zeros((n_slots,), jnp.int32),
                       mask.astype(jnp.int32))

    rows = count_of(live)
    g = jnp.arange(n_slots, dtype=jnp.int64)
    key = (jnp.asarray(dense.base, jnp.int64) + g).astype(kcol.data.dtype)
    cols: Dict[str, Column] = {key_names[0]: Column(
        kcol.type, key,
        None if kcol.valid is None else g < n_slots - 1)}

    def reduce_(vals, ident, kind):
        return scatter(jnp.full((n_slots,), ident, vals.dtype), vals,
                       "add" if kind == "sum" else kind)

    cols.update(_dense_aggregates(batch, aggs, live, rows, count_of,
                                  reduce_))
    return Batch(cols, n_slots), rows > 0


def _run_groups(batch: Batch, kcol: Column, key_name: str,
                aggs: Sequence[AggInput], run: int, live, spanned):
    """``dense_group_slots`` where the spanned keys ASCEND in runs of
    at most ``run`` rows (no NULL key among them): a group is a run of
    adjacent rows, its aggregates are made at the run's LAST row by
    combining the row with its ``run - 1`` predecessors where they
    hold the same key (shifted, elementwise passes: no scatter, no
    gather, no scan), and ``exists`` marks that last row where any row
    of the run is live. The batch returned is row for row the input:
    the key lane itself, the aggregates beside it."""
    cap = batch.capacity
    key = jnp.asarray(kcol.data)
    pos = jnp.arange(cap, dtype=jnp.int32)

    def back(lane, j):
        # the lane j rows up (what wraps around is masked by ``same``)
        return jnp.roll(lane, j)

    # same[j - 1]: the row j up exists, was spanned and holds this key
    same = [(pos >= j) & back(spanned, j) & (back(key, j) == key)
            for j in range(1, run)]

    def over_run(vals, ident, op):
        out = vals
        for j, s in enumerate(same, 1):
            out = op(out, jnp.where(s, back(vals, j), ident))
        return out

    def count_of(mask):
        return over_run(mask.astype(jnp.int32), jnp.int32(0), jnp.add)

    ahead = jnp.concatenate([spanned[1:] & (key[1:] == key[:-1]),
                             jnp.zeros((1,), bool)])
    rows = count_of(live)
    cols: Dict[str, Column] = {key_name: Column(kcol.type, key, None)}
    ops = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
    cols.update(_dense_aggregates(
        batch, aggs, live, rows, count_of,
        lambda vals, ident, kind: over_run(vals, ident, ops[kind])))
    return Batch(cols, cap), spanned & ~ahead & (rows > 0)


def _dense_aggregates(batch: Batch, aggs: Sequence[AggInput], live, rows,
                      count_of, reduce_) -> Dict[str, Column]:
    """The aggregate columns of the dense form, whichever way a group
    is added up: ``count_of(mask)`` counts a group's rows under a mask
    (``rows``: under ``live``), ``reduce_(values, identity, kind)``
    sums, or takes the least or greatest of, a group's values."""
    from ..types import BIGINT
    cols: Dict[str, Column] = {}
    for agg in aggs:
        mask = live     # the rows this aggregate counts
        if agg.mask is not None:
            mask = _agg_row_mask(batch, agg, live)
        if agg.kind != "count_star":
            col = batch.column(agg.input)
            if col.valid is not None:
                mask = mask & jnp.asarray(col.valid)
        n = rows if mask is live else count_of(mask)
        if agg.kind in ("count_star", "count"):
            cols[agg.output] = Column(BIGINT, n.astype(jnp.int64), None)
            continue
        vals, ident, out_type = _scatter_operand(col, agg.kind)
        cols[agg.output] = Column(
            out_type, reduce_(jnp.where(mask, vals, ident), ident, agg.kind),
            n > 0)
    return cols


def _scatter_operand(col: Column, kind: str):
    """(values in the accumulator's type, the identity, the SQL type of
    the result) of a sum, min or max over ``col``."""
    vals = jnp.asarray(col.data)
    if kind == "sum":
        acc = vals.dtype if vals.dtype in (
            jnp.float32, jnp.float64) else jnp.int64
        return vals.astype(acc), jnp.asarray(0, acc), _sum_type(col.type)
    return vals, _identity_for(kind, vals.dtype), col.type


def group_aggregate(batch: Batch, key_names: Sequence[str],
                    aggs: Sequence[AggInput],
                    groups_capacity: Optional[int] = None,
                    live: Optional[jax.Array] = None,
                    dense: Optional[DenseKeys] = None) -> Batch:
    """GROUP BY key_names with the given aggregates.

    Returns a Batch of key columns + aggregate columns, capacity-padded to
    ``groups_capacity`` (default: input capacity) with device num_groups.
    Aggregate null semantics: sum/min/max over zero non-null inputs yield
    NULL; count yields 0 (SQL standard, matching reference
    operator/aggregation/LongSumAggregation.java).

    ``live`` overrides the batch's prefix liveness with an explicit row
    mask (selection-vector execution: a fused upstream filter passes its
    mask here instead of compacting — compaction's nonzero+gather costs
    seconds at SF1 row counts on TPU).

    Three kernels (the BigintGroupByHash / MultiChannelGroupByHash split
    of the reference, re-specialized for TPU):
    - packed fast path when every key has a small STATIC domain
      (dictionary codes, bools): group id = packed key, aggregates =
      unrolled masked reductions — no sort, no gather, no scatter, which
      are all pathologically slow on TPU (measured v5e: lexsort 2.5s,
      take 5.1s, segment_sum 0.6s vs masked reduction 29ms at 8M rows).
    - dense: ONE integer key whose live values span less than
      ``dense_slots(capacity)`` (``dense_eligible``, ``dense_fits``):
      group id = key - least key, every aggregate a scatter into the
      slots (``dense_group_slots``), the slots that hold a group
      compacted — no sort at all. The least key is DATA: a caller
      inside a traced program passes it (``dense``, from one counted
      read of ``dense_key_range``); an eager call reads it here.
      Where the span does not fit, the general path runs.
    - general path: stable lexsort on key lanes + segment ops.
    """
    cap = batch.capacity
    gcap = groups_capacity or cap
    fast = _packed_group_aggregate(batch, key_names, aggs, gcap, live,
                                   clamp=groups_capacity is None)
    if fast is not None:
        note_form("packed", cap)
        return fast
    if dense is None and dense_eligible(batch, key_names, aggs):
        dense = _read_dense_keys(batch, key_names, live)
    if dense is not None:
        note_form("dense", cap)
        slots, exists = dense_group_slots(batch, key_names, aggs, dense,
                                          live)
        from .compact import compact_batch
        return compact_batch(slots, exists, gcap)
    note_form("sort", cap)
    live = batch.row_valid() if live is None else live

    lanes = _key_lanes(batch, key_names, live)
    order = stable_lexsort(lanes)
    live_s = jnp.take(live, order)

    # key-change boundaries over the sorted live prefix
    changed = jnp.zeros((cap,), dtype=bool)
    for lane in lanes[1:]:
        s = jnp.take(lane, order)
        changed = changed | (s != jnp.roll(s, 1))
    first = jnp.arange(cap) == 0
    boundary = (changed | first) & live_s
    gid = jnp.cumsum(boundary.astype(jnp.int64)) - 1
    num_groups = jnp.sum(boundary.astype(jnp.int64))
    gid_c = jnp.clip(gid, 0, gcap - 1).astype(jnp.int32)

    # first-row position of each group -> gather for key output
    grp_first = jnp.nonzero(boundary, size=gcap, fill_value=0)[0]
    grp_rows = jnp.take(order, grp_first)

    out_cols: Dict[str, Column] = {}
    for name in key_names:
        out_cols[name] = batch.column(name).gather(grp_rows)

    for agg in aggs:
        out_cols[agg.output] = _segment_agg(
            batch, agg, order, gid_c, live_s, gcap, lanes, live)

    return Batch(out_cols, num_groups)


def _int128_lanes(col: Column, order=None):
    lo = jnp.asarray(col.data).astype(jnp.int64)
    # a short-decimal input sign-extends into the hi lane (its sum can
    # still overflow int64 — that's why the SQL sum type is DECIMAL(38))
    hi = (jnp.asarray(col.data2).astype(jnp.int64)
          if col.data2 is not None else lo >> 63)
    if order is not None:
        lo = jnp.take(lo, order)
        hi = jnp.take(hi, order)
    return lo, hi


def _wide_decimal_agg(col: Column, kind: str) -> bool:
    """True when the aggregate must run on Int128 lanes: any long
    decimal, and a short-decimal SUM that could overflow int64
    (reference: DecimalSumAggregation accumulates in Int128). Column
    capacity is static, so capacity * 10^precision < 2^63 proves the
    single-lane int64 sum exact — keeps the hot TPC-H money sums
    (DECIMAL(12,2) at sf1) on the 1-lane kernel."""
    from ..types import DecimalType as _Dec
    if not isinstance(col.type, _Dec):
        return False
    if col.data2 is not None:
        return kind in ("sum", "min", "max")
    if kind != "sum":
        return False
    cap = int(jnp.asarray(col.data).shape[0])
    return cap * (10 ** col.type.precision) >= 2 ** 63


def _int128_masked_agg(col: Column, kind: str, gmasks, group_valid,
                       order=None) -> Column:
    """sum/min/max over DECIMAL(p>18) for the mask-per-group kernels.

    sum: each value decomposes into three int64 addend lanes
    (w0 + w1*2^32 + hi*2^64, 0 <= w0,w1 < 2^32) so per-group sums of up
    to 2^31 rows stay exact; lanes recombine with carry propagation.
    min/max: composite order (hi major signed, lo minor unsigned via
    the sign-flip trick). Reference: Int128 state of
    spi/type/Int128Math.java + DecimalSumAggregation."""
    from . import int128 as i128
    lo, hi = _int128_lanes(col, order)
    if kind == "sum":
        w0, w1, w2 = i128.sum_lanes(lo, hi)
        z = jnp.int64(0)
        s0 = jnp.stack([jnp.sum(jnp.where(g, w0, z)) for g in gmasks])
        s1 = jnp.stack([jnp.sum(jnp.where(g, w1, z)) for g in gmasks])
        s2 = jnp.stack([jnp.sum(jnp.where(g, w2, z)) for g in gmasks])
        slo, shi = i128.combine_sums(s0, s1, s2)
        return Column(_sum_type(col.type), slo, group_valid, data2=shi)
    red = jnp.min if kind == "min" else jnp.max
    ident = _identity_for(kind, jnp.int64)
    mhi = jnp.stack([red(jnp.where(g, hi, ident)) for g in gmasks])
    sbit = jnp.int64(-(2 ** 63))
    ulo = lo ^ sbit
    mlo = jnp.stack([red(jnp.where(g & (hi == mhi[k]), ulo, ident))
                     for k, g in enumerate(gmasks)]) ^ sbit
    return Column(col.type, mlo, group_valid, data2=mhi)


def _int128_segment_agg(col: Column, kind: str, valid, order, gid,
                        gcap: int, group_valid) -> Column:
    """sum/min/max over DECIMAL(p>18) for the lexsort/segment kernel
    (same lane decomposition as _int128_masked_agg)."""
    from . import int128 as i128
    lo, hi = _int128_lanes(col, order)
    if kind == "sum":
        w0, w1, w2 = i128.sum_lanes(lo, hi)
        z = jnp.int64(0)
        s0 = jax.ops.segment_sum(jnp.where(valid, w0, z), gid,
                                 num_segments=gcap)
        s1 = jax.ops.segment_sum(jnp.where(valid, w1, z), gid,
                                 num_segments=gcap)
        s2 = jax.ops.segment_sum(jnp.where(valid, w2, z), gid,
                                 num_segments=gcap)
        slo, shi = i128.combine_sums(s0, s1, s2)
        return Column(_sum_type(col.type), slo, group_valid, data2=shi)
    seg = jax.ops.segment_min if kind == "min" else jax.ops.segment_max
    ident = _identity_for(kind, jnp.int64)
    mhi = seg(jnp.where(valid, hi, ident), gid, num_segments=gcap)
    sbit = jnp.int64(-(2 ** 63))
    ulo = lo ^ sbit
    elig = valid & (hi == jnp.take(mhi, gid))
    mlo = seg(jnp.where(elig, ulo, ident), gid, num_segments=gcap) ^ sbit
    return Column(col.type, mlo, group_valid, data2=mhi)


def _segment_agg(batch: Batch, agg: AggInput, order, gid, live_s,
                 gcap: int, key_lanes=None, live_u=None) -> Column:
    from ..types import BIGINT, DOUBLE, is_string

    extra_mask = None
    if agg.mask is not None:
        mcol = batch.column(agg.mask)
        m = jnp.take(jnp.asarray(mcol.data).astype(bool), order)
        if mcol.valid is not None:
            m = m & jnp.take(jnp.asarray(mcol.valid), order)
        extra_mask = m

    if agg.kind == "count_star":
        ones = live_s.astype(jnp.int64)
        if extra_mask is not None:
            ones = jnp.where(extra_mask, ones, 0)
        data = jax.ops.segment_sum(ones, gid, num_segments=gcap)
        return Column(BIGINT, data, None)

    col = batch.column(agg.input)
    vals = jnp.take(jnp.asarray(col.data), order)
    valid = live_s if col.valid is None else (
        live_s & jnp.take(jnp.asarray(col.valid), order))
    if extra_mask is not None:
        valid = valid & extra_mask

    if agg.kind == "count":
        data = jax.ops.segment_sum(valid.astype(jnp.int64), gid,
                                   num_segments=gcap)
        return Column(BIGINT, data, None)

    nvalid = jax.ops.segment_sum(valid.astype(jnp.int64), gid,
                                 num_segments=gcap)
    group_valid = nvalid > 0

    if _wide_decimal_agg(col, agg.kind):
        return _int128_segment_agg(col, agg.kind, valid, order, gid,
                                   gcap, group_valid)

    if agg.kind == "sum":
        acc_dtype = vals.dtype if vals.dtype in (
            jnp.float32, jnp.float64) else jnp.int64
        masked = jnp.where(valid, vals.astype(acc_dtype),
                           jnp.asarray(0, acc_dtype))
        data = jax.ops.segment_sum(masked, gid, num_segments=gcap)
        return Column(_sum_type(col.type), data, group_valid)

    if agg.kind in ("bit_and", "bit_or"):
        # segmented associative scan over the group-sorted rows (AND/OR
        # have no jax.ops.segment_* primitive; they are associative and
        # commutative, so a (gid, value) scan + last-of-segment gather is
        # exact — reference: BitwiseAndAggregation/BitwiseOrAggregation)
        op = jnp.bitwise_and if agg.kind == "bit_and" else jnp.bitwise_or
        ident = jnp.asarray(-1 if agg.kind == "bit_and" else 0, jnp.int64)
        work = jnp.where(valid, vals.astype(jnp.int64), ident)
        gid64 = gid.astype(jnp.int64)

        def _comb(a, b):
            ga, va = a
            gb, vb = b
            return gb, jnp.where(ga == gb, op(va, vb), vb)

        _, scanned = jax.lax.associative_scan(_comb, (gid64, work))
        cap = order.shape[0]
        pos = jnp.arange(cap, dtype=jnp.int64)
        last = jax.ops.segment_max(
            jnp.where(live_s, pos, jnp.int64(-1)), gid, num_segments=gcap)
        data = jnp.take(scanned, jnp.clip(last, 0, cap - 1))
        return Column(BIGINT, data, group_valid)

    if agg.kind in ("min", "max"):
        seg = jax.ops.segment_min if agg.kind == "min" else \
            jax.ops.segment_max
        if is_string(col.type):
            rvals, ident, decode = _string_minmax_lane(col, vals,
                                                       agg.kind)
            data = decode(seg(jnp.where(valid, rvals, ident), gid,
                              num_segments=gcap))
            return Column(col.type, data, group_valid,
                          dictionary=col.dictionary)
        as_bool = vals.dtype == jnp.bool_
        work = vals.astype(jnp.int32) if as_bool else vals
        ident = _identity_for(agg.kind, work.dtype)
        data = seg(jnp.where(valid, work, ident), gid,
                   num_segments=gcap)
        if as_bool:
            data = data.astype(jnp.bool_)
        return Column(col.type, data, group_valid)

    if agg.kind == "any_value":
        # first VALID row of the group (respecting FILTER mask); NULL only
        # when the group has no valid value — matches global_aggregate
        cap = order.shape[0]
        pos = jnp.arange(cap, dtype=jnp.int64)
        grp_first = jax.ops.segment_min(
            jnp.where(valid, pos, jnp.int64(cap)), gid, num_segments=gcap)
        rows = jnp.take(order, jnp.clip(grp_first, 0, cap - 1))
        from dataclasses import replace as _replace
        return _replace(col.gather(rows), valid=group_valid)

    if agg.kind in ("argmin", "argmax"):
        # min_by/max_by: the value of `input` at the row where `input2`
        # is extreme (reference: operator/aggregation/
        # MinMaxByAggregationFunction.java). Two segment passes: the
        # extreme comparator, then the first row attaining it.
        from dataclasses import replace as _replace
        cap = order.shape[0]
        comp = batch.column(agg.input2)
        if comp.data2 is not None:
            raise NotImplementedError(
                f"{agg.kind} by DECIMAL(p>18) is not supported yet")
        cvalid = live_s if comp.valid is None else (
            live_s & jnp.take(jnp.asarray(comp.valid), order))
        if extra_mask is not None:
            cvalid = cvalid & extra_mask
        work, _ = _order_lane(comp, order)
        lo = agg.kind == "argmin"
        ident = _identity_for("min" if lo else "max", work.dtype)
        work = jnp.where(cvalid & ~_isnan(work), work, ident)
        seg = jax.ops.segment_min if lo else jax.ops.segment_max
        ext = seg(work, gid, num_segments=gcap)
        cand = cvalid & (work == jnp.take(ext, gid))
        pos = jnp.arange(cap, dtype=jnp.int64)
        first = jax.ops.segment_min(
            jnp.where(cand, pos, jnp.int64(cap)), gid, num_segments=gcap)
        rows = jnp.take(order, jnp.clip(first, 0, cap - 1))
        gv = jax.ops.segment_sum(cvalid.astype(jnp.int64), gid,
                                 num_segments=gcap) > 0
        out = col.gather(rows)
        ov = gv if out.valid is None else gv & jnp.asarray(out.valid)
        return _replace(out, valid=ov)

    if agg.kind == "hll":
        # approx_set: per-group sparse HLL entries, one extra sort +
        # segment pass (reference: ApproximateSetAggregation; design
        # note in ops/hll.py)
        from ..types import HyperLogLogType, INTEGER as _INT
        from .hll import DEFAULT_BUCKET_BITS, grouped_sparse_hll
        b = int(agg.param) if agg.param else DEFAULT_BUCKET_BITS
        start, length, entries = grouped_sparse_hll(vals, valid, gid,
                                                    gcap, b)
        return Column(HyperLogLogType(b), start, group_valid, None,
                      length, Column(_INT, entries))

    if agg.kind == "hll_merge":
        # merge(hll): per-group max-union of sketch rows. Host numpy —
        # merge consumes small pre-aggregated sketch batches, and the
        # chain-JIT falls back to eager execution on the host round
        # trip (reference: MergeHyperLogLogAggregation)
        from ..types import HyperLogLogType, INTEGER as _INT
        from .hll import merge_sparse_host
        b = getattr(col.type, "bucket_bits", 11)
        import numpy as _onp
        starts = _onp.asarray(jax.device_get(vals))
        lens = _onp.asarray(jax.device_get(
            jnp.take(jnp.asarray(col.data2), order)))
        ent = _onp.asarray(jax.device_get(col.elements.data))
        v_np = _onp.asarray(jax.device_get(valid))
        g_np = _onp.asarray(jax.device_get(gid))
        start, length, out_ent = merge_sparse_host(
            starts, lens, ent, v_np, g_np, gcap, b)
        cap_e = max(int(out_ent.shape[0]), 1)
        from ..config import capacity_for as _cfor
        pad = _cfor(cap_e)
        out_ent = _onp.pad(out_ent, (0, pad - out_ent.shape[0]))
        return Column(HyperLogLogType(b), jnp.asarray(start),
                      group_valid, None, jnp.asarray(length),
                      Column(_INT, jnp.asarray(out_ent)))

    if agg.kind in ("count_distinct", "percentile"):
        return _resorted_agg(batch, agg, col, gid, live_s, gcap,
                             key_lanes, extra_mask, order, live_u)

    if agg.kind == "array_agg":
        # group runs are contiguous in the sorted order: the flat
        # elements column IS the group-sorted input; each group's array
        # is (first position, included-row count). FILTER-masked rows
        # are sunk to the end of their group run by a secondary sort
        # lane so inclusion stays a prefix (reference:
        # operator/aggregation/ArrayAggregationFunction — NULL inputs
        # are collected, masked rows are not).
        from ..types import ArrayType
        from dataclasses import replace as _replace
        cap = order.shape[0]
        include = live_s if extra_mask is None else (live_s & extra_mask)
        if extra_mask is not None:
            live = (batch.row_valid() if live_u is None else live_u)
            inc_u = jnp.zeros((cap,), bool).at[order].set(include)
            use_order, use_gid, _, _, _ = _resort(
                key_lanes, [(~inc_u).astype(jnp.uint64)], live, gcap)
            use_inc = jnp.take(inc_u, use_order)
        else:
            use_order, use_gid, use_inc = order, gid, include
        pos = jnp.arange(cap, dtype=jnp.int64)
        start = jax.ops.segment_min(
            jnp.where(use_inc, pos, jnp.int64(cap)), use_gid,
            num_segments=gcap)
        length = jax.ops.segment_sum(use_inc.astype(jnp.int64), use_gid,
                                     num_segments=gcap)
        elements = col.gather(use_order)
        return Column(ArrayType(col.type),
                      jnp.clip(start, 0, cap - 1), length > 0, None,
                      length, elements)

    if agg.kind in ("map_agg", "histogram"):
        return _resorted_agg(batch, agg, col, gid, live_s, gcap,
                             key_lanes, extra_mask, order, live_u)

    if agg.kind in ("map_union", "multimap_agg", "numeric_histogram"):
        # host-side collection aggregates (hll_merge pattern; see
        # ops/collections.py module docstring for the rationale)
        from .collections import (grouped_map_union, grouped_multimap_agg,
                                  grouped_numeric_histogram, rows_by_group)
        groups = rows_by_group(order, gid, valid, gcap)
        if agg.kind == "map_union":
            return grouped_map_union(col, groups, group_valid)
        if agg.kind == "multimap_agg":
            return grouped_multimap_agg(col, batch.column(agg.input2),
                                        groups, group_valid)
        from ..types import DecimalType as _Dec
        scale = (10.0 ** col.type.scale
                 if isinstance(col.type, _Dec) else None)
        wcol = batch.column(agg.input2) if agg.input2 else None
        return grouped_numeric_histogram(col, groups, group_valid,
                                         int(agg.param or 2), scale,
                                         wcol)

    if agg.kind in ("tdigest", "qdigest", "digest_merge"):
        from .collections import rows_by_group
        from .digest import (DEFAULT_COMPRESSION, DEFAULT_QDIGEST_BUDGET,
                             grouped_digest, grouped_digest_merge)
        groups = rows_by_group(order, gid, valid, gcap)
        if agg.kind == "digest_merge":
            return grouped_digest_merge(col, groups, group_valid,
                                        _merge_budget(col))
        return _grouped_digest_build(batch, agg, col, groups,
                                     group_valid)

    raise ValueError(f"unknown aggregate kind {agg.kind}")


def _merge_budget(col: Column) -> int:
    """Recompression budget for merge(digest): qdigest sketches carry
    an accuracy budget (2/accuracy nodes) that a merge must not shrink
    — recompressing a 400-node qdigest to tdigest's 100 centroids
    would quadruple the user's requested quantile error. Honor the
    LARGEST input run so merged sketches keep their builders' budget
    (reference: QuantileDigest.merge keeps maxError)."""
    from ..types import QDigestType
    from .digest import DEFAULT_COMPRESSION, DEFAULT_QDIGEST_BUDGET
    base = (DEFAULT_QDIGEST_BUDGET if isinstance(col.type, QDigestType)
            else DEFAULT_COMPRESSION)
    if col.data2 is not None:
        import numpy as _np
        lens = _np.asarray(jax.device_get(col.data2))
        if lens.size:
            base = max(base, int(lens.max()))
    return base


def _grouped_digest_build(batch: Batch, agg: AggInput, col: Column,
                          groups, group_valid) -> Column:
    from ..types import (DecimalType as _Dec, QDigestType, T_DIGEST)
    from .digest import (DEFAULT_COMPRESSION, DEFAULT_QDIGEST_BUDGET,
                         grouped_digest)
    wcol = (batch.column(agg.input2)
            if getattr(agg, "input2", None) else None)
    scale = (10.0 ** col.type.scale
             if isinstance(col.type, _Dec) else None)
    if agg.kind == "tdigest":
        return grouped_digest(col, groups, group_valid, T_DIGEST,
                              DEFAULT_COMPRESSION, wcol, scale)
    budget = (int(2.0 / float(agg.param))
              if agg.param else DEFAULT_QDIGEST_BUDGET)
    return grouped_digest(col, groups, group_valid,
                          QDigestType(col.type), budget, wcol, scale)


def _isnan(x: jax.Array) -> jax.Array:
    if x.dtype in (jnp.float32, jnp.float64):
        return jnp.isnan(x)
    return jnp.zeros(x.shape, bool)


def _order_lane(col: Column, order=None) -> Tuple[jax.Array, object]:
    """A single lane whose numeric order == the SQL order of the column
    (collation ranks for strings, int32 for bools); second return is the
    rank->code decoder (strings only)."""
    from ..types import is_string
    d = jnp.asarray(col.data)
    decoder = None
    if is_string(col.type):
        ranks = col.dictionary.rank_codes()
        decoder = jnp.asarray(_invert_permutation(ranks))
        d = jnp.take(jnp.asarray(ranks), d, mode="clip").astype(jnp.int32)
    elif d.dtype == jnp.bool_:
        d = d.astype(jnp.int32)
    if order is not None:
        d = jnp.take(d, order)
    return d, decoder


def _resort(key_lanes, tie_lanes, live, gcap: int):
    """Re-sort rows by (key lanes, tie lanes) and recompute group ids.
    Group ids stay aligned with the primary sort of group_aggregate
    because both orders sort by the key lanes first. Returns
    (order2, gid2, live_s2, key_changed, is_first)."""
    cap = live.shape[0]
    full = list(key_lanes) + list(tie_lanes)
    order2 = jnp.lexsort(full[::-1])
    live_s2 = jnp.take(live, order2)
    changed = jnp.zeros((cap,), dtype=bool)
    for lane in key_lanes[1:]:
        s = jnp.take(lane, order2)
        changed = changed | (s != jnp.roll(s, 1))
    first = jnp.arange(cap) == 0
    boundary2 = (changed | first) & live_s2
    gid2 = jnp.clip(jnp.cumsum(boundary2.astype(jnp.int64)) - 1,
                    0, gcap - 1).astype(jnp.int32)
    return order2, gid2, live_s2, changed, first


def _resorted_agg(batch: Batch, agg: AggInput, col: Column, gid, live_s,
                  gcap: int, key_lanes, extra_mask, order,
                  live_u=None) -> Column:
    """Aggregates that need rows RE-sorted by (keys, value): exact
    count_distinct (reference approximates with HLL —
    ApproximateCountDistinctAggregation.java; exact is a superset) and
    exact percentile (reference: qdigest approx_percentile). Group ids
    stay aligned with the primary sort because both orders sort by the
    key lanes first."""
    from ..types import BIGINT
    cap = order.shape[0]
    live = batch.row_valid() if live_u is None else live_u
    valid_u = live if col.valid is None else live & jnp.asarray(col.valid)
    if agg.mask is not None:
        mcol = batch.column(agg.mask)
        m = jnp.asarray(mcol.data).astype(bool)
        if mcol.valid is not None:
            m = m & jnp.asarray(mcol.valid)
        valid_u = valid_u & m

    if agg.kind in ("count_distinct", "map_agg", "histogram"):
        vlanes = equality_lanes(col.data)
        if col.data2 is not None:
            vlanes = vlanes + equality_lanes(col.data2)
        vlanes = [jnp.where(valid_u, u, jnp.zeros_like(u))
                  for u in vlanes]
        tie = [(~valid_u).astype(jnp.uint64)] + vlanes
    else:
        if col.data2 is not None:
            raise NotImplementedError(
                "percentile over DECIMAL(p>18) is not supported yet")
        olane, _ = _order_lane(col)
        tie = [(~valid_u).astype(jnp.uint64), olane]

    order2, gid2, live_s2, changed_k, first = _resort(
        key_lanes, tie, live, gcap)
    valid2 = jnp.take(valid_u, order2)

    if agg.kind in ("count_distinct", "map_agg", "histogram"):
        changed_v = changed_k
        for lane in tie:
            s = jnp.take(lane, order2)
            changed_v = changed_v | (s != jnp.roll(s, 1))
        newval = (changed_v | first) & valid2
        if agg.kind == "count_distinct":
            data = jax.ops.segment_sum(newval.astype(jnp.int64), gid2,
                                       num_segments=gcap)
            return Column(BIGINT, data, None)
        # map_agg / histogram: each (group, distinct key) run is one
        # map entry; runs are (group, key)-major so per-group entry
        # ranges are contiguous (reference: operator/aggregation/
        # MapAggregationFunction / histogram/Histogram.java)
        from ..types import MapType
        runid = jnp.clip(jnp.cumsum(newval.astype(jnp.int64)) - 1,
                         0, cap - 1).astype(jnp.int32)
        pos = jnp.arange(cap, dtype=jnp.int64)
        run_start = jax.ops.segment_min(
            jnp.where(newval, pos, jnp.int64(cap)), runid,
            num_segments=cap)
        entry_rows = jnp.take(order2, jnp.clip(run_start, 0, cap - 1))
        keys_pool = col.gather(entry_rows)
        first_run = jax.ops.segment_min(
            jnp.where(newval, runid.astype(jnp.int64), jnp.int64(cap)),
            gid2, num_segments=gcap)
        nentries = jax.ops.segment_sum(newval.astype(jnp.int64), gid2,
                                       num_segments=gcap)
        if agg.kind == "histogram":
            counts = jax.ops.segment_sum(
                valid2.astype(jnp.int64), runid, num_segments=cap)
            vals_pool = Column(BIGINT, counts, None)
            out_t = MapType(col.type, BIGINT)
        else:
            vcol = batch.column(agg.input2)
            vals_pool = vcol.gather(entry_rows)
            out_t = MapType(col.type, vcol.type)
        return Column(out_t, jnp.clip(first_run, 0, cap - 1),
                      nentries > 0, None, nentries, keys_pool,
                      vals_pool)

    # exact percentile: valid rows of each group are a contiguous
    # ascending run starting at the group boundary (invalids sort last
    # within the group); pick the nearest-rank element
    from dataclasses import replace as _replace
    pos = jnp.arange(cap, dtype=jnp.int64)
    start = jax.ops.segment_min(
        jnp.where(live_s2, pos, jnp.int64(cap)), gid2, num_segments=gcap)
    nvalid = jax.ops.segment_sum(valid2.astype(jnp.int64), gid2,
                                 num_segments=gcap)
    q = float(agg.param if agg.param is not None else 0.5)
    k = jnp.clip(jnp.floor(q * (nvalid - 1).astype(jnp.float64) + 0.5)
                 .astype(jnp.int64), 0, jnp.maximum(nvalid - 1, 0))
    rows = jnp.take(order2, jnp.clip(start + k, 0, cap - 1))
    out = col.gather(rows)
    return _replace(out, valid=nvalid > 0)


def _invert_permutation(ranks):
    import numpy as np
    inv = np.empty(len(ranks), dtype=np.int32)
    inv[np.asarray(ranks)] = np.arange(len(ranks), dtype=np.int32)
    return inv


def _sum_type(t):
    from ..types import BIGINT, DOUBLE, REAL, DecimalType, is_integral
    if is_integral(t):
        return BIGINT
    if isinstance(t, DecimalType):
        return DecimalType(38, t.scale)
    if t.name == "real":
        return REAL
    return DOUBLE


def global_aggregate(batch: Batch, aggs: Sequence[AggInput],
                     live: Optional[jax.Array] = None) -> Batch:
    """Aggregation without GROUP BY (reference: operator/
    AggregationOperator.java) — masked full reductions, one output row.
    ``live`` as in group_aggregate (selection-vector input)."""
    from ..types import BIGINT

    live = batch.row_valid() if live is None else live
    out: Dict[str, Column] = {}
    for agg in aggs:
        extra = None
        if agg.mask is not None:
            mcol = batch.column(agg.mask)
            extra = jnp.asarray(mcol.data).astype(bool)
            if mcol.valid is not None:
                extra = extra & jnp.asarray(mcol.valid)
        if agg.kind == "count_star":
            m = live if extra is None else (live & extra)
            out[agg.output] = Column(
                BIGINT, jnp.sum(m.astype(jnp.int64))[None], None)
            continue
        col = batch.column(agg.input)
        vals = jnp.asarray(col.data)
        valid = live if col.valid is None else live & jnp.asarray(col.valid)
        if extra is not None:
            valid = valid & extra
        n = jnp.sum(valid.astype(jnp.int64))
        if agg.kind == "count":
            out[agg.output] = Column(BIGINT, n[None], None)
            continue
        has = (n > 0)[None]
        if _wide_decimal_agg(col, agg.kind):
            out[agg.output] = _int128_masked_agg(col, agg.kind, [valid],
                                                 has)
            continue
        if agg.kind == "sum":
            acc_dtype = vals.dtype if vals.dtype in (
                jnp.float32, jnp.float64) else jnp.int64
            s = jnp.sum(jnp.where(valid, vals.astype(acc_dtype),
                                  jnp.asarray(0, acc_dtype)))[None]
            out[agg.output] = Column(_sum_type(col.type), s, has)
        elif agg.kind in ("min", "max"):
            from ..types import is_string as _is_str
            if _is_str(col.type):
                rvals, ident, decode = _string_minmax_lane(
                    col, vals, agg.kind)
                masked = jnp.where(valid, rvals, ident)
                r = (jnp.min(masked) if agg.kind == "min"
                     else jnp.max(masked))
                r = decode(r)[None]
                out[agg.output] = Column(col.type, r, has,
                                         dictionary=col.dictionary)
            else:
                as_bool = vals.dtype == jnp.bool_
                work = vals.astype(jnp.int32) if as_bool else vals
                ident = _identity_for(agg.kind, work.dtype)
                masked = jnp.where(valid, work, ident)
                r = (jnp.min(masked) if agg.kind == "min"
                     else jnp.max(masked))[None]
                if as_bool:
                    r = r.astype(jnp.bool_)
                out[agg.output] = Column(col.type, r, has)
        elif agg.kind == "any_value":
            from dataclasses import replace as _replace
            idx = jnp.argmax(valid)  # first valid row (0 if none)
            out[agg.output] = _replace(col.gather(idx[None]), valid=has)
        elif agg.kind in ("bit_and", "bit_or"):
            op = (jnp.bitwise_and if agg.kind == "bit_and"
                  else jnp.bitwise_or)
            ident = jnp.asarray(-1 if agg.kind == "bit_and" else 0,
                                jnp.int64)
            masked = jnp.where(valid, vals.astype(jnp.int64), ident)
            r = jax.lax.reduce(masked, ident, op, (0,))[None]
            out[agg.output] = Column(BIGINT, r, has)
        elif agg.kind in ("argmin", "argmax"):
            from dataclasses import replace as _replace
            comp = batch.column(agg.input2)
            if comp.data2 is not None:
                raise NotImplementedError(
                    f"{agg.kind} by DECIMAL(p>18) is not supported yet")
            cvalid = live if comp.valid is None else (
                live & jnp.asarray(comp.valid))
            if extra is not None:
                cvalid = cvalid & extra
            work, _ = _order_lane(comp)
            lo = agg.kind == "argmin"
            ident = _identity_for("min" if lo else "max", work.dtype)
            work = jnp.where(cvalid & ~_isnan(work), work, ident)
            idx = jnp.argmin(work) if lo else jnp.argmax(work)
            gv = jnp.any(cvalid)[None]
            res = col.gather(idx[None])
            ov = gv if res.valid is None else gv & jnp.asarray(res.valid)
            out[agg.output] = _replace(res, valid=ov)
        elif agg.kind == "count_distinct":
            vlanes = equality_lanes(col.data)
            if col.data2 is not None:
                vlanes = vlanes + equality_lanes(col.data2)
            vlanes = [jnp.where(valid, u, jnp.zeros_like(u))
                      for u in vlanes]
            full = [(~valid).astype(jnp.uint64)] + vlanes
            order2 = jnp.lexsort(full[::-1])
            valid2 = jnp.take(valid, order2)
            changed = jnp.arange(batch.capacity) == 0
            for lane in vlanes:
                s = jnp.take(lane, order2)
                changed = changed | (s != jnp.roll(s, 1))
            cnt = jnp.sum((changed & valid2).astype(jnp.int64))
            out[agg.output] = Column(BIGINT, cnt[None], None)
        elif agg.kind == "array_agg":
            from ..types import ArrayType
            # included rows (live, FILTER-passing; NULL values stay)
            inc = live if extra is None else live & extra
            order2 = jnp.lexsort([(~inc).astype(jnp.uint64)][::-1])
            elements = col.gather(order2)
            n_inc = jnp.sum(inc.astype(jnp.int64))
            out[agg.output] = Column(
                ArrayType(col.type), jnp.zeros((1,), jnp.int64),
                (n_inc > 0)[None], None, n_inc[None], elements)
        elif agg.kind in ("map_agg", "histogram"):
            from ..types import MapType
            vlanes = equality_lanes(col.data)
            if col.data2 is not None:
                vlanes = vlanes + equality_lanes(col.data2)
            vlanes = [jnp.where(valid, u, jnp.zeros_like(u))
                      for u in vlanes]
            full = [(~valid).astype(jnp.uint64)] + vlanes
            order2 = jnp.lexsort(full[::-1])
            valid2 = jnp.take(valid, order2)
            cap = batch.capacity
            changed = jnp.arange(cap) == 0
            for lane in vlanes:
                s = jnp.take(lane, order2)
                changed = changed | (s != jnp.roll(s, 1))
            newent = (changed | (jnp.arange(cap) == 0)) & valid2
            runid = jnp.clip(jnp.cumsum(newent.astype(jnp.int64)) - 1,
                             0, cap - 1).astype(jnp.int32)
            pos = jnp.arange(cap, dtype=jnp.int64)
            run_start = jax.ops.segment_min(
                jnp.where(newent, pos, jnp.int64(cap)), runid,
                num_segments=cap)
            entry_rows = jnp.take(order2,
                                  jnp.clip(run_start, 0, cap - 1))
            keys_pool = col.gather(entry_rows)
            nent = jnp.sum(newent.astype(jnp.int64))
            if agg.kind == "histogram":
                counts = jax.ops.segment_sum(
                    valid2.astype(jnp.int64), runid, num_segments=cap)
                vals_pool = Column(BIGINT, counts, None)
                out_t = MapType(col.type, BIGINT)
            else:
                vcol = batch.column(agg.input2)
                vals_pool = vcol.gather(entry_rows)
                out_t = MapType(col.type, vcol.type)
            out[agg.output] = Column(
                out_t, jnp.zeros((1,), jnp.int64), (nent > 0)[None],
                None, nent[None], keys_pool, vals_pool)
        elif agg.kind in ("map_union", "multimap_agg",
                          "numeric_histogram"):
            from .collections import (grouped_map_union,
                                      grouped_multimap_agg,
                                      grouped_numeric_histogram,
                                      rows_by_group)
            cap = batch.capacity
            ident = jnp.arange(cap, dtype=jnp.int64)
            gid0 = jnp.zeros((cap,), jnp.int32)
            groups = rows_by_group(ident, gid0, valid, 1)
            if agg.kind == "map_union":
                out[agg.output] = grouped_map_union(col, groups, has)
            elif agg.kind == "multimap_agg":
                out[agg.output] = grouped_multimap_agg(
                    col, batch.column(agg.input2), groups, has)
            else:
                from ..types import DecimalType as _Dec
                scale = (10.0 ** col.type.scale
                         if isinstance(col.type, _Dec) else None)
                wcol = (batch.column(agg.input2) if agg.input2
                        else None)
                out[agg.output] = grouped_numeric_histogram(
                    col, groups, has, int(agg.param or 2), scale, wcol)
        elif agg.kind in ("tdigest", "qdigest", "digest_merge"):
            from .collections import rows_by_group
            from .digest import (DEFAULT_COMPRESSION,
                                 grouped_digest_merge)
            cap = batch.capacity
            ident = jnp.arange(cap, dtype=jnp.int64)
            gid0 = jnp.zeros((cap,), jnp.int32)
            groups = rows_by_group(ident, gid0, valid, 1)
            if agg.kind == "digest_merge":
                out[agg.output] = grouped_digest_merge(
                    col, groups, has, _merge_budget(col))
            else:
                out[agg.output] = _grouped_digest_build(
                    batch, agg, col, groups, has)
        elif agg.kind == "hll":
            from ..types import HyperLogLogType, INTEGER as _INT
            from .hll import DEFAULT_BUCKET_BITS, grouped_sparse_hll
            b = int(agg.param) if agg.param else DEFAULT_BUCKET_BITS
            gid0 = jnp.zeros((batch.capacity,), jnp.int32)
            start, length, entries = grouped_sparse_hll(vals, valid,
                                                        gid0, 1, b)
            out[agg.output] = Column(
                HyperLogLogType(b), start, has, None, length,
                Column(_INT, entries))
        elif agg.kind == "hll_merge":
            from ..types import HyperLogLogType, INTEGER as _INT
            from .hll import merge_sparse_host
            from ..config import capacity_for as _cfor
            b = getattr(col.type, "bucket_bits", 11)
            import numpy as _onp
            starts = _onp.asarray(jax.device_get(vals))
            lens = _onp.asarray(jax.device_get(col.data2))
            ent = _onp.asarray(jax.device_get(col.elements.data))
            v_np = _onp.asarray(jax.device_get(valid))
            g_np = _onp.zeros(batch.capacity, _onp.int64)
            start, length, out_ent = merge_sparse_host(
                starts, lens, ent, v_np, g_np, 1, b)
            pad = _cfor(max(int(out_ent.shape[0]), 1))
            out_ent = _onp.pad(out_ent, (0, pad - out_ent.shape[0]))
            out[agg.output] = Column(
                HyperLogLogType(b), jnp.asarray(start), has, None,
                jnp.asarray(length), Column(_INT, jnp.asarray(out_ent)))
        elif agg.kind == "percentile":
            from dataclasses import replace as _replace
            if col.data2 is not None:
                raise NotImplementedError(
                    "percentile over DECIMAL(p>18) is not supported yet")
            olane, _ = _order_lane(col)
            full = [(~valid).astype(jnp.uint64), olane]
            order2 = jnp.lexsort(full[::-1])
            q = float(agg.param if agg.param is not None else 0.5)
            k = jnp.clip(jnp.floor(q * (n - 1).astype(jnp.float64) + 0.5)
                         .astype(jnp.int64), 0, jnp.maximum(n - 1, 0))
            rows = jnp.take(order2, k[None])
            out[agg.output] = _replace(col.gather(rows), valid=has)
        else:
            raise ValueError(f"unknown aggregate kind {agg.kind}")
    return Batch(out, 1)
