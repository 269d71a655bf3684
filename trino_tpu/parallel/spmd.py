"""SPMD collective kernels: the TPU data plane.

Reference parity: the exchange layer — PartitionedOutputOperator.java:55
(hash partition + scatter into per-partition buffers), ExchangeClient.java
:149 (pull + merge), BroadcastOutputBuffer (replicate). TPU-first redesign
(SURVEY.md §2.7, §7.4): REMOTE REPARTITION == ``jax.lax.all_to_all`` over
the ICI mesh inside a ``shard_map``; REPLICATE == ``all_gather``; GATHER
== collect on the coordinator (mesh.py unshard_batch). There is no wire
serde or pull/ack protocol inside a slice — XLA schedules the collective.

The same columnar kernels (ops/groupby, ops/join, exec/expr) run
unchanged inside the shard_map trace: a Trino *task* is the per-shard
slice of one SPMD program. Host syncs happen only between programs, for
data-dependent capacity decisions (the two-phase pattern of
ops/join.py, lifted to the distributed case).

Mesh programs are cached and named like every other program of the
engine (exec/progkey.py, bucket "spmd"): ``mesh_program`` keeps ONE
jitted ``shard_map`` per (kind, key, mesh, operand structure) under the
name ``spmd_<kind>_<key8>``, so a repeated query traces nothing, and every
dispatch is a ``dispatch`` / ``jit_trace`` span counted in
``trino_tpu_device_programs_total{kind="spmd_<kind>"}``. A caller of
``shard_apply`` and its kin that cannot name what its closure captures
passes ``key=None`` and gets a per-call program
(``spmd_<kind>_local``); the exchanges always have a key.

The exchange is sized by what is sent. Phase 1 (``exchange_counts``)
gives the [source, destination] row-count matrix; the host reads it
once and picks the per-pair send capacity and the per-shard receive
capacity. Phase 2 bins rows with one ``cumsum`` per destination and one
int32 scatter (no sort), sends ``[n_dev, send_cap]`` buffers through
``all_to_all`` and lays the received runs end to end with an index
computed by arithmetic (no ``nonzero``). Received rows keep
(source shard, source position) order within each destination.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map

from ..columnar import Batch, Column
from ..config import capacity_for
from ..obs.trace import active_span, active_trace, dispatch_span
from ..ops.groupby import AggInput, group_aggregate
from ..ops.hashing import hash_columns
from .mesh import AXIS, ShardedBatch, row_bytes

# --------------------------------------------------------------------------
# keyed, named mesh programs
# --------------------------------------------------------------------------

def _mesh_key(mesh) -> tuple:
    return tuple(int(d.id) for d in mesh.devices.flat)


def mesh_program(kind: str, key, mesh, operands,
                 build: Callable[[], tuple]):
    """The jitted ``shard_map`` program of ``kind`` for ``key`` over
    ``mesh``: (program, hit). ``operands`` is the tuple of input
    pytrees — its STRUCTURE (column names, types, dictionaries, which
    lanes exist) is part of the identity, shapes are not (jax
    specializes per shape under one callable). ``build()`` returns
    ``(f, in_specs, out_specs)`` and runs on a miss only. ``key`` must
    name everything ``f`` closes over; ``None`` means it cannot be
    named, and the program is built for this call alone."""
    from ..exec.progkey import PROGRAMS

    def sharded():
        f, in_specs, out_specs = build()
        return shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    ck = None if key is None else (
        kind, key, _mesh_key(mesh), jax.tree.structure(operands))
    return PROGRAMS.program("spmd", ck, sharded, "spmd_" + kind, key)


def mesh_call(kind: str, key, mesh, operands, build, **attrs):
    """Dispatch the mesh program on ``operands`` under its span (which
    carries ``attrs``). The span times the host's dispatch: the
    program's time on the chips is read on the device trace, and what
    waits for it is the read that needs its outputs (an exchange's
    ``host_read[exchange_done]``). Only EXPLAIN ANALYZE waits here."""
    prog, hit = mesh_program(kind, key, mesh, operands, build)
    trace = active_trace()
    with dispatch_span(trace, prog.program, hit, "spmd_" + kind,
                       **attrs) as sp:
        out = prog(*operands)
        if sp is not None and trace.analyze:
            jax.block_until_ready(out)
    return out


def _col_specs(cols: Dict[str, Column], spec) -> Dict[str, Column]:
    """A pytree of PartitionSpecs shaped like the columns dict."""
    return jax.tree.map(lambda _: spec, cols)


def _sharded_in(sb: ShardedBatch):
    return (_col_specs(sb.columns, P(AXIS)), P())


def _per_shard(cols: Dict[str, Column], n: int) -> int:
    some = next(iter(cols.values()))
    return int(jnp.asarray(some.data).shape[0]) // n


def _lanes(c: Column) -> List[jax.Array]:
    return [jnp.asarray(l) for l in (c.data, c.valid, c.data2)
            if l is not None]


def _relane(c: Column, moved: Sequence[jax.Array]) -> Column:
    it = iter(moved)
    data = next(it)
    valid = next(it) if c.valid is not None else None
    d2 = next(it) if c.data2 is not None else None
    return Column(c.type, data, valid, c.dictionary, d2)


def _read_counts(arr, site: str) -> np.ndarray:
    """The blocking read of a small replicated count vector or matrix
    between the halves of a two-phase pattern."""
    with active_span("host_read", site=site):
        return np.asarray(arr)


# --------------------------------------------------------------------------
# the exchange (runs inside a shard_map trace)
# --------------------------------------------------------------------------

def _hash_pid(cols: Dict[str, Column], key_names: Sequence[str],
              n_dev: int) -> jax.Array:
    h = hash_columns([cols[k] for k in key_names])
    return (h % jnp.uint64(n_dev)).astype(jnp.int32)


def _dest_counts(pid: jax.Array, live: jax.Array, n_dev: int) -> jax.Array:
    """[n_dev] int32: live rows bound for each destination."""
    hit = (pid[None, :] == jnp.arange(n_dev, dtype=jnp.int32)[:, None]) \
        & live[None, :]
    return jnp.sum(hit.astype(jnp.int32), axis=1)


def _shard_exchange(cols: Dict[str, Column], my_n: jax.Array,
                    pid: jax.Array, n_dev: int, send_cap: int,
                    out_cap: int) -> Tuple[Dict[str, Column], jax.Array]:
    """Per-shard exchange body: given each row's destination shard,
    move the live rows there. ``send_cap`` bounds the rows one shard
    sends to one destination, ``out_cap`` the rows one shard receives;
    both come from phase 1's real counts."""
    per = int(pid.shape[0])
    live = jnp.arange(per, dtype=jnp.int32) < my_n.astype(jnp.int32)
    # slot of a row among the live rows with its destination: one
    # running count per destination, no sort
    rank = jnp.zeros((per,), jnp.int32)
    counts = []
    for p in range(n_dev):
        m = live & (pid == p)
        run = jnp.cumsum(m.astype(jnp.int32))
        rank = jnp.where(m, run - 1, rank)
        counts.append(run[-1])
    counts = jnp.stack(counts)
    slots = n_dev * send_cap
    flat = jnp.where(live, pid * send_cap + rank, slots)
    # source row of every send slot: ONE int32 scatter; dead rows fall
    # off the end and are dropped
    src = jnp.zeros((slots,), jnp.int32).at[flat].set(
        jnp.arange(per, dtype=jnp.int32), mode="drop",
        unique_indices=True)

    recv_counts = jax.lax.all_to_all(counts, AXIS, 0, 0)
    starts = jnp.cumsum(recv_counts) - recv_counts
    new_n = jnp.sum(recv_counts).astype(jnp.int64)
    # output slot k holds row (k - starts[s]) of source s, where s is
    # the last source whose run starts at or before k
    k = jnp.arange(out_cap, dtype=jnp.int32)
    s = jnp.zeros((out_cap,), jnp.int32)
    for p in range(1, n_dev):
        s = s + (k >= starts[p]).astype(jnp.int32)
    ridx = jnp.clip(s * send_cap + (k - jnp.take(starts, s)), 0,
                    slots - 1)

    out: Dict[str, Column] = {}
    for name, c in cols.items():
        moved = []
        for lane in _lanes(c):
            send = jnp.take(lane, src, axis=0).reshape(n_dev, send_cap)
            recv = jax.lax.all_to_all(send, AXIS, 0, 0)
            moved.append(jnp.take(recv.reshape(-1), ridx, axis=0))
        out[name] = _relane(c, moved)
    return out, new_n


def _shard_broadcast(cols: Dict[str, Column], num_rows_vec: jax.Array,
                     out_cap: int) -> Tuple[Dict[str, Column], jax.Array]:
    """Per-shard: every shard's live rows, on every shard (REPLICATE
    exchange / broadcast join build side), shard-major."""
    per = _per_shard(cols, 1)
    n_dev = int(num_rows_vec.shape[0])
    counts = num_rows_vec.astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    k = jnp.arange(out_cap, dtype=jnp.int32)
    s = jnp.zeros((out_cap,), jnp.int32)
    for p in range(1, n_dev):
        s = s + (k >= starts[p]).astype(jnp.int32)
    idx = jnp.clip(s * per + (k - jnp.take(starts, s)), 0,
                   n_dev * per - 1)
    new_n = jnp.sum(num_rows_vec)
    out: Dict[str, Column] = {}
    for name, c in cols.items():
        moved = [jnp.take(jax.lax.all_gather(lane, AXIS).reshape(-1),
                          idx, axis=0) for lane in _lanes(c)]
        out[name] = _relane(c, moved)
    return out, new_n


# --------------------------------------------------------------------------
# whole-mesh operations (host API over ShardedBatch)
# --------------------------------------------------------------------------

def _counts_matrix(sb: ShardedBatch, key, pid_fn,
                   extra: tuple = ()) -> jax.Array:
    """Phase 1: the replicated [source, destination] matrix of
    live-row counts. ``extra``: replicated operands handed on to ``pid_fn`` (a range
    exchange's splitters), so that they are data of the program and
    not of its trace."""
    n = sb.n_shards

    def build():
        def f(cols, num_rows_vec, *more):
            my_n = num_rows_vec[jax.lax.axis_index(AXIS)]
            per = _per_shard(cols, 1)
            live = jnp.arange(per, dtype=jnp.int64) < my_n
            pid = pid_fn(cols, my_n, *more)
            return jax.lax.all_gather(_dest_counts(pid, live, n), AXIS)
        return f, _sharded_in(sb) + (P(),) * len(extra), P()

    return mesh_call("exchange_counts", key, sb.mesh,
                     (sb.columns, sb.num_rows) + tuple(extra), build)


def _exchange(sb: ShardedBatch, key, pid_fn, counts: np.ndarray,
              extra: tuple = ()) -> ShardedBatch:
    """Phase 2: move the rows, at capacities read off ``counts``."""
    n = sb.n_shards
    send_cap = capacity_for(max(int(counts.max()), 1), minimum=8)
    out_cap = capacity_for(max(int(counts.sum(axis=0).max()), 1),
                           minimum=8)

    def build():
        def f(cols, num_rows_vec, *more):
            my_n = num_rows_vec[jax.lax.axis_index(AXIS)]
            out, new_n = _shard_exchange(
                cols, my_n, pid_fn(cols, my_n, *more), n, send_cap,
                out_cap)
            return out, jax.lax.all_gather(new_n, AXIS)
        return (f, _sharded_in(sb) + (P(),) * len(extra),
                (_col_specs(sb.columns, P(AXIS)), P()))

    cols, new_counts = mesh_call(
        "exchange", (key, send_cap, out_cap), sb.mesh,
        (sb.columns, sb.num_rows) + tuple(extra), build)
    return ShardedBatch(cols, new_counts, sb.mesh, out_cap)


def _exchange_done(sp, moved: ShardedBatch) -> ShardedBatch:
    """Close an ``exchange`` span on its moving program's outputs: ONE
    ``host_read[exchange_done]``, so the span (``exchange_ms``) times
    the exchange and not only its dispatch. Outside a traced query
    nothing waits."""
    if sp is not None:
        with active_span("host_read", site="exchange_done"):
            jax.block_until_ready((moved.columns, moved.num_rows))
    return moved


def _two_phase_exchange(sb: ShardedBatch, key, pid_fn,
                        extra: tuple = ()) -> ShardedBatch:
    with active_span("exchange", kind="repartition") as sp:
        counts = _read_counts(
            _counts_matrix(sb, key, pid_fn, extra), "exchange_counts")
        if sp is not None:
            sp.attrs["rows"] = int(counts.sum())
            sp.attrs["bytes"] = int(counts.sum()) * row_bytes(sb.columns)
        return _exchange_done(sp, _exchange(sb, key, pid_fn, counts,
                                            extra))


def repartition_by_hash(sb: ShardedBatch,
                        key_names: Sequence[str]) -> ShardedBatch:
    """REMOTE REPARTITION: redistribute rows so equal keys land on the
    same shard, both phases (one blocking read of the counts between
    them), inside one ``exchange`` span."""
    keys = tuple(key_names)
    n = sb.n_shards
    return _two_phase_exchange(
        sb, ("hash", keys), lambda cols, _n: _hash_pid(cols, keys, n))


# --------------------------------------------------------------------------
# range repartition (distributed sort / merge-exchange analog)
# --------------------------------------------------------------------------

def _range_pid(batch: Batch, sort_keys, splitter_lanes) -> jax.Array:
    """Destination shard id per row: the number of splitters whose
    composite sort-lane tuple is strictly below the row's. Splitters
    ascend, so shard ids ascend with ORDER BY position — shard-major
    concatenation of per-shard sorted rows IS the global order."""
    from ..ops.sort import sort_lanes
    lanes = sort_lanes(batch, sort_keys)[1:]  # drop the liveness lane
    some = lanes[0]
    dest = jnp.zeros(some.shape, jnp.int32)
    n_split = len(splitter_lanes[0])
    for si in range(n_split):
        gt = jnp.zeros(some.shape, bool)
        eq = jnp.ones(some.shape, bool)
        for lane, sl in zip(lanes, splitter_lanes):
            sval = jnp.asarray(sl[si], dtype=lane.dtype)
            gt = gt | (eq & (lane > sval))
            eq = eq & (lane == sval)
        dest = dest + gt.astype(jnp.int32)
    return dest


def sample_range_splitters(sb: ShardedBatch, sort_keys,
                           samples_per_shard: int = 256):
    """Phase 0 of a distributed sort: evenly sample each shard's sort
    lanes, gather the samples, and pick n_dev-1 splitters at sample
    quantiles (the reference's sampled range partitioning for
    distributed_sort / MergeOperator's range exchange). Returns a list
    of per-lane splitter value arrays, or None when the relation is
    empty."""
    from ..ops.sort import sort_lanes
    n = sb.n_shards
    S = samples_per_shard
    keys = tuple(sort_keys)

    # out_specs needs the lane count up front; derive it from a tiny
    # 8-row head batch so no full-column lane computation runs here
    head = {name: Column(c.type, jnp.asarray(c.data)[:8],
                         None if c.valid is None
                         else jnp.asarray(c.valid)[:8], c.dictionary)
            for name, c in sb.columns.items()}
    n_lanes_probe = len(sort_lanes(Batch(head, 0), keys)) - 1

    def build():
        def f(cols, num_rows_vec):
            my_n = num_rows_vec[jax.lax.axis_index(AXIS)]
            lanes = sort_lanes(Batch(cols, my_n), keys)[1:]
            pos = (jnp.arange(S, dtype=jnp.int64)
                   * jnp.maximum(my_n, 1)) // S
            samp = tuple(
                jnp.take(l, jnp.clip(pos, 0, l.shape[0] - 1), mode="clip")
                for l in lanes)
            live = jnp.arange(S, dtype=jnp.int64) < my_n
            return samp + (live,)
        return (f, _sharded_in(sb),
                tuple([P(AXIS)] * (n_lanes_probe + 1)))

    out = mesh_call("range_sample", (keys, S), sb.mesh,
                    (sb.columns, sb.num_rows), build)
    live = np.asarray(out[-1])
    if not live.any():
        return None
    lanes_h = [np.asarray(l)[live] for l in out[:-1]]
    order = np.lexsort(lanes_h[::-1])
    m = len(order)
    picks = [order[min(((i + 1) * m) // n, m - 1)] for i in range(n - 1)]
    return [l[picks] for l in lanes_h]


def repartition_by_range(sb: ShardedBatch, sort_keys,
                         splitter_lanes) -> ShardedBatch:
    """Range exchange: redistribute rows so shard i holds the i-th
    ORDER BY slice. A per-shard sort afterwards yields a globally
    sorted relation under shard-major gather (unshard_batch). The
    splitters go in as operands: one program per sort keys, whatever
    the sample gave."""
    keys = tuple(sort_keys)
    return _two_phase_exchange(
        sb, ("range", keys),
        lambda cols, my_n, *splitters: _range_pid(
            Batch(cols, my_n), keys, splitters),
        tuple(jnp.asarray(l) for l in splitter_lanes))


def distributed_group_aggregate(sb: ShardedBatch,
                                key_names: Sequence[str],
                                aggs: Sequence[AggInput]) -> ShardedBatch:
    """PARTIAL agg per shard -> all_to_all by key hash -> FINAL agg.

    The PushPartialAggregationThroughExchange plan shape (SURVEY.md
    §2.7 partial/final row): every aggregate below declares a combine
    that is itself a segment op, so the partial output columns feed the
    final step directly. Three mesh programs around one sized exchange;
    the partial step is what keeps the exchange small when groups are
    few."""
    from ..ops.groupby import COMBINABLE_KINDS
    keys, aggs = list(key_names), list(aggs)
    ident = (tuple(keys), tuple(aggs))
    if all(a.kind in COMBINABLE_KINDS for a in aggs):
        finals = [AggInput(COMBINABLE_KINDS[a.kind], a.output, None,
                           a.output) for a in aggs]
        part = shard_apply(
            sb, lambda b: group_aggregate(b, keys, aggs,
                                          groups_capacity=b.capacity),
            key=("agg_partial", ident))
        moved = repartition_by_hash(part, keys)
        return shard_apply(
            moved, lambda b: group_aggregate(b, keys, finals,
                                             groups_capacity=b.capacity),
            key=("agg_final", ident))
    # non-decomposable aggregates (count_distinct / percentile /
    # argmin / argmax): repartition ROWS by key hash first, then
    # aggregate exactly — every group is wholly on one shard
    moved = repartition_by_hash(sb, keys)
    return shard_apply(
        moved, lambda b: group_aggregate(b, keys, aggs,
                                         groups_capacity=b.capacity),
        key=("agg_rows", ident))


def _sharded_result(cols, counts, mesh) -> ShardedBatch:
    return ShardedBatch(cols, counts, mesh,
                        _per_shard(cols, mesh.devices.size))


def shard_apply(sb: ShardedBatch, fn, key=None) -> ShardedBatch:
    """Run a Batch->Batch transformation independently on every shard
    (the intra-task pipeline segment between exchanges: filter/project/
    partial ops — SURVEY.md §2.7 intra-node row). ``key`` names what
    ``fn`` closes over (see ``mesh_program``)."""

    def build():
        def f(cols, num_rows_vec):
            d = jax.lax.axis_index(AXIS)
            out = fn(Batch(cols, num_rows_vec[d]))
            counts = jax.lax.all_gather(out.num_rows_device(), AXIS)
            return out.columns, counts
        return f, _sharded_in(sb), (P(AXIS), P())

    cols, counts = mesh_call("apply", key, sb.mesh,
                             (sb.columns, sb.num_rows), build)
    return _sharded_result(cols, counts, sb.mesh)


def shard_apply2s(sa: ShardedBatch, sb: ShardedBatch, fn,
                  key=None) -> ShardedBatch:
    """Per-shard transformation over two co-sharded operands (the
    PARTITIONED-distribution join body: both sides already hash-
    repartitioned on the join keys, so a shard joins only its slice;
    a broadcast build side is co-sharded too, whole on every shard)."""

    def build():
        def f(acols, an, bcols, bn):
            d = jax.lax.axis_index(AXIS)
            out = fn(Batch(acols, an[d]), Batch(bcols, bn[d]))
            counts = jax.lax.all_gather(out.num_rows_device(), AXIS)
            return out.columns, counts
        return f, _sharded_in(sa) + _sharded_in(sb), (P(AXIS), P())

    cols, counts = mesh_call(
        "apply2", key, sa.mesh,
        (sa.columns, sa.num_rows, sb.columns, sb.num_rows), build)
    return _sharded_result(cols, counts, sa.mesh)


def shard_apply2(sa: ShardedBatch, b_host: Batch, fn,
                 key=None) -> ShardedBatch:
    """Per-shard transformation with a REPLICATED second operand (a
    filtering source held by the coordinator): fn(shard Batch,
    replicated Batch) -> Batch."""

    def build():
        def f(cols, num_rows_vec, bcols, bn):
            d = jax.lax.axis_index(AXIS)
            out = fn(Batch(cols, num_rows_vec[d]), Batch(bcols, bn))
            counts = jax.lax.all_gather(out.num_rows_device(), AXIS)
            return out.columns, counts
        return (f, _sharded_in(sa) + (_col_specs(b_host.columns, P()),
                                      P()), (P(AXIS), P()))

    cols, counts = mesh_call(
        "apply2r", key, sa.mesh,
        (sa.columns, sa.num_rows, b_host.columns,
         jnp.asarray(b_host.num_rows_host(), jnp.int64)), build)
    return _sharded_result(cols, counts, sa.mesh)


def shard_totals2(sa: ShardedBatch, b_host: Batch, fn,
                  key=None) -> jax.Array:
    """Per-shard scalar with replicated second operand."""

    def build():
        def f(cols, num_rows_vec, bcols, bn):
            d = jax.lax.axis_index(AXIS)
            t = fn(Batch(cols, num_rows_vec[d]), Batch(bcols, bn))
            return jax.lax.all_gather(t, AXIS)
        return (f, _sharded_in(sa) + (_col_specs(b_host.columns, P()),
                                      P()), P())

    return mesh_call(
        "totals2r", key, sa.mesh,
        (sa.columns, sa.num_rows, b_host.columns,
         jnp.asarray(b_host.num_rows_host(), jnp.int64)), build)


def broadcast_sharded(sb: ShardedBatch) -> ShardedBatch:
    """REPLICATE exchange: every shard ends up with every row (shard-
    major), at the capacity of the live total."""
    with active_span("exchange", kind="broadcast") as sp:
        total = int(_read_counts(sb.num_rows, "broadcast_rows").sum())
        if sp is not None:
            sp.attrs["rows"] = total
            sp.attrs["bytes"] = total * row_bytes(sb.columns)
        cap = capacity_for(max(total, 1), minimum=8)

        def build():
            def f(cols, num_rows_vec):
                out, new_n = _shard_broadcast(cols, num_rows_vec, cap)
                return out, jax.lax.all_gather(new_n, AXIS)
            return f, _sharded_in(sb), (P(AXIS), P())

        cols, counts = mesh_call("broadcast", cap, sb.mesh,
                                 (sb.columns, sb.num_rows), build)
        # every shard holds the same rows; counts[d] all equal the total
        return _exchange_done(sp, ShardedBatch(cols, counts, sb.mesh,
                                               cap))
