"""SPMD collective kernels: the TPU data plane.

Reference parity: the exchange layer — PartitionedOutputOperator.java:55
(hash partition + scatter into per-partition buffers), ExchangeClient.java
:149 (pull + merge), BroadcastOutputBuffer (replicate). TPU-first redesign
(SURVEY.md §2.7, §7.4): REMOTE REPARTITION == ``jax.lax.all_to_all`` over
the ICI mesh inside a ``shard_map``; REPLICATE == ``all_gather``; GATHER
== host collect (mesh.py unshard_batch). There is no wire serde or
pull/ack protocol inside a slice — XLA schedules the collective.

The same columnar kernels (ops/groupby, ops/join, exec/expr) run
unchanged inside the shard_map trace: a Trino *task* is the per-shard
slice of one SPMD program. Host syncs happen only between shard_map
calls, for data-dependent capacity decisions (the two-phase pattern of
ops/join.py, lifted to the distributed case).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from ..columnar import Batch, Column
from ..ops.groupby import AggInput, group_aggregate
from ..ops.hashing import hash_columns
from .mesh import AXIS, ShardedBatch, row_spec


def _spmd(f, **kw):
    """``shard_map`` as ONE jitted program per call. Called eagerly,
    shard_map dispatches every primitive of ``f`` as its own mesh-wide
    program: a q1 partial->exchange->final aggregation is hundreds of
    separate SPMD compiles (a 4-device tpch.tiny q1 did not return in
    240 s on a cold cache); jitted it is one."""
    return jax.jit(shard_map(f, **kw))


def _col_specs(cols: Dict[str, Column], spec) -> Dict[str, Column]:
    """A pytree of PartitionSpecs shaped like the columns dict."""
    return jax.tree.map(lambda _: spec, cols)


# --------------------------------------------------------------------------
# shard-level repartition (runs inside a shard_map trace)
# --------------------------------------------------------------------------

def _shard_repartition(cols: Dict[str, Column], my_n: jax.Array,
                       key_names: Sequence[str], n_dev: int,
                       out_cap: int) -> Tuple[Dict[str, Column],
                                              jax.Array]:
    """Per-shard: hash-bin rows by destination, all_to_all, compact.
    Returns (received columns [out_cap], my new row count)."""
    h = hash_columns([cols[k] for k in key_names])
    pid = (h % jnp.uint64(n_dev)).astype(jnp.int32)
    return _shard_exchange(cols, my_n, pid, n_dev, out_cap)


def _shard_exchange(cols: Dict[str, Column], my_n: jax.Array,
                    pid: jax.Array, n_dev: int,
                    out_cap: int) -> Tuple[Dict[str, Column], jax.Array]:
    """Per-shard exchange body: given each row's destination shard id,
    bin rows, all_to_all, compact. The received rows preserve
    (source-shard, source-position) order within each destination."""
    some = next(iter(cols.values()))
    per = int(some.data.shape[0])
    live = jnp.arange(per, dtype=jnp.int64) < my_n
    sort_key = jnp.where(live, pid, n_dev)
    order = jnp.argsort(sort_key, stable=True)

    counts = jax.ops.segment_sum(
        live.astype(jnp.int64), jnp.clip(pid, 0, n_dev - 1),
        num_segments=n_dev)
    starts = jnp.cumsum(counts) - counts

    # send slot matrix [n_dev, per]: bin p's row j comes from
    # order[starts[p] + j]
    j = jnp.arange(per, dtype=jnp.int64)[None, :]
    src = starts[:, None] + j
    send_idx = jnp.take(order, jnp.clip(src, 0, per - 1), axis=0)

    recv_counts = jax.lax.all_to_all(counts, AXIS, 0, 0)
    new_n = jnp.sum(recv_counts)

    # compact gather index over the received [n_dev, per] buffers
    rj = jnp.arange(per, dtype=jnp.int64)[None, :]
    recv_live = (rj < recv_counts[:, None]).reshape(-1)
    flat_idx = jnp.nonzero(recv_live, size=out_cap, fill_value=0)[0]

    out: Dict[str, Column] = {}
    for name, c in cols.items():
        lanes = [c.data] + ([c.valid] if c.valid is not None else []) \
            + ([c.data2] if c.data2 is not None else [])
        moved = []
        for lane in lanes:
            send = jnp.take(jnp.asarray(lane), send_idx, axis=0)
            recv = jax.lax.all_to_all(send, AXIS, 0, 0)
            moved.append(jnp.take(recv.reshape(-1), flat_idx, axis=0))
        data = moved[0]
        k = 1
        valid = None
        if c.valid is not None:
            valid = moved[k]
            k += 1
        d2 = moved[k] if c.data2 is not None else None
        out[name] = Column(c.type, data, valid, c.dictionary, d2)
    return out, new_n


def _shard_broadcast(cols: Dict[str, Column], num_rows_vec: jax.Array,
                     out_cap: int) -> Tuple[Dict[str, Column], jax.Array]:
    """Per-shard: replicate every shard's live rows to all shards
    (REPLICATE exchange / broadcast join build side)."""
    some = next(iter(cols.values()))
    per = int(some.data.shape[0])
    n_dev = num_rows_vec.shape[0]
    j = jnp.arange(per, dtype=jnp.int64)[None, :]
    live = (j < num_rows_vec[:, None]).reshape(-1)
    flat_idx = jnp.nonzero(live, size=out_cap, fill_value=0)[0]
    new_n = jnp.sum(num_rows_vec)
    out: Dict[str, Column] = {}
    for name, c in cols.items():
        lanes = [c.data] + ([c.valid] if c.valid is not None else []) \
            + ([c.data2] if c.data2 is not None else [])
        moved = []
        for lane in lanes:
            g = jax.lax.all_gather(jnp.asarray(lane), AXIS)  # [n_dev, per]
            moved.append(jnp.take(g.reshape(-1), flat_idx, axis=0))
        data = moved[0]
        k = 1
        valid = None
        if c.valid is not None:
            valid = moved[k]
            k += 1
        d2 = moved[k] if c.data2 is not None else None
        out[name] = Column(c.type, data, valid, c.dictionary, d2)
    return out, new_n


# --------------------------------------------------------------------------
# whole-mesh operations (host API over ShardedBatch)
# --------------------------------------------------------------------------

def repartition_by_hash(sb: ShardedBatch, key_names: Sequence[str],
                        out_cap: Optional[int] = None) -> ShardedBatch:
    """REMOTE REPARTITION: redistribute rows so equal keys land on the
    same shard. ``out_cap`` bounds the post-exchange per-shard capacity;
    default is the safe worst case n_dev * per_shard_cap."""
    n = sb.n_shards
    cap = out_cap or n * sb.per_shard_cap

    def f(cols, num_rows_vec):
        d = jax.lax.axis_index(AXIS)
        my_n = num_rows_vec[d]
        out, new_n = _shard_repartition(cols, my_n, key_names, n, cap)
        counts = jax.lax.all_gather(new_n, AXIS)
        return out, counts

    mesh = sb.mesh
    fn = _spmd(
        f, mesh=mesh,
        in_specs=(_col_specs(sb.columns, P(AXIS)), P()),
        out_specs=(_col_specs(sb.columns, P(AXIS)), P()),
        check_vma=False)
    cols, counts = fn(sb.columns, sb.num_rows)
    return ShardedBatch(cols, counts, mesh, cap)


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
    import numpy as np
    from ..ops.sort import sort_lanes
    n = sb.n_shards
    S = samples_per_shard

    def f(cols, num_rows_vec):
        d = jax.lax.axis_index(AXIS)
        my_n = num_rows_vec[d]
        b = Batch(cols, my_n)
        lanes = sort_lanes(b, sort_keys)[1:]
        pos = (jnp.arange(S, dtype=jnp.int64)
               * jnp.maximum(my_n, 1)) // S
        samp = tuple(
            jnp.take(l, jnp.clip(pos, 0, l.shape[0] - 1), mode="clip")
            for l in lanes)
        live = jnp.arange(S, dtype=jnp.int64) < my_n
        return samp + (live,)

    # out_specs needs the lane count up front; derive it from a tiny
    # 8-row head batch so no full-column lane computation runs here
    head = {name: Column(c.type, jnp.asarray(c.data)[:8],
                         None if c.valid is None
                         else jnp.asarray(c.valid)[:8], c.dictionary)
            for name, c in sb.columns.items()}
    n_lanes_probe = len(sort_lanes(Batch(head, 0), sort_keys)) - 1

    g = _spmd(f, mesh=sb.mesh,
              in_specs=(_col_specs(sb.columns, P(AXIS)), P()),
              out_specs=tuple([P(AXIS)] * (n_lanes_probe + 1)),
              check_vma=False)
    out = g(sb.columns, sb.num_rows)
    live = np.asarray(out[-1])
    if not live.any():
        return None
    lanes_h = [np.asarray(l)[live] for l in out[:-1]]
    order = np.lexsort(lanes_h[::-1])
    m = len(order)
    picks = [order[min(((i + 1) * m) // n, m - 1)] for i in range(n - 1)]
    return [l[picks] for l in lanes_h]


def range_dest_counts(sb: ShardedBatch, sort_keys,
                      splitter_lanes) -> jax.Array:
    """Per-destination row totals for a range exchange (two-phase
    capacity sizing, mirroring repartition_dest_counts)."""
    n = sb.n_shards

    def f(cols, num_rows_vec):
        d = jax.lax.axis_index(AXIS)
        my_n = num_rows_vec[d]
        some = next(iter(cols.values()))
        per = int(some.data.shape[0])
        live = jnp.arange(per, dtype=jnp.int64) < my_n
        pid = _range_pid(Batch(cols, my_n), sort_keys, splitter_lanes)
        counts = jax.ops.segment_sum(
            live.astype(jnp.int64), jnp.clip(pid, 0, n - 1),
            num_segments=n)
        return jax.lax.psum(counts, AXIS)

    g = _spmd(f, mesh=sb.mesh,
              in_specs=(_col_specs(sb.columns, P(AXIS)), P()),
              out_specs=P(),
              check_vma=False)
    return g(sb.columns, sb.num_rows)


def repartition_by_range(sb: ShardedBatch, sort_keys, splitter_lanes,
                         out_cap: Optional[int] = None) -> ShardedBatch:
    """Range exchange: redistribute rows so shard i holds the i-th
    ORDER BY slice. A per-shard sort afterwards yields a globally
    sorted relation under shard-major gather (unshard_batch)."""
    n = sb.n_shards
    cap = out_cap or n * sb.per_shard_cap

    def f(cols, num_rows_vec):
        d = jax.lax.axis_index(AXIS)
        my_n = num_rows_vec[d]
        pid = _range_pid(Batch(cols, my_n), sort_keys, splitter_lanes)
        out, new_n = _shard_exchange(cols, my_n, pid, n, cap)
        counts = jax.lax.all_gather(new_n, AXIS)
        return out, counts

    fn = _spmd(
        f, mesh=sb.mesh,
        in_specs=(_col_specs(sb.columns, P(AXIS)), P()),
        out_specs=(_col_specs(sb.columns, P(AXIS)), P()),
        check_vma=False)
    cols, counts = fn(sb.columns, sb.num_rows)
    return ShardedBatch(cols, counts, sb.mesh, cap)


def distributed_group_aggregate(sb: ShardedBatch,
                                key_names: Sequence[str],
                                aggs: Sequence[AggInput],
                                out_cap: Optional[int] = None
                                ) -> ShardedBatch:
    """PARTIAL agg per shard -> all_to_all by key hash -> FINAL agg.

    This is the PushPartialAggregationThroughExchange plan shape
    (SURVEY.md §2.7 partial/final row) as one SPMD program: every
    aggregate below declares a combine that is itself a segment op,
    so the partial output columns feed the final step directly."""
    from ..ops.groupby import COMBINABLE_KINDS
    n = sb.n_shards
    partial_cap = sb.per_shard_cap
    exch_cap = n * partial_cap if out_cap is None else out_cap

    decomposable = all(a.kind in COMBINABLE_KINDS for a in aggs)
    if decomposable:
        finals: List[AggInput] = [
            AggInput(COMBINABLE_KINDS[a.kind], a.output, None, a.output)
            for a in aggs]

    def f(cols, num_rows_vec):
        d = jax.lax.axis_index(AXIS)
        my_n = num_rows_vec[d]
        local = Batch(cols, my_n)
        if decomposable:
            part = group_aggregate(local, list(key_names), list(aggs),
                                   groups_capacity=partial_cap)
            moved, new_n = _shard_repartition(
                part.columns, part.num_rows_device(), key_names, n,
                exch_cap)
            fin = group_aggregate(Batch(moved, new_n), list(key_names),
                                  finals, groups_capacity=exch_cap)
        else:
            # non-decomposable aggregates (count_distinct / percentile /
            # argmin / argmax): repartition ROWS by key hash first, then
            # aggregate exactly — every group is wholly on one shard
            moved, new_n = _shard_repartition(
                cols, my_n, key_names, n, exch_cap)
            fin = group_aggregate(Batch(moved, new_n), list(key_names),
                                  list(aggs), groups_capacity=exch_cap)
        counts = jax.lax.all_gather(fin.num_rows_device(), AXIS)
        return fin.columns, counts

    mesh = sb.mesh
    fn = _spmd(f, mesh=mesh,
               in_specs=(_col_specs(sb.columns, P(AXIS)), P()),
               out_specs=(P(AXIS), P()),
               check_vma=False)
    cols, counts = fn(sb.columns, sb.num_rows)
    return ShardedBatch(cols, counts, mesh, exch_cap)


def shard_apply(sb: ShardedBatch, fn, out_cap: Optional[int] = None
                ) -> ShardedBatch:
    """Run a Batch->Batch transformation independently on every shard
    (the intra-task pipeline segment between exchanges: filter/project/
    partial ops — SURVEY.md §2.7 intra-node row). ``fn`` must keep the
    capacity at ``out_cap`` (default: unchanged)."""
    cap = out_cap or sb.per_shard_cap

    def f(cols, num_rows_vec):
        d = jax.lax.axis_index(AXIS)
        out = fn(Batch(cols, num_rows_vec[d]))
        counts = jax.lax.all_gather(out.num_rows_device(), AXIS)
        return out.columns, counts

    g = _spmd(f, mesh=sb.mesh,
              in_specs=(_col_specs(sb.columns, P(AXIS)), P()),
              out_specs=(P(AXIS), P()),
              check_vma=False)
    cols, counts = g(sb.columns, sb.num_rows)
    return ShardedBatch(cols, counts, sb.mesh, cap)


def shard_totals(sb: ShardedBatch, fn) -> jax.Array:
    """Per-shard scalar reduction (e.g. join-size phase 1): fn(Batch) ->
    int scalar; returns the [n_dev] vector (host-readable)."""

    def f(cols, num_rows_vec):
        d = jax.lax.axis_index(AXIS)
        t = fn(Batch(cols, num_rows_vec[d]))
        return jax.lax.all_gather(t, AXIS)

    g = _spmd(f, mesh=sb.mesh,
              in_specs=(_col_specs(sb.columns, P(AXIS)), P()),
              out_specs=P(),
              check_vma=False)
    return g(sb.columns, sb.num_rows)


def repartition_dest_counts(sb: ShardedBatch,
                            key_names: Sequence[str]) -> jax.Array:
    """Phase 1 of a two-phase repartition: the [n_dev] vector of row
    totals each destination shard would receive — lets the caller size
    the exchange capacity from real counts instead of the
    n_dev * per_shard_cap worst case (VERDICT weak #10)."""
    n = sb.n_shards

    def f(cols, num_rows_vec):
        d = jax.lax.axis_index(AXIS)
        my_n = num_rows_vec[d]
        some = next(iter(cols.values()))
        per = int(some.data.shape[0])
        live = jnp.arange(per, dtype=jnp.int64) < my_n
        h = hash_columns([cols[k] for k in key_names])
        pid = (h % jnp.uint64(n)).astype(jnp.int32)
        counts = jax.ops.segment_sum(
            live.astype(jnp.int64), jnp.clip(pid, 0, n - 1),
            num_segments=n)
        return jax.lax.psum(counts, AXIS)

    g = _spmd(f, mesh=sb.mesh,
              in_specs=(_col_specs(sb.columns, P(AXIS)), P()),
              out_specs=P(),
              check_vma=False)
    return g(sb.columns, sb.num_rows)


def shard_apply2s(sa: ShardedBatch, sb: ShardedBatch, fn,
                  out_cap: int) -> ShardedBatch:
    """Per-shard transformation over two co-sharded operands (the
    PARTITIONED-distribution join body: both sides already hash-
    repartitioned on the join keys, so a shard joins only its slice)."""

    def f(acols, an, bcols, bn):
        d = jax.lax.axis_index(AXIS)
        out = fn(Batch(acols, an[d]), Batch(bcols, bn[d]))
        counts = jax.lax.all_gather(out.num_rows_device(), AXIS)
        return out.columns, counts

    g = _spmd(
        f, mesh=sa.mesh,
        in_specs=(_col_specs(sa.columns, P(AXIS)), P(),
                  _col_specs(sb.columns, P(AXIS)), P()),
        out_specs=(P(AXIS), P()),
        check_vma=False)
    cols, counts = g(sa.columns, sa.num_rows, sb.columns, sb.num_rows)
    return ShardedBatch(cols, counts, sa.mesh, out_cap)


def shard_totals2s(sa: ShardedBatch, sb: ShardedBatch, fn) -> jax.Array:
    """Per-shard scalar over two co-sharded operands."""

    def f(acols, an, bcols, bn):
        d = jax.lax.axis_index(AXIS)
        t = fn(Batch(acols, an[d]), Batch(bcols, bn[d]))
        return jax.lax.all_gather(t, AXIS)

    g = _spmd(
        f, mesh=sa.mesh,
        in_specs=(_col_specs(sa.columns, P(AXIS)), P(),
                  _col_specs(sb.columns, P(AXIS)), P()),
        out_specs=P(),
        check_vma=False)
    return g(sa.columns, sa.num_rows, sb.columns, sb.num_rows)


def shard_apply2(sa: ShardedBatch, b_host: Batch, fn,
                 out_cap: int) -> ShardedBatch:
    """Per-shard transformation with a REPLICATED second operand (a
    broadcast-join build side / filtering source): fn(shard Batch,
    replicated Batch) -> Batch of capacity out_cap."""

    def f(cols, num_rows_vec, bcols, bn):
        d = jax.lax.axis_index(AXIS)
        out = fn(Batch(cols, num_rows_vec[d]), Batch(bcols, bn))
        counts = jax.lax.all_gather(out.num_rows_device(), AXIS)
        return out.columns, counts

    g = _spmd(
        f, mesh=sa.mesh,
        in_specs=(_col_specs(sa.columns, P(AXIS)), P(),
                  _col_specs(b_host.columns, P()), P()),
        out_specs=(P(AXIS), P()),
        check_vma=False)
    cols, counts = g(sa.columns, sa.num_rows, b_host.columns,
                     jnp.asarray(b_host.num_rows_host(), jnp.int64))
    return ShardedBatch(cols, counts, sa.mesh, out_cap)


def shard_totals2(sa: ShardedBatch, b_host: Batch, fn) -> jax.Array:
    """Per-shard scalar with replicated second operand."""

    def f(cols, num_rows_vec, bcols, bn):
        d = jax.lax.axis_index(AXIS)
        t = fn(Batch(cols, num_rows_vec[d]), Batch(bcols, bn))
        return jax.lax.all_gather(t, AXIS)

    g = _spmd(
        f, mesh=sa.mesh,
        in_specs=(_col_specs(sa.columns, P(AXIS)), P(),
                  _col_specs(b_host.columns, P()), P()),
        out_specs=P(),
        check_vma=False)
    return g(sa.columns, sa.num_rows, b_host.columns,
             jnp.asarray(b_host.num_rows_host(), jnp.int64))


def broadcast_sharded(sb: ShardedBatch,
                      out_cap: Optional[int] = None) -> ShardedBatch:
    """REPLICATE exchange: every shard ends up with every row."""
    n = sb.n_shards
    cap = out_cap or n * sb.per_shard_cap

    def f(cols, num_rows_vec):
        out, new_n = _shard_broadcast(cols, num_rows_vec, cap)
        counts = jax.lax.all_gather(new_n, AXIS)
        return out, counts

    fn = _spmd(f, mesh=sb.mesh,
               in_specs=(_col_specs(sb.columns, P(AXIS)), P()),
               out_specs=(P(AXIS), P()),
               check_vma=False)
    cols, counts = fn(sb.columns, sb.num_rows)
    # broadcast output is replicated per shard; counts[d] all equal total
    return ShardedBatch(cols, counts, sb.mesh, cap)
