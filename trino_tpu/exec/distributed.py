"""Distributed plan executor: SQL over the device mesh.

Reference parity: the distributed scheduler + worker stack
(SqlQueryScheduler.java:112, SqlStageExecution, the exchange layer) —
TPU-first redesign (SURVEY.md §7.4): a stage's tasks are the shards of
one SPMD program; exchanges are collectives:

- table scans: splits round-robin onto shards, each shard's lanes read
  or generated on its own chip and kept resident there (the sharded
  scan cache, exec/executor.py read_table_sharded)
- filter/project: per-shard shard_map segments
- aggregation over a filter/project chain: ONE mesh program — the
  chain as a selection vector and the partial aggregation per shard,
  the partial rows all_gathered, the final combine on every shard
  (the one-chip ``stream_full`` program's two halves around a
  collective); where the partials are not small: partial →
  all_to_all repartition → final (PushPartialAggregationThroughExchange)
- joins: the build side broadcast by all_gather (REPLICATED, the
  DetermineJoinDistributionType branch) or both sides repartitioned
  on the join keys (PARTITIONED); then ONE per-shard join for both, a
  count program and an expand program around one read of the totals
- semi joins: replicated filtering source + per-shard mask
- TopN: per-shard TopN, gather, final TopN; Sort/Window/SetOps gather to
  the coordinator shard (single-node fallback)

Data-dependent output capacities use the two-phase pattern: a counts
shard_map, a host max, then the expansion shard_map with static shapes.
"""

from __future__ import annotations

from dataclasses import replace as dc_replace
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..catalog import CatalogManager
from ..columnar import Batch, Column
from ..config import capacity_for
from ..ops import compact, join as join_ops, sort as sort_ops
from ..ops.groupby import (COMBINABLE_KINDS as _COMBINABLE, AggInput,
                           global_aggregate, group_aggregate)
from ..parallel.mesh import (AXIS, ShardedBatch, get_mesh, shard_batch,
                             unshard_batch)
from ..parallel.spmd import (P, _col_specs, broadcast_sharded,
                             distributed_group_aggregate, mesh_call,
                             repartition_by_hash, shard_apply,
                             shard_apply2, shard_apply2s, shard_totals2)
from ..plan.nodes import (AggregationNode, FilterNode, JoinNode, LimitNode,
                          OutputNode, PlanNode, ProjectNode, SemiJoinNode,
                          TableScanNode, TopNNode)
from ..planner.logical import SemiJoinMultiNode
from ..session import Session
from ..types import BOOLEAN, BIGINT
from .executor import (Executor, QueryError, _Pre, _lower_aggregates,
                       expand_columns, expand_lanes, join_verify_filter,
                       make_stream_parts, narrow, read_table_sharded)
from .literals import literal_scope
from .progkey import (PROGRAMS, UNTRACEABLE, canonicalize_nodes,
                      node_fingerprint)
from .expr import eval_expr, eval_predicate

Value = Union[Batch, ShardedBatch]

# a relation smaller than this isn't worth sharding at all
MIN_SHARD_ROWS = 1 << 12
# the fused mesh aggregation gathers every shard's partial rows onto
# every shard: only where a shard's partial is this small (no GROUP
# BY, or keys with small static domains); larger partials go through
# the exchange
FUSED_PARTIAL_ROWS = 1 << 10
# a build side's key range or key set prunes the probe before the
# exchange only where it is cheap to collect and can be selective
DYNAMIC_FILTER_BUILD_ROWS = 100_000

# An aggregation that passed ``_small_partial`` and still could not be
# one program (untraceable: host code in an aggregate, as on the
# one-chip path; or a partial the packed kernel declined for its
# aggregate kinds) is refused in the program cache's "spmd" bucket by
# its canonical key ALONE: whatever mesh and operand structure its
# programs are kept under, none is tried again.

class _PartialTooLarge(Exception):
    pass


def _small_partial(node: AggregationNode, chain, cols) -> bool:
    """Whether a shard's partial aggregation is a handful of rows, read
    off the plan: no GROUP BY, or every group key a column of the scan
    (through the chain's renames) with a small static domain
    (dictionary codes, booleans), the packed kernel's case. A computed
    key or a key of unknown domain (q3's orderkey) means partials as
    large as the shard: those go through the exchange."""
    from ..ops.groupby import FAST_DOMAIN_LIMIT, _static_domain
    from ..rex import InputRef
    groups = 1
    for sym in node.group_keys:
        for nd in chain:            # from the aggregation down
            if isinstance(nd, ProjectNode):
                e = nd.assignments.get(sym)
                if not isinstance(e, InputRef):
                    return False
                sym = e.name
        col = cols.get(sym)
        d = None if col is None or col.data2 is not None \
            else _static_domain(col)
        if d is None:
            return False
        groups *= d + 1
    return groups <= FAST_DOMAIN_LIMIT


class DistributedExecutor(Executor):
    """Executor whose intermediate values may be row-sharded across the
    mesh. Nodes without a distributed strategy gather to the host and
    reuse the local implementation (COORDINATOR_ONLY fallback)."""

    def __init__(self, catalogs: CatalogManager, session: Session,
                 mesh=None, collect_stats: bool = False):
        super().__init__(catalogs, session, collect_stats)
        self.mesh = mesh or get_mesh()

    # -- helpers ---------------------------------------------------------
    def _host(self, v: Value) -> Batch:
        return unshard_batch(v) if isinstance(v, ShardedBatch) else v

    def execute_host(self, node: PlanNode) -> Batch:
        return self._host(self.execute(node))

    def _execute_node(self, node: PlanNode):  # type: ignore[override]
        cancel = getattr(self.session, "cancel", None)
        if cancel is not None and cancel.is_set():
            raise QueryError("Query was canceled")

        def inner():
            method = getattr(self, "_dexec_" + type(node).__name__,
                             None)
            if method is not None:
                return method(node)
            # local fallback: materialize sharded sources on host
            return self._exec_local(node)

        if not self.collect_stats:
            return inner()
        # same per-node stats discipline as the local executor
        return self._stats_wrap(node, inner)

    def _exec_local(self, node: PlanNode) -> Batch:
        method = getattr(super(), "_exec_" + type(node).__name__, None)
        if method is None:
            raise QueryError(
                f"no executor for plan node {type(node).__name__}")
        # parent handlers recurse via self.execute(source) and expect
        # host Batches; pre-materialize every source (COORDINATOR_ONLY
        # gather) so sharded values never leak into local operators
        import dataclasses
        if node.sources and dataclasses.is_dataclass(node):
            updates = {}
            for f in dataclasses.fields(node):
                v = getattr(node, f.name)
                if isinstance(v, PlanNode):
                    updates[f.name] = _Pre(self.execute_host(v))
                elif isinstance(v, tuple) and v and all(
                        isinstance(x, PlanNode) for x in v):
                    updates[f.name] = tuple(
                        _Pre(self.execute_host(x)) for x in v)
            if updates:
                node = dc_replace(node, **updates)
        return method(node)

    # make the parent's recursive self.execute(source) calls transparent:
    # any source executed through the parent class must come back as a
    # host Batch
    def _exec_lifted(self, node: PlanNode) -> Batch:
        return self.execute_host(node)

    # -- leaves ----------------------------------------------------------
    def _dexec_TableScanNode(self, node: TableScanNode) -> Value:
        conn = self.catalogs.connector(node.handle.catalog)
        columns = sorted(set(node.assignments.values()))
        est = conn.table_row_count(node.handle) or 0
        if est < MIN_SHARD_ROWS and len(
                conn.get_splits(node.handle, self.mesh.devices.size)) == 1:
            return self._exec_local(node)
        sb = read_table_sharded(conn, node.handle, columns, self.mesh)
        if self.collect_stats and self._frames:
            n = sb.num_rows     # per shard, read at the end of execute
            if self.analyze:
                with self._host_read("split_rows"):
                    n = sb.total_rows_host()
            self._frames[-1]["rows"].append(n)
        # rename connector columns to plan symbols
        cols = {sym: sb.columns[col]
                for sym, col in node.assignments.items()}
        return ShardedBatch(cols, sb.num_rows, sb.mesh, sb.per_shard_cap)

    def _dexec__Pre(self, node: _Pre) -> Value:
        return node.batch

    # -- per-shard pipeline segments ------------------------------------
    def _dexec_FilterNode(self, node: FilterNode) -> Value:
        src = self.execute(node.source)
        if not isinstance(src, ShardedBatch):
            return super()._exec_FilterNode(
                dc_replace(node, source=_Pre(src)))
        return shard_apply(
            src, lambda b: compact.filter_batch(
                b, eval_predicate(node.predicate, b)),
            key=_node_key(node))

    def _dexec_ProjectNode(self, node: ProjectNode) -> Value:
        src = self.execute(node.source)
        if not isinstance(src, ShardedBatch):
            return super()._exec_ProjectNode(
                dc_replace(node, source=_Pre(src)))
        return shard_apply(
            src, lambda b: Batch({s: eval_expr(e, b)
                                  for s, e in node.assignments.items()},
                                 b.num_rows),
            key=_node_key(node))

    def _dexec_OutputNode(self, node: OutputNode) -> Batch:
        src = self._host(self.execute(node.source))
        return Batch({s: src.column(s) for s in node.symbols},
                     src.num_rows)

    def _dexec_LimitNode(self, node: LimitNode) -> Batch:
        src = self.execute(node.source)
        if isinstance(src, ShardedBatch):
            # per-shard pre-limit bounds the gather to n * count rows
            src = shard_apply(
                src, lambda b: compact.limit_batch(b, node.count),
                key=_node_key(node))
            src = unshard_batch(src)
        return compact.limit_batch(src, node.count)

    def _dexec_TopNNode(self, node: TopNNode) -> Value:
        src = self.execute(node.source)
        keys = [sort_ops.SortKey(k.symbol, k.ascending, k.nulls_first)
                for k in node.keys]
        if isinstance(src, ShardedBatch):
            # per-shard partial TopN, gather, final TopN
            src = shard_apply(
                src, lambda b: sort_ops.topn_batch(b, keys, node.count),
                key=_node_key(node))
            src = unshard_batch(src)
        # the final TopN as the one-device path runs it (under
        # fragment_jit ONE cached ``chain`` program): called eagerly,
        # ``topn_batch`` compiles its scan anew on every query
        return self._execute_inner(dc_replace(node, source=_Pre(src)))

    def _dexec_SortNode(self, node) -> Value:
        """Distributed sort (distributed_sort session property): sampled
        range exchange + per-shard sort, replacing the gather-to-
        coordinator fallback. Reference: operator/MergeOperator.java
        (sorted merge exchange) — TPU-first: shard i receives the i-th
        ORDER BY slice via an all_to_all range repartition, sorts it
        locally, and shard-major gather order IS the global order."""
        src = self.execute(node.source)
        if not isinstance(src, ShardedBatch):
            return super()._exec_SortNode(
                dc_replace(node, source=_Pre(src)))
        keys = [sort_ops.SortKey(k.symbol, k.ascending, k.nulls_first)
                for k in node.keys]
        key_cols = [src.columns[k.column] for k in keys
                    if k.column in src.columns]
        distributable = (
            bool(self.session.get("distributed_sort"))
            and src.n_shards > 1
            and src.total_rows_host() >= MIN_SHARD_ROWS
            and all(c.elements is None for c in src.columns.values())
            and all(c.data2 is None for c in key_cols))
        if not distributable:
            return super()._exec_SortNode(
                dc_replace(node, source=_Pre(self._host(src))))
        from ..parallel.spmd import (repartition_by_range,
                                     sample_range_splitters)
        splitters = sample_range_splitters(src, keys)
        if splitters is None:  # empty relation
            return super()._exec_SortNode(
                dc_replace(node, source=_Pre(self._host(src))))
        rp = repartition_by_range(src, keys, splitters)
        return shard_apply(
            rp, lambda b: sort_ops.sort_batch(b, keys),
            key=_node_key(node))

    # -- window ----------------------------------------------------------
    def _dexec_WindowNode(self, node) -> Value:
        """Distributed window: hash-repartition on the PARTITION BY
        keys, run the window kernel per shard — every partition is
        wholly on one shard, so per-shard evaluation is exact.
        Reference: operator/WindowOperator.java downstream of a
        partitioned exchange (AddExchanges window rule); replaces the
        gather-to-coordinator fallback (round-4 verdict weak #6)."""
        src = self.execute(node.source)
        if not isinstance(src, ShardedBatch):
            return super()._exec_WindowNode(
                dc_replace(node, source=_Pre(src)))
        pkeys = list(node.partition_by)
        distributable = (
            bool(pkeys)
            and all(k in src.columns for k in pkeys)
            and src.n_shards > 1
            and src.total_rows_host() >= MIN_SHARD_ROWS
            and all(c.elements is None for c in src.columns.values())
            and all(src.columns[k].data2 is None for k in pkeys))
        if not distributable:
            return super()._exec_WindowNode(
                dc_replace(node, source=_Pre(self._host(src))))
        from .window import execute_window
        rp = repartition_by_hash(src, pkeys)
        try:
            return shard_apply(rp, lambda b: execute_window(b, node),
                               key=_node_key(node))
        except UNTRACEABLE:
            # a window shape the kernel can't trace (host-side frame
            # math): correctness first, gather and run locally
            return super()._exec_WindowNode(
                dc_replace(node, source=_Pre(self._host(src))))

    # -- set operations --------------------------------------------------
    def _dexec_SetOpNode(self, node) -> Value:
        """Distributed INTERSECT/EXCEPT: schema-align both sides, hash
        -repartition each on ALL output columns (equal rows co-locate),
        then run the tag+group+filter kernel per shard. Reference:
        SetOperationNodeUtils + partitioned exchange; replaces the
        gather fallback (round-4 verdict weak #6)."""
        from .executor import setop_batches
        left = self.execute(node.left)
        right = self.execute(node.right)
        out_syms = list(node.schema)

        def align(v: Value, m: Dict[str, str]) -> Value:
            # pure rename/subset: no device pass needed either way
            if isinstance(v, ShardedBatch):
                return ShardedBatch(
                    {o: v.columns[i] for o, i in m.items()},
                    v.num_rows, v.mesh, v.per_shard_cap)
            return Batch({o: v.column(i) for o, i in m.items()},
                         v.num_rows)

        lb = align(left, node.left_map)
        rb = align(right, node.right_map)
        distributable = (
            isinstance(lb, ShardedBatch) and isinstance(rb, ShardedBatch)
            and lb.n_shards > 1
            and (lb.total_rows_host() + rb.total_rows_host()
                 >= MIN_SHARD_ROWS)
            and all(c.elements is None and c.data2 is None
                    for v in (lb, rb) for c in v.columns.values()))
        if not distributable:
            hb_l = self._host(lb) if isinstance(lb, ShardedBatch) else lb
            hb_r = self._host(rb) if isinstance(rb, ShardedBatch) else rb
            return setop_batches(hb_l, hb_r, node.op, node.distinct,
                                 out_syms)
        lb, rb = _align_setop_dicts(lb, rb, out_syms)
        lrp = repartition_by_hash(lb, out_syms)
        rrp = repartition_by_hash(rb, out_syms)
        out_cap = capacity_for(lrp.per_shard_cap + rrp.per_shard_cap)
        return shard_apply2s(
            lrp, rrp,
            lambda a, b: _setop_traced(a, b, node.op, node.distinct,
                                       out_syms, out_cap),
            key=("setop", node.op, node.distinct, tuple(out_syms),
                 out_cap))

    # -- aggregation -----------------------------------------------------
    def _dexec_AggregationNode(self, node: AggregationNode) -> Value:
        # the filter/project chain under the aggregation runs inside
        # the aggregation's own program as a selection vector (no
        # compaction of the scan's rows), like the one-chip path
        chain = []
        cur = node.source
        while isinstance(cur, (FilterNode, ProjectNode)):
            chain.append(cur)
            cur = cur.source
        base = self.execute(cur)
        if isinstance(base, ShardedBatch):
            fused = self._fused_aggregation(node, chain, base)
            if fused is not None:
                return fused
        below = _Pre(base)
        for nd in reversed(chain):
            below = dc_replace(nd, source=below)
        src = self.execute(below)
        if not isinstance(src, ShardedBatch):
            return super()._exec_AggregationNode(
                dc_replace(node, source=_Pre(src)))
        if any(a.kind in ("array_agg", "map_agg", "histogram",
                          "approx_most_frequent", "map_union",
                          "multimap_agg", "numeric_histogram",
                          "tdigest_agg", "qdigest_agg",
                          "approx_set", "merge")
               for a in node.aggregates.values()):
            # array/map offsets don't survive shard-local numbering;
            # gather to the coordinator shard and aggregate locally
            return super()._exec_AggregationNode(
                dc_replace(node, source=_Pre(self._host(src))))
        # lower avg & friends against the global sharded lanes (extra
        # columns are elementwise — they stay sharded)
        glob = Batch(src.columns, 0)
        phys, post, extra = _lower_aggregates(node.aggregates, glob)
        if extra:
            cols = dict(src.columns)
            cols.update(extra)
            src = ShardedBatch(cols, src.num_rows, src.mesh,
                               src.per_shard_cap)
        if node.group_keys:
            out = distributed_group_aggregate(src, list(node.group_keys),
                                              phys)
            if post:
                cols = dict(out.columns)
                host_view = Batch(out.columns, 0)
                for sym, fn in post.items():
                    cols[sym] = fn(host_view)
                keep = set(node.group_keys) | set(node.aggregates)
                cols = {s: c for s, c in cols.items() if s in keep}
                out = ShardedBatch(cols, out.num_rows, out.mesh,
                                   out.per_shard_cap)
            return out
        # global aggregation: per-shard partials -> gather -> combine
        if not phys:
            return self._single_row(None)
        if any(a.kind not in _COMBINABLE for a in phys):
            # non-decomposable kinds: gather rows, aggregate exactly
            return super()._exec_AggregationNode(
                dc_replace(node, source=_Pre(self._host(src))))
        partial = shard_apply(
            src, lambda b: _pad_one(global_aggregate(b, phys)),
            key=("global_partial", tuple(phys)))
        gathered = unshard_batch(partial)
        finals = [AggInput(_combine_kind(a.kind), a.output, None,
                           a.output) for a in phys]
        out = global_aggregate(gathered, finals)
        if post:
            cols = dict(out.columns)
            for sym, fn in post.items():
                cols[sym] = fn(out)
            keep = set(node.aggregates)
            cols = {s: c for s, c in cols.items() if s in keep}
            out = Batch(cols, 1)
        return out

    def _fused_aggregation(self, node: AggregationNode, chain,
                           src: ShardedBatch) -> Optional[Batch]:
        """Aggregation over a filter/project chain over a sharded
        value as ONE mesh program: per shard the chain as a selection
        vector and the partial aggregation (``make_stream_parts``, the
        halves of the one-chip ``stream_full`` program), the partial
        rows all_gathered, the final combine and post-processing on
        every shard. Applies where a shard's partial is small (no GROUP
        BY, or keys with small static domains: the packed kernel);
        returns None otherwise and the caller takes the exchange."""
        if any(a.distinct or a.kind in self._NONSTREAMABLE
               for a in node.aggregates.values()):
            return None
        if not _small_partial(node, chain, src.columns):
            return None
        canon = canonicalize_nodes([node] + chain)
        node_x, chain_x = ((canon.nodes[0], canon.nodes[1:])
                           if canon is not None else (node, chain))
        key = None if canon is None else canon.key
        if key is not None and PROGRAMS.denied("spmd", key):
            return None
        binding = None
        cols = src.columns
        literals = {}
        if canon is not None:
            binding = canon.binding(Batch(cols, 0))
            bound = binding.rename_in(Batch(cols, 0))
            cols = bound.columns
            # the plan's literal vectors, replicated beside the lanes
            literals = getattr(bound, "literals", None) or {}
        partial, finish = make_stream_parts(self._detached(), chain_x,
                                            node_x)

        def build():
            def f(cols, num_rows_vec, literals):
                with literal_scope(literals or None):
                    return fused(cols, num_rows_vec)

            def fused(cols, num_rows_vec):
                d = jax.lax.axis_index(AXIS)
                out, phys, post = partial(Batch(cols, num_rows_vec[d]))
                if out.capacity > FUSED_PARTIAL_ROWS:
                    raise _PartialTooLarge()
                live = (jnp.arange(out.capacity, dtype=jnp.int64)
                        < out.num_rows_device())
                gathered = jax.tree.map(
                    lambda lane: jax.lax.all_gather(lane, AXIS)
                    .reshape(-1), out.columns)
                glive = jax.lax.all_gather(live, AXIS).reshape(-1)
                return finish(
                    Batch(gathered, jnp.sum(glive.astype(jnp.int64))),
                    phys, post, live=glive)
            return f, (_col_specs(cols, P(AXIS)), P(), P()), P()

        try:
            out = mesh_call("agg", key, src.mesh,
                            (cols, src.num_rows, literals), build)
        except (_PartialTooLarge,) + UNTRACEABLE:
            if key is not None:
                PROGRAMS.deny("spmd", key)
            return None
        # the result is the same on every chip: keep the coordinator's
        out = jax.tree.map(lambda a: a.addressable_shards[0].data, out)
        return out if binding is None else binding.rename_out(out)

    # -- joins -----------------------------------------------------------
    def _dexec_JoinNode(self, node: JoinNode) -> Value:
        jt = node.join_type
        if jt == "right":
            # swap before executing children so subtrees run only once
            from ..plan.nodes import JoinClause
            return self._dexec_JoinNode(JoinNode(
                node.right, node.left, "left",
                tuple(JoinClause(c.right, c.left) for c in node.criteria),
                node.filter, outputs=node.outputs))
        left = self.execute(node.left)
        right = self.execute(node.right)
        if not isinstance(left, ShardedBatch) and \
                not isinstance(right, ShardedBatch):
            return super()._exec_JoinNode(
                dc_replace(node, left=_Pre(left), right=_Pre(right)))
        if jt == "full" or not node.criteria or jt == "cross":
            # rare shapes: host fallback
            return super()._exec_JoinNode(
                dc_replace(node, left=_Pre(self._host(left)),
                           right=_Pre(self._host(right))))

        pkeys = [c.left for c in node.criteria]
        bkeys = [c.right for c in node.criteria]
        probe = left if isinstance(left, ShardedBatch) else None
        if probe is None:
            # probe on host, build sharded: gather build, local join
            return super()._exec_JoinNode(
                dc_replace(node, left=_Pre(left),
                           right=_Pre(self._host(right))))

        # hash-collision re-verification for inexact key lanes
        # (JoinProbe real-equality semantics; see executor.py)
        node = dc_replace(node, filter=join_verify_filter(
            _key_views(left.columns, pkeys),
            _key_views(right.columns, bkeys), pkeys, bkeys, node.filter))

        # dynamic filtering: build-side key ranges prune probe rows
        # BEFORE any exchange (reference: DynamicFilterService.java:95 +
        # DynamicFilterSourceOperator — collect on the build, push to
        # the probe; here collection is a host reduction over the build
        # key lanes and the push is a per-shard pre-filter)
        probe = self._dynamic_filter_probe(probe, right, pkeys, bkeys,
                                           jt)

        if not isinstance(right, ShardedBatch):
            # a build side held by the coordinator (a table too small
            # to shard): spread it, so that ONE join path serves it
            right = shard_batch(right, self.mesh)
        build = _align_sharded_dicts(probe, right, pkeys, bkeys)
        if (str(node.distribution or "").lower() == "partitioned"
                and jt in ("inner", "left")):
            # PARTITIONED distribution (DetermineJoinDistributionType's
            # PARTITIONED branch; AddExchanges.java's FIXED_HASH on both
            # children): both sides move so that matching rows
            # co-locate; the build side is never replicated
            probe = repartition_by_hash(probe, pkeys)
            build = repartition_by_hash(build, bkeys)
        else:
            # REPLICATED distribution: every shard gets the whole build
            build = broadcast_sharded(build)
        return self._join_shards(node, probe, build, pkeys, bkeys, jt)

    def _join_shards(self, node: JoinNode, probe: ShardedBatch,
                     build: ShardedBatch, pkeys, bkeys, jt: str) -> Value:
        """The per-shard join of two co-located operands (after the
        repartition or the broadcast), two-phase: a count program that
        keeps its run starts, counts and build order ON the shards, one
        blocking read of the per-shard totals (and the probe's mode
        beside them, as on one chip), an expand program at the capacity
        they give, handed the lanes the plan above reads (executor.py
        ``expand_columns``: the same rule as on one chip)."""
        outer = jt == "left"
        filt = node.filter
        pkeys, bkeys = tuple(pkeys), tuple(bkeys)
        operands = (probe.columns, probe.num_rows,
                    build.columns, build.num_rows)
        in_specs = (_col_specs(probe.columns, P(AXIS)), P(),
                    _col_specs(build.columns, P(AXIS)), P())
        pcols, bcols, kept = expand_columns(
            probe.columns, build.columns, expand_lanes(node.outputs, filt))
        expand_operands = (pcols, probe.num_rows, bcols, build.num_rows)

        def build_count():
            def f(pcols, pn, bcols, bn):
                d = jax.lax.axis_index(AXIS)
                pb, bb = Batch(pcols, pn[d]), Batch(bcols, bn[d])
                start, count, side = join_ops.match_runs(
                    pb, bb, list(pkeys), list(bkeys))
                eff = jnp.where(pb.row_valid(), jnp.maximum(count, 1),
                                0) if (outer and filt is None) else count
                return (start, count, side.order, jax.lax.all_gather(
                    join_ops.total_and_mode(eff, side, pb), AXIS))
            return f, in_specs, (P(AXIS), P(AXIS), P(AXIS), P())

        start, count, order, totals = mesh_call(
            "join_count", (pkeys, bkeys, outer and filt is None),
            probe.mesh, operands, build_count)
        out_cap = capacity_for(max(self._read_join_total(totals), 1))
        pad_cap = probe.per_shard_cap if (outer and
                                          filt is not None) else 0

        def build_expand():
            def f(pcols, pn, bcols, bn, start, count, order):
                d = jax.lax.axis_index(AXIS)
                out = _shard_join(Batch(pcols, pn[d]), Batch(bcols, bn[d]),
                                  start, count, order, jt, filt, out_cap,
                                  pad_cap, node.outputs)
                return out.columns, jax.lax.all_gather(
                    out.num_rows_device(), AXIS)
            return (f, (_col_specs(pcols, P(AXIS)), P(),
                        _col_specs(bcols, P(AXIS)), P(),
                        P(AXIS), P(AXIS), P(AXIS)),
                    (P(AXIS), P()))

        cols, counts = mesh_call(
            "join_expand", (jt, repr(filt), out_cap, pad_cap,
                            node.outputs),
            probe.mesh, expand_operands + (start, count, order),
            build_expand,
            form=join_ops.expand_form(probe.per_shard_cap, out_cap),
            lanes=kept)
        return ShardedBatch(cols, counts, probe.mesh, out_cap + pad_cap)

    def _dynamic_filter_probe(self, probe: ShardedBatch, build: Value,
                              pkeys, bkeys, jt: str) -> ShardedBatch:
        """Pre-exchange probe pruning from build-side key min/max
        (enable_dynamic_filtering session property). INNER joins only —
        outer probe rows must survive. Dictionary keys are skipped
        (codes are shard-local). Records rows_in/rows_kept on the
        executor for EXPLAIN/verification."""
        if jt != "inner" or not isinstance(probe, ShardedBatch):
            return probe
        if not bool(self.session.get("enable_dynamic_filtering")):
            return probe
        with self._host_read("dynamic_filter_rows"):
            build_rows = (build.total_rows_host()
                          if isinstance(build, ShardedBatch)
                          else build.num_rows_host())
        if build_rows > DYNAMIC_FILTER_BUILD_ROWS:
            # collecting the keys of a large build side costs a copy of
            # its key lane to the host, and its range prunes little
            return probe
        bounds = []
        for pk, bk in zip(pkeys, bkeys):
            pc = probe.columns[pk]
            bc = build.columns[bk]
            if pc.dictionary is not None or bc.dictionary is not None \
                    or bc.data2 is not None:
                continue
            data = np.asarray(bc.data)
            if isinstance(build, ShardedBatch):
                per = build.per_shard_cap
                counts = np.asarray(build.num_rows)
                live = (np.arange(per)[None, :]
                        < counts[:, None]).reshape(-1)
            else:
                n = build.num_rows_host()
                live = np.arange(data.shape[0]) < n
            if bc.valid is not None:
                live = live & np.asarray(bc.valid)
            vals = data[live]
            # the collected values are OPERANDS of the pruning program,
            # not constants of its trace: one program per key shape
            if vals.size == 0:
                bounds.append(((pk, "none", False),
                               np.zeros(2, data.dtype)))  # drop all
                continue
            # small-domain exact set beats min/max by orders of
            # magnitude on sparse keys (the reference's
            # discrete-values DynamicFilter domain)
            uniq = np.unique(vals)
            if uniq.dtype.kind in "iu":
                # padded with its own maximum: membership is unchanged
                cap = capacity_for(uniq.size, minimum=8)
                bounds.append(((pk, "set", False), np.pad(
                    uniq, (0, cap - uniq.size), mode="edge")))
                continue
            has_nan = (vals.dtype.kind == "f"
                       and bool(np.isnan(vals).any()))
            with np.errstate(invalid="ignore"):
                mn = (np.nanmin(vals) if has_nan else vals.min())
                mx = (np.nanmax(vals) if has_nan else vals.max())
            bounds.append(((pk, "range", has_nan),
                           np.asarray([mn, mx], data.dtype)))
        if not bounds:
            return probe
        specs = tuple(spec for spec, _ in bounds)
        operands = tuple(arr for _, arr in bounds)

        def build_prune():
            def f(cols, num_rows_vec, *values):
                b = Batch(cols, num_rows_vec[jax.lax.axis_index(AXIS)])
                mask = b.row_valid()
                for (pk, mode, has_nan), v in zip(specs, values):
                    c = b.column(pk)
                    d = jnp.asarray(c.data)
                    if mode == "none":
                        m = jnp.zeros(d.shape, bool)
                    elif mode == "set":
                        pos = jnp.clip(jnp.searchsorted(v, d), 0,
                                       v.shape[0] - 1)
                        m = jnp.take(v, pos, mode="clip") == d
                    else:
                        m = (d >= v[0]) & (d <= v[1])
                        if has_nan:
                            # engine equality treats all NaNs as equal
                            # (ops/hashing.py), so NaN probes can match
                            # a NaN build key and must survive
                            m = m | jnp.isnan(d)
                    if c.valid is not None:
                        # NULL keys never match an inner join
                        m = m & jnp.asarray(c.valid)
                    mask = mask & m
                out = compact.filter_batch(b, mask)
                return out.columns, jax.lax.all_gather(
                    out.num_rows_device(), AXIS)
            return (f, (_col_specs(probe.columns, P(AXIS)), P())
                    + tuple(P() for _ in operands), (P(AXIS), P()))

        cols, counts = mesh_call(
            "dynamic_filter", specs, probe.mesh,
            (probe.columns, probe.num_rows) + operands, build_prune)
        kept = ShardedBatch(cols, counts, probe.mesh, probe.per_shard_cap)
        if self.collect_stats:
            # (rows before, rows kept), read with the node row counts
            # at the end of execute
            self._unread.append((
                lambda kept_rows, before: setattr(
                    self, "dynamic_filter_rows", (before, kept_rows)),
                kept.num_rows, [probe.num_rows]))
        return kept

    def _dexec_SemiJoinNode(self, node: SemiJoinNode) -> Value:
        src = self.execute(node.source)
        if not isinstance(src, ShardedBatch):
            return super()._exec_SemiJoinNode(
                dc_replace(node, source=_Pre(src),
                           filtering_source=_Pre(self.execute_host(
                               node.filtering_source))))
        filt = self.execute_host(node.filtering_source)
        filt = _align_sharded_strings(src, filt, [node.source_key],
                                      [node.filtering_key])

        def f(b: Batch, fb: Batch) -> Batch:
            matched, key_null, build_null, nonempty = \
                join_ops.semi_join_mask(b, fb, [node.source_key],
                                        [node.filtering_key])
            valid = matched | ~nonempty | (~key_null & ~build_null)
            cols = dict(b.columns)
            cols[node.output] = Column(BOOLEAN, matched, valid)
            return Batch(cols, b.num_rows)

        return shard_apply2(src, filt, f)

    def _dexec_SemiJoinMultiNode(self, node: SemiJoinMultiNode) -> Value:
        src = self.execute(node.source)
        if not isinstance(src, ShardedBatch):
            return super()._exec_SemiJoinMultiNode(
                dc_replace(node, source=_Pre(src),
                           filtering_source=_Pre(self.execute_host(
                               node.filtering_source))))
        filt = self.execute_host(node.filtering_source)
        skeys = list(node.source_keys)
        fkeys = list(node.filtering_keys)
        filt = _align_sharded_strings(src, filt, skeys, fkeys)
        if skeys:
            node = dc_replace(node, filter=join_verify_filter(
                _key_views(src.columns, skeys),
                _key_views(filt.columns, fkeys), skeys, fkeys,
                node.filter))
        if node.filter is None and skeys:
            def f(b: Batch, fb: Batch) -> Batch:
                matched, _, _, _ = join_ops.semi_join_mask(
                    b, fb, skeys, fkeys)
                cols = dict(b.columns)
                cols[node.output] = Column(BOOLEAN, matched, None)
                return Batch(cols, b.num_rows)
            return shard_apply2(src, filt, f)

        def phase1(pb: Batch, fb: Batch):
            if skeys:
                _, count, _ = join_ops.match_counts(pb, fb, skeys, fkeys)
                return jnp.sum(count)
            return pb.num_rows_device() * fb.num_rows_device()

        totals = shard_totals2(src, filt, phase1)
        cand_cap = capacity_for(max(int(jnp.max(totals)), 1))

        def phase2(pb: Batch, fb: Batch) -> Batch:
            ppos = "__probe_pos$"
            pcols = dict(pb.columns)
            pcols[ppos] = Column(
                BIGINT, jnp.arange(pb.capacity, dtype=jnp.int64), None)
            probe2 = Batch(pcols, pb.num_rows)
            if skeys:
                start, count, order = join_ops.match_counts(
                    probe2, fb, skeys, fkeys)
            else:
                start, count, order = join_ops.cross_counts(probe2, fb)
            lanes = expand_lanes((), node.filter)
            cand = join_ops.expand_join(narrow(probe2, lanes),
                                        narrow(fb, lanes), start, count,
                                        order, cand_cap, "inner")
            mask = (eval_predicate(node.filter, cand)
                    if node.filter is not None else cand.row_valid())
            pp = jnp.asarray(cand.column(ppos).data)
            live = cand.row_valid() & mask
            matched = jnp.zeros((pb.capacity,), bool).at[
                jnp.where(live, pp, 0)].max(live)
            cols = dict(pb.columns)
            cols[node.output] = Column(BOOLEAN, matched, None)
            return Batch(cols, pb.num_rows)

        return shard_apply2(src, filt, phase2)


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _combine_kind(kind: str) -> str:
    return _COMBINABLE[kind]


def _align_setop_dicts(lb: ShardedBatch, rb: ShardedBatch,
                       syms) -> Tuple[ShardedBatch, ShardedBatch]:
    """Put both set-op sides' string columns on ONE merged dictionary
    (merge keeps left codes stable; right codes remap), so hash
    repartition co-locates equal strings and the per-shard group-by
    compares codes directly."""
    lcols = dict(lb.columns)
    rcols = dict(rb.columns)
    changed = False
    for s in syms:
        lc, rc = lcols.get(s), rcols.get(s)
        if lc is None or rc is None or lc.dictionary is None \
                or rc.dictionary is None \
                or lc.dictionary is rc.dictionary:
            continue
        merged, _, ro = lc.dictionary.merge(rc.dictionary)
        rcols[s] = dc_replace(
            rc, data=jnp.take(jnp.asarray(ro), jnp.asarray(rc.data),
                              mode="clip"), dictionary=merged)
        lcols[s] = dc_replace(lc, dictionary=merged)
        changed = True
    if not changed:
        return lb, rb
    return (ShardedBatch(lcols, lb.num_rows, lb.mesh, lb.per_shard_cap),
            ShardedBatch(rcols, rb.num_rows, rb.mesh, rb.per_shard_cap))


def _setop_traced(lb: Batch, rb: Batch, op: str, distinct: bool,
                  out_syms, out_cap: int) -> Batch:
    """setop_batches' shard_map-traceable twin: same tagging and
    semantics (exec/executor.py setop_tag/setop_keep_times), but a
    traced concat, a static groups capacity, and a device-scalar total
    (no host syncs inside shard_map)."""
    from .executor import SETOP_AGGS, setop_keep_times, setop_tag
    tagged = setop_tag(lb, rb)
    both = _trace_concat(tagged[0], tagged[1], out_cap)
    g = group_aggregate(both, out_syms, list(SETOP_AGGS),
                        groups_capacity=out_cap)
    nl = jnp.asarray(g.column("__nl$").data)
    nr = jnp.asarray(g.column("__nr$").data)
    keep, times = setop_keep_times(nl, nr, op, distinct)
    out = compact.filter_batch(g, keep)
    if times is not None:
        times = jnp.take(times, compact.mask_to_gather(keep)[0])
        live_times = jnp.where(out.row_valid(), times, 0)
        total = jnp.sum(live_times)           # device scalar
        incl = jnp.cumsum(live_times)
        p = jnp.clip(join_ops.run_positions(incl, out_cap), 0,
                     out.capacity - 1)
        out = out.gather(p, total)
    return Batch({s: out.column(s) for s in out_syms}, out.num_rows)


def _pad_one(b: Batch) -> Batch:
    """Pad a 1-row aggregate result to capacity 8 for shard transport."""
    cols = {}
    for s, c in b.columns.items():
        data = jnp.pad(jnp.asarray(c.data), (0, 8 - c.capacity))
        valid = (None if c.valid is None
                 else jnp.pad(jnp.asarray(c.valid), (0, 8 - c.capacity)))
        d2 = (None if c.data2 is None
              else jnp.pad(jnp.asarray(c.data2), (0, 8 - c.capacity)))
        cols[s] = Column(c.type, data, valid, c.dictionary, data2=d2)
    return Batch(cols, b.num_rows)


def _align_sharded_dicts(probe: ShardedBatch, build: ShardedBatch,
                         pkeys, bkeys) -> ShardedBatch:
    """Remap the build side's string-key code lanes onto the probe
    side's dictionaries (both sharded). The remap table is tiny and
    replicated; the gather is elementwise over the sharded lane."""
    cols = dict(build.columns)
    changed = False
    for pk, bk in zip(pkeys, bkeys):
        pc = probe.columns.get(pk)
        bc = cols.get(bk)
        if pc is None or bc is None or pc.dictionary is None \
                or bc.dictionary is None or pc.dictionary is bc.dictionary:
            continue
        merged, _, ro = pc.dictionary.merge(bc.dictionary)
        remap = jnp.asarray(ro)
        cols[bk] = dc_replace(
            bc, data=jnp.take(remap, jnp.asarray(bc.data), mode="clip"),
            dictionary=merged)
        changed = True
    if not changed:
        return build
    return ShardedBatch(cols, build.num_rows, build.mesh,
                        build.per_shard_cap)


def _align_sharded_strings(sb: ShardedBatch, host: Batch, skeys, hkeys
                           ) -> Batch:
    """Remap the host/build side's string key columns onto the sharded
    side's dictionaries so code equality == string equality. The sharded
    side's codes are left untouched (remapping them is also possible but
    costs a device pass per shard)."""
    cols = dict(host.columns)
    for sk, hk in zip(skeys, hkeys):
        sc = sb.columns.get(sk)
        hc = cols.get(hk)
        if sc is None or hc is None or sc.dictionary is None \
                or hc.dictionary is None:
            continue
        if sc.dictionary is hc.dictionary:
            continue
        # build-side strings unseen on the probe side get codes beyond
        # the probe dictionary — they can never equal a probe code,
        # which is exactly the join semantics required
        merged, rs, ro = sc.dictionary.merge(hc.dictionary)
        remap = jnp.asarray(ro)
        cols[hk] = dc_replace(
            hc, data=jnp.take(remap, jnp.asarray(hc.data), mode="clip"),
            dictionary=merged)
    return Batch(cols, host.num_rows)


def _trace_concat(a: Batch, b: Batch, out_cap: int) -> Batch:
    """Concatenate two batches' live prefixes inside a trace (static
    capacities; counts are device scalars)."""
    na = a.num_rows_device()
    nb = b.num_rows_device()
    live = jnp.concatenate([
        jnp.arange(a.capacity, dtype=jnp.int64) < na,
        jnp.arange(b.capacity, dtype=jnp.int64) < nb])
    idx = jnp.nonzero(live, size=out_cap, fill_value=0)[0]
    cols = {}
    for name in a.names:
        ca, cb = a.column(name), b.column(name)
        data = jnp.take(jnp.concatenate(
            [jnp.asarray(ca.data),
             # jnp dtype read: np.asarray here would host-sync a traced
             # array inside shard_map
             jnp.asarray(cb.data).astype(jnp.asarray(ca.data).dtype)]),
            idx, mode="clip")
        valid = None
        if ca.valid is not None or cb.valid is not None:
            va = (jnp.ones((ca.capacity,), bool) if ca.valid is None
                  else jnp.asarray(ca.valid))
            vb = (jnp.ones((cb.capacity,), bool) if cb.valid is None
                  else jnp.asarray(cb.valid))
            valid = jnp.take(jnp.concatenate([va, vb]), idx, mode="clip")
        d2 = None
        if ca.data2 is not None or cb.data2 is not None:
            from ..columnar import hi_lane_or_fill
            d2 = jnp.take(jnp.concatenate(
                [hi_lane_or_fill(ca), hi_lane_or_fill(cb)]), idx,
                mode="clip")
        cols[name] = Column(ca.type, data, valid, ca.dictionary,
                            data2=d2)
    return Batch(cols, na + nb)


def _shard_join(pb: Batch, bb: Batch, start, count, order, jt: str,
                filt, out_cap: int, pad_cap: int, outputs=None) -> Batch:
    """Trace-safe single-shard join expansion from the count program's
    run starts, counts and build order (the per-shard body of both
    join distributions). ``pb`` and ``bb`` hold the lanes the expand
    gathers (either may hold none); the capacities are ``count``'s and
    ``order``'s; what only the residual read leaves after it."""
    outer = jt == "left"
    if filt is None:
        return join_ops.expand_join(pb, bb, start, count, order, out_cap,
                                    "left" if outer else "inner")
    pcap = count.shape[0]
    ppos = "__probe_pos$"
    pcols = dict(pb.columns)
    pcols[ppos] = Column(BIGINT, jnp.arange(pcap, dtype=jnp.int64), None)
    probe2 = Batch(pcols, pb.num_rows)
    cand = join_ops.expand_join(probe2, bb, start, count, order, out_cap,
                                "inner")
    mask = eval_predicate(filt, cand)
    out = compact.filter_batch(cand, mask)
    pp = jnp.asarray(out.column(ppos).data)
    live_out = out.row_valid()
    out = Batch({s: c for s, c in out.columns.items() if s != ppos
                 and (outputs is None or s in outputs)}, out.num_rows)
    if not outer:
        return out
    matched = jnp.zeros((pcap,), bool).at[
        jnp.where(live_out, pp, 0)].max(live_out)
    live_p = jnp.arange(pcap, dtype=jnp.int64) < pb.num_rows_device()
    idx, n_pad = compact.mask_to_gather(live_p & ~matched)
    pad_cols = dict(narrow(pb, outputs).gather(idx, n_pad).columns)
    for s, c in narrow(bb, outputs).columns.items():
        z = jnp.zeros((pcap,), dtype=jnp.asarray(c.data).dtype)
        pad_cols[s] = Column(c.type, z, jnp.zeros((pcap,), bool),
                             c.dictionary)
    return _trace_concat(out, Batch(pad_cols, n_pad), out_cap + pad_cap)


def _key_views(cols, keys) -> Dict[str, Column]:
    """The key columns with EMPTY data lanes of the same dtype:
    ``join_verify_filter`` learns a key's dtype by copying its lane to
    the host (ROADMAP S3), which a lane sharded over the mesh must not
    pay; type, dtype and the second lane are all it reads."""
    return {k: dc_replace(cols[k], data=np.empty(
        0, np.dtype(cols[k].data.dtype))) for k in keys}


def _node_key(node: PlanNode):
    """The mesh-program key of a per-shard node segment: its structural
    fingerprint (exec/progkey.py), or None where it has none."""
    fp = node_fingerprint(node)
    return None if fp is None else (type(node).__name__, fp)
