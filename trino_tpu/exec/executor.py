"""Single-process plan executor.

Reference parity: the worker execution stack — LocalExecutionPlanner
(sql/planner/LocalExecutionPlanner.java:307) + Driver loop
(operator/Driver.java:355-440) + the operator set (SURVEY.md §2.1).
TPU-first redesign (SURVEY.md §7.2): there is no operator pull-loop; the
executor walks the plan bottom-up, evaluating each node as whole-column
jnp transformations over capacity-padded Batches. XLA fuses chains of
filter/project/aggregate into single device programs; data-dependent
cardinalities (filter/join output sizes) are the only host syncs — the
two-phase "count, pick bucket, expand" pattern of ops/join.py.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field, replace as dc_replace
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..catalog import CatalogManager
from ..columnar import (Batch, Column, StringDictionary, batch_from_pylist,
                        empty_batch, pad_batch)
from ..config import (CONFIG, MemoryLimitExceeded, capacity_for,
                      reserve_bytes)
from ..ops import compact, join as join_ops, sort as sort_ops
from ..ops.groupby import (AggInput, dense_key_lane, dense_key_range,
                           dense_keys, global_aggregate, group_aggregate,
                           note_form, noted_forms)
from ..ops.hashing import hash_columns, partition_of
from ..plan.nodes import (AggregationNode, Aggregate, AssignUniqueIdNode,
                          EnforceSingleRowNode, ExchangeNode, FilterNode,
                          JoinNode, LimitNode, MarkDistinctNode, OffsetNode,
                          OutputNode, PartitionedOutputNode, PlanNode,
                          ProjectNode, RemoteSourceNode, SampleNode,
                          SemiJoinNode, SetOpNode, SortNode, TableScanNode,
                          TopNNode, UnionNode, ValuesNode, WindowNode)
from ..planner.logical import SemiJoinMultiNode
from ..rex import Const, InputRef, input_names
from ..session import Session
from ..types import (BIGINT, BOOLEAN, DOUBLE, REAL, DecimalType, Type,
                     is_integral, is_string)
from .expr import EvalError, eval_expr, eval_predicate
from .literals import bound_count, call_bound
from .progkey import PROGRAMS, UNTRACEABLE, named_jit


class QueryError(Exception):
    """Engine/user-facing failure. ``error_name`` (when set) pins the
    StandardErrorCode name for errors.classify — governance errors
    (memory kills, deadline breaches) must reach the client with their
    Trino identity, not a message-sniffed guess."""

    def __init__(self, message: str,
                 error_name: "Optional[str]" = None):
        super().__init__(message)
        if error_name is not None:
            self.error_name = error_name


class _Pre(PlanNode):
    """Wraps an already-computed Batch so handlers can recurse through
    self.execute() transparently (used by the distributed executors to
    pre-materialize sources and by the remote scheduler to substitute
    gathered fragments). Lives here — NOT in exec/distributed.py — so
    the host-worker dispatch path (exec/remote.py) stays importable
    when the mesh stack (parallel/spmd.py) is unavailable."""

    __slots__ = ("batch",)

    def __init__(self, batch):
        self.batch = batch

    @property
    def sources(self):
        return ()

    def output_schema(self):
        return self.batch.schema()


@dataclass
class NodeStats:
    """OperatorStats analog (operator/OperatorStats.java): per-plan-node
    wall time, row/byte flow, compile (jit-trace) wall, device time
    (jitted-dispatch completion, distinct from wall — the tensor-
    runtime headline split), thread-CPU time, and cache-hit flags,
    powering EXPLAIN ANALYZE, /v1/query/{id}, and the distributed
    stats rollup (workers serialize these in task results; the
    coordinator merges them per stage — see merge_node_stats)."""
    name: str
    detail: str = ""
    wall_s: float = 0.0
    output_rows: int = -1
    input_rows: int = -1
    input_bytes: int = -1
    output_bytes: int = -1
    compile_s: float = 0.0
    cache_hit: Optional[bool] = None
    # seconds this node's jitted dispatches took on the HOST clock from
    # dispatch to outputs ready (exec/executor.py _jit_call), under
    # EXPLAIN ANALYZE only (0 in a served query, whose wall is host
    # time): an upper bound on device time — own dispatches only, NOT
    # children's (unlike wall, which nests)
    device_s: float = 0.0
    # thread-CPU seconds across this node's execution (includes
    # children, like wall — the two are directly comparable)
    cpu_s: float = 0.0

    def put_rows(self, output_rows: int, input_rows: int) -> None:
        self.output_rows, self.input_rows = output_rows, input_rows

    def to_dict(self) -> dict:
        return {"name": self.name, "detail": self.detail,
                "wall_s": self.wall_s, "output_rows": self.output_rows,
                "input_rows": self.input_rows,
                "input_bytes": self.input_bytes,
                "output_bytes": self.output_bytes,
                "compile_s": self.compile_s,
                "cache_hit": self.cache_hit,
                "device_s": self.device_s,
                "cpu_s": self.cpu_s}

    @staticmethod
    def from_dict(d: dict) -> "NodeStats":
        return NodeStats(
            d.get("name", "?"), d.get("detail", ""),
            float(d.get("wall_s", 0.0)), int(d.get("output_rows", -1)),
            int(d.get("input_rows", -1)), int(d.get("input_bytes", -1)),
            int(d.get("output_bytes", -1)),
            float(d.get("compile_s", 0.0)), d.get("cache_hit"),
            float(d.get("device_s", 0.0)), float(d.get("cpu_s", 0.0)))


def _discard_rows(output_rows: int, input_rows: int) -> None:
    """The row counts of an internal wrapper: its parent's input, no
    entry of its own."""


def _sum_counts(vals: Sequence[int]) -> int:
    known = [v for v in vals if v is not None and v >= 0]
    return sum(known) if known else -1


def merge_node_stats(
        per_worker: Sequence[Sequence["NodeStats"]]) -> List["NodeStats"]:
    """Roll worker-reported per-node stats up into one per-stage list.
    Every worker executed the SAME fragment plan, but fast paths
    (streaming aggregation fuses scan+agg into one entry; an empty
    split share takes the generic path) mean the lists need not align
    positionally — entries merge by (node name, occurrence index),
    ordered by the most detailed worker's list. Rows/bytes sum across
    workers (they partition the input); wall and compile take the max
    (the stage's critical path — tasks run concurrently); cache_hit
    ANDs (one cold worker means the stage paid a compile)."""
    lists = [list(l) for l in per_worker if l]
    if not lists:
        return []

    def keyed(l: Sequence["NodeStats"]):
        seen: Dict[str, int] = {}
        out = []
        for s in l:
            i = seen.get(s.name, 0)
            seen[s.name] = i + 1
            out.append(((s.name, i), s))
        return out

    base = max(lists, key=len)
    by_key: Dict[tuple, List[NodeStats]] = {}
    extras: List[tuple] = []
    # base is first in the stable descending sort, so keys discovered
    # in OTHER lists are by construction not base keys
    for l in sorted(lists, key=len, reverse=True):
        for k, s in keyed(l):
            if k not in by_key:
                by_key[k] = []
                if l is not base:
                    extras.append(k)  # go after base's order
            by_key[k].append(s)
    order = [k for k, _ in keyed(base)] + extras
    merged: List[NodeStats] = []
    for k in order:
        same = by_key[k]
        hits = [s.cache_hit for s in same if s.cache_hit is not None]
        merged.append(NodeStats(
            same[0].name, same[0].detail,
            max(s.wall_s for s in same),
            _sum_counts([s.output_rows for s in same]),
            _sum_counts([s.input_rows for s in same]),
            _sum_counts([s.input_bytes for s in same]),
            _sum_counts([s.output_bytes for s in same]),
            max(s.compile_s for s in same),
            all(hits) if hits else None,
            # device/CPU are RESOURCE totals: tasks run concurrently
            # on different devices/cores, so the stage consumed the
            # SUM (wall takes the max — the critical path)
            sum(s.device_s for s in same),
            sum(s.cpu_s for s in same)))
    return merged


def render_analyze_lines(plan_lines, stats, trace) -> List[str]:
    """The EXPLAIN ANALYZE text body: plan tree, per-node stats, and
    the span-tree section — one renderer shared by the local runner
    and the distributed host runner so the formats cannot drift."""
    lines = list(plan_lines or [])
    lines.append("")
    lines.extend(stats_lines(stats or []))
    if trace is not None and trace.roots:
        lines.append("")
        lines.append("Trace:")
        lines.extend(trace.lines())
    return lines


def stats_lines(stats: Sequence["NodeStats"]) -> List[str]:
    """EXPLAIN ANALYZE text rendering of a NodeStats list (reference:
    planprinter/PlanPrinter's textDistributedPlan stats columns)."""
    out = []
    for s in stats:
        parts = [f"{s.name}: {s.wall_s * 1000:.2f}ms"]
        if s.input_rows >= 0:
            parts.append(f"in {s.input_rows} rows"
                         + (f"/{s.input_bytes}B"
                            if s.input_bytes >= 0 else ""))
        parts.append(f"out {s.output_rows} rows"
                     + (f"/{s.output_bytes}B"
                        if s.output_bytes >= 0 else ""))
        if s.compile_s > 0:
            parts.append(f"compile {s.compile_s * 1000:.2f}ms")
        if s.device_s > 0:
            # device time ≠ wall: the jitted dispatches' completion
            # wait, the number that explains tensor-engine latency
            parts.append(f"device {s.device_s * 1000:.2f}ms")
        if s.cpu_s > 0:
            parts.append(f"cpu {s.cpu_s * 1000:.2f}ms")
        if s.cache_hit is not None:
            parts.append("cache hit" if s.cache_hit else "cache miss")
        if s.detail:
            parts.append(s.detail)
        out.append(", ".join(parts))
    return out


# plan nodes whose _apply_ is pure jnp (traceable): a chain of these over
# one source compiles into a single XLA program — the reference's
# "one bytecode class per pipeline" (ExpressionCompiler) as jax.jit
# (SURVEY.md §7.2)
_TRACEABLE = ()  # filled after class definition
_PPOS, _BPOS = "__probe_pos$", "__build_pos$"

# jitted plan programs live ACROSS queries in the one program cache
# (exec/progkey.py PROGRAMS), keyed by the canonical program keys of
# that module's ONE canonicalizer (shared with the hot-shape registry,
# exec/hotshapes.py, and the AOT compiler, exec/aot.py); the buckets
# filled from this module: "chain", "stream" (per-split and
# whole-table aggregation), "ragged" (canonical chain + the __rq
# provenance lane, progkey.ragged_nodes), "window" and "join" (the
# count + expand programs of the materialized hash join).

# process metrics (obs/metrics.py; scraped at GET /metrics). These are
# per-query-phase increments, never per-row — the lock cost is noise.
from contextlib import nullcontext as _nullcontext
from ..obs.metrics import METRICS as _METRICS
from ..obs.trace import active_span, dispatch_span
_NO_SPAN = _nullcontext()
# the jit-cache family is defined ONCE in obs/metrics.py (streamjoin's
# probe-program cache feeds the same family — a second registration
# here would trip the metrics-hygiene lint)
from ..obs.metrics import GROUPBYS as _M_GROUPBYS, \
    GROUPBY_LANES as _M_GROUPBY_LANES
from ..obs.metrics import JIT_CACHE_LOOKUPS as _M_JIT
_M_SCAN = _METRICS.counter(
    "trino_tpu_scan_cache_total",
    "HBM-resident scan cache lookups by granularity and outcome",
    ("cache", "result"))
_M_SCAN_BYTES = _METRICS.gauge(
    "trino_tpu_scan_cache_bytes",
    "Bytes of table lanes resident in the scan cache", ("connector",))
_M_SPILL = _METRICS.counter(
    "trino_tpu_spill_bytes_total",
    "Bytes written to host RAM by oversized-join spill")
_M_SPLITS = _METRICS.counter(
    "trino_tpu_splits_read_total", "Table splits read by the executor")


# volatility lives in rex (a property of expressions, shared with the
# planner); these aliases keep the executor-local names working
from ..rex import VOLATILE_FNS as _VOLATILE_FNS, \
    expr_volatile as _expr_volatile


def _keys_inexact(cols, keys) -> bool:
    """True when the uint64 equality lane of ops/join.py cannot be
    bijective for these keys: multi-column (hash-combined), float
    (hash-converted), or Int128 decimal (only the low lane is hashed)."""
    if len(keys) > 1:
        return True
    c = cols[keys[0]]
    if c.data2 is not None:
        return True
    # a lane's dtype is known without reading it (np.asarray would copy
    # the whole lane to the host: 450 ms for a key lane a scan derive
    # had just made)
    return np.dtype(c.data.dtype).kind == "f"


def join_verify_filter(left_cols, right_cols, pkeys, bkeys, filt):
    """Hash-collision re-verification (reference: JoinProbe verifies
    candidate positions by real key equality, never by hash alone).
    When the key lane is inexact, append key-equality conjuncts to the
    residual filter; the residual join path then drops collision rows
    and repairs outer rows from the surviving match set."""
    if not (_keys_inexact(left_cols, pkeys)
            or _keys_inexact(right_cols, bkeys)):
        return filt
    from ..rex import Call as _RCall, and_all
    eqs = [
        _RCall("=", (InputRef(pk, left_cols[pk].type),
                     InputRef(bk, right_cols[bk].type)), BOOLEAN)
        for pk, bk in zip(pkeys, bkeys)]
    return and_all(([filt] if filt is not None else []) + eqs)


def expand_lanes(outputs, residual=None):
    """The lanes a join's expand gathers (the reference's
    left/rightOutputSymbols, PruneJoinColumns): the join's ``outputs``,
    what its residual filter reads, and the position lanes the outer
    repair reads; None where the join puts out every lane."""
    if outputs is None:
        return None
    keep = set(outputs) | {_PPOS, _BPOS}
    if residual is not None:
        keep |= input_names(residual)
    return frozenset(keep)


def expand_columns(probe_cols, build_cols, lanes):
    """(probe columns, build columns, ``"<kept>/<offered>"``): the
    columns of a join's two inputs that its expand is handed — ONE rule
    for every join path (one chip, the mesh, the streamed probe) — and
    the ``lanes`` attr of the expand's dispatch span."""
    offered = len(probe_cols) + len(build_cols)
    if lanes is not None:
        probe_cols = {s: c for s, c in probe_cols.items() if s in lanes}
        build_cols = {s: c for s, c in build_cols.items() if s in lanes}
    return (probe_cols, build_cols,
            f"{len(probe_cols) + len(build_cols)}/{offered}")


def narrow(b: Batch, lanes) -> Batch:
    """``b`` with the lanes in ``lanes`` alone (every lane for None)."""
    if lanes is None:
        return b
    return Batch({s: c for s, c in b.columns.items() if s in lanes},
                 b.num_rows)


def _expand_width(probe: Batch, build: Batch, lanes) -> int:
    """The lanes an expand of ``probe`` and ``build`` puts out: what
    its memory reservation counts."""
    p, b, _ = expand_columns(probe.columns, build.columns, lanes)
    return len(p) + len(b)


def _call_noting_forms(jitted, args: tuple):
    """Call a jitted program; on the call that TRACES it, keep with the
    program the form each ``group_aggregate`` inside it chose
    (``jitted.groupby_forms``: ((form, input lanes), ...), empty where
    it groups nothing). A program's forms are static, so what the first
    call noted is what every later dispatch runs."""
    if getattr(jitted, "groupby_forms", None) is not None:
        return jitted(*args)
    with noted_forms() as notes:
        out = jitted(*args)
    jitted.groupby_forms = tuple(notes)
    return out


class _NotDense(Exception):
    """Raised while the dense aggregation program is traced: the
    lowered aggregates or the key lane turned out not to be the dense
    form's (``ops/groupby.py dense_eligible``)."""


# the rule of the counted filter (``Executor._counted_filter``): a
# Filter over a batch of at least this many lanes is evaluated as a
# mask, COUNTED (one host read, site ``filter_rows``) and compacted at
# the capacity of what it keeps. Under it a filter compacts at its
# input's capacity, as before: a sync costs more than it would save.
COUNTED_FILTER_MIN_LANES = 1 << 22


class Executor:
    def __init__(self, catalogs: CatalogManager, session: Session,
                 collect_stats: bool = False,
                 fragment_jit: Optional[bool] = None):
        self.catalogs = catalogs
        self.session = session
        self.collect_stats = collect_stats
        self.stats: List[NodeStats] = []
        if fragment_jit is None:
            # on an accelerator every eager op is its own dispatch, so
            # chains run as one jitted program there; on CPU the
            # compile cost dominates short queries.
            # TRINO_TPU_FRAGMENT_JIT=1|0 overrides the backend default
            # (a CPU fleet serving REPEATED shapes amortizes compiles
            # through the canonical-key caches + persistent cache, and
            # the warm-path tests exercise exactly that)
            env = os.environ.get("TRINO_TPU_FRAGMENT_JIT", "")
            if env in ("0", "1"):
                fragment_jit = env == "1"
            else:
                fragment_jit = jax.default_backend() not in ("cpu",)
        self.fragment_jit = fragment_jit
        self._no_jit_chains: set = set()
        self._not_dense: set = set()    # ids of aggregations that declined
        self._jit_chains: dict = {}
        # per-query telemetry accumulators (obs/): stat frames track
        # each node's input flow (children add their output on exit);
        # peak/spill feed the enriched QueryCompletedEvent
        self._frames: List[dict] = []
        # row counts held as the values the plan left them (a host int
        # or a device count) until the end of ``execute`` reads them
        # all in ONE transfer (``_settle_rows``): (put(rows, input
        # rows), output count, input counts)
        self._unread: List[tuple] = []
        self._running = False
        self.peak_reserved_bytes: int = 0
        self.spilled_bytes: int = 0
        # morsel streaming (exec/streamjoin.py): chunks processed and
        # host->device bytes moved by streamed operators this query —
        # exported in worker task status (streamChunks/streamH2dBytes)
        # and rolled up by the remote/stage schedulers
        self.stream_chunks: int = 0
        self.stream_h2d_bytes: int = 0
        # ragged multi-query batching (exec/taskexec.py RaggedBatcher):
        # chain dispatches this query served through a co-batched
        # ragged program — exported in worker task status
        # (raggedBatched) and rolled up by the remote/stage schedulers
        self.ragged_batched: int = 0
        # device-time attribution under EXPLAIN ANALYZE (ISSUE 15):
        # seconds this executor's jitted dispatches took from dispatch
        # to data-ready on the host clock (_jit_call; an upper bound on
        # device time), exported as deviceSeconds in worker task status
        # and rolled up per stage. 0 in a served query: its device time
        # is read on the device trace, not waited for
        self.device_s: float = 0.0
        # > 0 while a morsel-streamed chunk loop is driving dispatches
        # (exec/streamjoin.py run_streamed): device timing's block-
        # until-ready would serialize the double-buffered overlap, so
        # streamed chunks forgo device attribution — the overlap
        # contract outranks it
        self._stream_depth: int = 0
        # remote-task split addressing: (part, nparts) makes every scan
        # read only splits with index % nparts == part (the worker's
        # share of a fragment — server/task_worker.py fragment payloads;
        # reference: SqlStageExecution assigning splits to tasks)
        self.scan_partition: Optional[Tuple[int, int]] = None
        # stage-DAG exchange input (trino_tpu/stage/): fid -> batches
        # of this task's partition of upstream stage ``fid``'s output
        # (the ExchangeOperator hook; wired by server/task_worker.py
        # for worker stage tasks and by exec/remote.py for the
        # coordinator's root stage)
        self.exchange_reader = None

    def _detached(self) -> "Executor":
        """Lightweight clone captured by closures that outlive this
        query in the structural JIT caches: shares catalogs/session but
        carries no per-query jit/stats state, so a cached program does
        not pin its first query's executor object graph."""
        return Executor(self.catalogs, self.session)

    @property
    def trace(self):
        """The current query's span tree (obs/trace.py), carried on the
        Session by the runner; None outside a traced query."""
        return getattr(self.session, "trace", None)

    @property
    def analyze(self) -> bool:
        """EXPLAIN ANALYZE (its trace says so): every program is waited
        for and timed, every plan node's rows are read at its end. A
        served query does neither."""
        tr = self.trace
        return tr is not None and tr.analyze

    # ------------------------------------------------------------------
    def execute(self, node: PlanNode) -> Batch:
        """Run ``node``'s plan. With telemetry (a trace or node stats)
        the outermost call ends in ONE ``host_read[node_rows]``: the
        plan's output waited for and every node's row count read."""
        if self._running or not (self.collect_stats
                                 or self.trace is not None):
            return self._execute_node(node)
        self._running = True
        try:
            out = self._execute_node(node)
        finally:
            self._running = False
        self._settle_rows(out)
        return out

    def _settle_rows(self, out) -> None:
        """The end of a telemetered ``execute``: wait for the plan's
        output and bring every row count the stats hold back in ONE
        transfer (``host_read[node_rows]``), then write them into the
        stats. Under EXPLAIN ANALYZE every count was read at its node's
        fence: nothing is left to wait for."""
        pending, self._unread = self._unread, []
        # a child's count is also its parent's input: each value once
        held = {id(v): v for _s, o, ins in pending for v in (o, *ins)
                if not isinstance(v, int)}
        if not self.analyze:
            lanes = [] if out is None else [
                lane for c in out.columns.values()
                for lane in (c.data, c.valid, c.data2) if lane is not None]
            with self._host_read("node_rows"):
                try:
                    jax.block_until_ready(lanes)
                except Exception:   # noqa: BLE001 — non-array lanes
                    pass
                held = dict(zip(held, jax.device_get(list(held.values()))))

        def rows(v) -> int:
            return v if isinstance(v, int) else int(np.sum(held[id(v)]))
        for put, o, ins in pending:
            put(rows(o), sum(rows(v) for v in ins))

    def _execute_node(self, node: PlanNode) -> Batch:
        cancel = getattr(self.session, "cancel", None)
        if cancel is not None and cancel.is_set():
            # cooperative cancellation between plan nodes (reference:
            # Driver loop checks the yield/termination signal)
            raise QueryError("Query was canceled")
        deadline = getattr(self.session, "deadline", None)
        if deadline is not None and time.monotonic() > deadline:
            # deadline enforcement at the same granularity as cancel:
            # a breach stops execution between plan nodes instead of
            # waiting for the coordinator's next poll
            raise QueryError(
                "Query exceeded the maximum run time "
                "(query_max_run_time)", error_name="EXCEEDED_TIME_LIMIT")
        yld = getattr(self.session, "split_yield", None)
        if yld is not None:
            # shared split scheduler (exec/taskexec.py): a plan-node
            # boundary is a yield point too — operators without split
            # or chunk loops (exchange-fed joins, sorts) still hand
            # the runner slot to a higher-priority query's task here
            yld()
        if not self.collect_stats:
            return self._execute_inner(node)
        return self._stats_wrap(node, lambda: self._execute_inner(node))

    def _stats_wrap(self, node: PlanNode, fn):
        """Time one node's execution and record a NodeStats entry.
        A frame on the stack accumulates this node's input flow: every
        child node adds its own output rows/bytes to the parent frame
        on exit, and split reads add the scanned rows directly. The
        row counts stay as the plan holds them (``_unread``) until the
        end of ``execute`` reads them together; only EXPLAIN ANALYZE
        reads each node's at its end (``host_read[node_fence]``), so
        that its wall holds the node's device work."""
        frame = {"rows": [], "bytes": 0, "compile_s": 0.0,
                 "cache": None, "device_s": 0.0}
        self._frames.append(frame)
        t0 = time.perf_counter()
        cpu0 = time.thread_time()
        try:
            out = fn()
        finally:
            self._frames.pop()
        if out is None:     # a path that declined: nothing ran
            return None
        # CPU before EXPLAIN ANALYZE's blocking row read below: the host
        # decode of the output is accounting, not the operator's work
        cpu_s = max(time.thread_time() - cpu0, 0.0)
        n = out.num_rows    # a host int, or a device count (per shard)
        if self.analyze:
            with self._host_read("node_fence"):
                n = int(np.sum(np.asarray(n)))
        obytes = sum(_col_bytes(c) for c in out.columns.values())
        name = type(node).__name__.replace("Node", "")
        put = _discard_rows
        if not name.startswith("_"):
            # internal wrappers (_Pre preloaded batches) are plumbing,
            # not operators — they feed the parent's input, no entry
            detail = ""
            if frame.get("stream_chunks"):
                # morsel streaming: chunk count + transfer volume per
                # operator, the EXPLAIN ANALYZE face of streamjoin.py
                detail = (f"streamed {frame['stream_chunks']} chunks, "
                          f"{frame.get('stream_h2d', 0)}B h2d")
            entry = NodeStats(
                name, detail, wall_s=time.perf_counter() - t0,
                input_bytes=frame["bytes"],
                output_bytes=obytes, compile_s=frame["compile_s"],
                cache_hit=frame["cache"],
                device_s=frame["device_s"], cpu_s=cpu_s)
            self.stats.append(entry)
            put = entry.put_rows
        self._unread.append((put, n, frame["rows"]))
        if self._frames:
            parent = self._frames[-1]
            parent["rows"].append(n)
            parent["bytes"] += obytes
        return out

    def _host_read(self, site: str):
        """The span of ONE blocking device-to-host read (``host_read``,
        attr ``site``): every place where this executor pulls a value
        to the host and the device drains meanwhile — a bubble the
        counters at /metrics count by site. A no-op without a trace."""
        tr = self.trace
        return (tr.span("host_read", site=site) if tr is not None
                else _NO_SPAN)

    def _jit_call(self, jitted, args: tuple, cache: str, hit: bool,
                  **attrs):
        """Invoke a jitted program under a ``dispatch`` (steady state)
        or ``jit_trace`` (first, cache-miss call: trace + XLA compile +
        dispatch) span carrying the program's identity
        (``program=<kind>:<key8>``, exec/progkey.py named_jit) and
        ``attrs``, attributing compile wall to the current node's stats
        frame. The span runs from the call to its return and waits for
        nothing: jax's dispatch is async, the program's device time is
        read on the device trace under the same program name, and what
        waits is the read that needs an output. Only EXPLAIN ANALYZE
        waits here (``device_execute``, ``device_ms``: the HOST clock
        from dispatch to outputs ready, an upper bound on the program's
        device time, summed per node into ``device_s``)."""
        tr = self.trace
        if tr is None and not self.collect_stats:
            return _call_noting_forms(jitted, args)
        bound = bound_count(args)
        if bound:
            # the literals the program takes as arguments
            # (exec/literals.py): trino_tpu_program_literal_args_total
            attrs["args"] = bound
        t0 = time.perf_counter()
        t1 = dev_s = None
        try:
            with dispatch_span(tr, getattr(jitted, "program", None)
                               or f"{cache}:local", hit, cache,
                               **attrs) as sp:
                out = _call_noting_forms(jitted, args)
                t1 = time.perf_counter()
                forms = getattr(jitted, "groupby_forms", None)
                if forms and sp is not None:
                    # the grouped aggregations inside the program, by
                    # the form each runs in (packed | dense | sort) and
                    # its input lanes: obs/metrics.py counts them per
                    # dispatch (trino_tpu_groupby_total / _lanes_total)
                    sp.attrs["form"] = forms[0][0]
                    sp.attrs["groupby"] = ",".join(
                        f"{f}:{n}" for f, n in forms)
                if tr is not None and tr.analyze \
                        and self._stream_depth == 0:
                    # device attribution syncs — inside a streamed
                    # chunk loop that sync would serialize the double-
                    # buffered transfer/compute overlap, so streamed
                    # dispatches skip it (their chunks report wall
                    # only)
                    try:
                        jax.block_until_ready(out)
                    except Exception:   # noqa: BLE001 — non-array outputs
                        pass
                    t2 = time.perf_counter()
                    # hit: the whole dispatch-to-ready window; miss:
                    # only the post-trace completion wait (the trace+
                    # compile share lands in compile_s below)
                    dev_s = (t2 - t0) if hit else (t2 - t1)
                    if sp is not None:
                        sp.attrs["device_ms"] = round(dev_s * 1000, 3)
            return out
        finally:
            if t1 is None:
                t1 = time.perf_counter()
            if dev_s:
                self.device_s += dev_s
                if self._frames:
                    self._frames[-1]["device_s"] += dev_s
            if not hit and self._frames:
                self._frames[-1]["compile_s"] += t1 - t0
                if self._frames[-1]["cache"] is None:
                    self._frames[-1]["cache"] = False
            elif hit and self._frames \
                    and self._frames[-1]["cache"] is None:
                self._frames[-1]["cache"] = True

    def _read_split(self, conn, split, columns) -> Batch:
        """Split read with telemetry: wall-timed for the
        SplitCompletedEvent (fired when the session carries an event
        manager — the task/split completion path), counted into the
        metrics registry, and charged to the current node's input."""
        t0 = time.perf_counter()
        b = read_split_cached(conn, split, columns)
        wall = time.perf_counter() - t0
        _M_SPLITS.inc()
        if self.collect_stats and self._frames:
            n = b.num_rows
            if self.analyze:
                with self._host_read("split_rows"):
                    n = b.num_rows_host()
            self._frames[-1]["rows"].append(n)
            self._frames[-1]["bytes"] += sum(
                _col_bytes(c) for c in b.columns.values())
        events = getattr(self.session, "events", None)
        if events is not None:
            from ..server.events import SplitCompletedEvent
            h = split.handle
            events.split_completed(SplitCompletedEvent(
                getattr(self.session, "query_id", "") or "",
                f"{h.catalog}.{h.schema}.{h.table}"
                f"[{split.part}/{split.part_count}]", wall))
        yld = getattr(self.session, "split_yield", None)
        if yld is not None:
            # a completed split IS the scheduler quantum (exec/
            # taskexec.py): account it and maybe hand the runner slot
            # to a higher-priority query's task before the next split
            yld()
        return b

    def _execute_inner(self, node: PlanNode) -> Batch:
        if isinstance(node, (FilterNode, ProjectNode)):
            # beyond-HBM morsel streaming (exec/streamjoin.py): a
            # Filter/Project chain over a scan whose materialization
            # estimate exceeds the memory budget streams fixed-capacity
            # chunks through the (one) compiled chain program instead
            # of raising the memory error
            from .streamjoin import maybe_stream_chain
            streamed = maybe_stream_chain(self, node)
            if streamed is not None:
                return streamed
        if isinstance(node, AggregationNode):
            dense = self._try_dense_aggregation(node)
            if dense is not None:
                return dense
            streamed = self._try_streaming_aggregation(node)
            if streamed is not None:
                return streamed
            masked = self._try_masked_filter_aggregation(node)
            if masked is not None:
                return masked
        if self.fragment_jit and isinstance(node, _TRACEABLE):
            chain = []
            cur = node
            # aggregations are a chain BARRIER, not a link: executing
            # them through _execute_inner gives them their own fused
            # program with selection-vector filter->aggregate fusion
            # (no 8M-row compaction gather) + the whole-table fast path;
            # the chain above jits over the small aggregated output
            while isinstance(cur, _TRACEABLE) \
                    and not isinstance(cur, AggregationNode):
                chain.append(cur)
                cur = cur.source
            if chain:
                # canonical program key (exec/progkey.py): renamed
                # symbols and reordered columns land on ONE cached
                # program; plans outside the canonical subset keep
                # per-query identity keys
                from .progkey import canonicalize_nodes
                base, chain = self._counted_base(cur, chain)
                if not chain:
                    return base
                canon = canonicalize_nodes(chain)
                key = canon.key if canon is not None \
                    else tuple(id(n) for n in chain)
                if key not in self._no_jit_chains:
                    try:
                        out = self._run_chain_jit(key, chain, base,
                                                  canon)
                        if out is not None:
                            return out
                    except UNTRACEABLE:
                        # chain touches host-only paths (row-
                        # materializing string fns); run it eagerly
                        # from here on
                        self._no_jit_chains.add(key)
                        if canon is not None:
                            PROGRAMS.deny("chain", key)
                b = base
                for nd in reversed(chain):
                    b = self._dispatch_apply(nd, b)
                return b
        method = getattr(self, "_exec_" + type(node).__name__, None)
        if method is None:
            raise QueryError(
                f"no executor for plan node {type(node).__name__}")
        try:
            return method(node)
        except EvalError as e:
            raise QueryError(str(e)) from e

    # ------------------------------------------------------------------
    # streaming aggregation over scan splits (grouped execution analog:
    # execution/Lifespan.java + SpillableHashAggregationBuilder — bound
    # memory by aggregating split-by-split with one compiled program,
    # then combining partials)
    # ------------------------------------------------------------------
    _STREAM_CHAIN = None   # set after class body

    # ------------------------------------------------------------------
    # capacity that follows the live rows: a Filter (a HAVING, a semi
    # join's mark) over millions of lanes that keeps a few hundred rows
    # is evaluated as a MASK, counted, and compacted ONCE at the
    # capacity of what is left — so that what runs above it (a join's
    # build-side sort, ops/join.py build_side) runs at 2^10 lanes, not
    # at the table's 2^24 or the aggregation's 2^26
    # ------------------------------------------------------------------
    def _counted_base(self, cur: PlanNode, chain: List[PlanNode]):
        """``(base, rest)`` for a traceable chain (top-down) over
        ``cur``: the executed source and the nodes still to run over
        it. The chain's lowest Filter/Project run is taken off and run
        counted where (a) ``cur`` is an aggregation that takes the
        dense form (the HAVING filters the group slots BEFORE their one
        compaction: ``_try_dense_aggregation``), or (b) it holds a
        Filter and the source has ``COUNTED_FILTER_MIN_LANES`` lanes or
        more. Else nothing changes: ``(execute(cur), chain)``."""
        low = len(chain)
        while low and isinstance(chain[low - 1],
                                 (FilterNode, ProjectNode)):
            low -= 1
        bottom = chain[low:]
        if isinstance(cur, AggregationNode):
            def dense():
                return self._try_dense_aggregation(cur, bottom)
            # the aggregation's statistics then hold what the HAVING
            # left of its groups: the two ran as one
            out = (self._stats_wrap(cur, dense) if self.collect_stats
                   else dense())
            if out is not None:
                return out, chain[:low]
        base = self.execute(cur)
        if base.capacity >= COUNTED_FILTER_MIN_LANES and any(
                isinstance(n, FilterNode) for n in bottom):
            out = self._counted_filter(bottom, base)
            if out is not None:
                return out, chain[:low]
        return base, chain

    def _counted_filter(self, nodes: List[PlanNode],
                        base: Batch) -> Optional[Batch]:
        """A Filter/Project run (top-down) over ``base`` in two
        programs around ONE read: the run as a selection vector (its
        columns at the input's capacity, the mask, the count), then the
        compaction at ``capacity_for(count)``. None where the run
        cannot be keyed or traced: the caller compacts as before."""
        from .progkey import canonicalize_nodes
        canon = canonicalize_nodes(nodes)
        if canon is None:
            return None
        key = (canon.key, "mask")
        got = PROGRAMS.program(
            "chain", key,
            lambda: make_mask_program(self._detached(), canon.nodes),
            "chain_mask", key)
        if got is None:
            return None
        jitted, hit = got
        binding = canon.binding(base)
        try:
            cols, live, n = self._jit_call(
                jitted, (binding.rename_in(base),), "chain", hit)
        except UNTRACEABLE:
            PROGRAMS.deny("chain", key)
            return None
        return binding.rename_out(self._compact_counted(cols, live, n))

    def _compact_counted(self, cols: Batch, live, n) -> Batch:
        """The rows of ``cols`` where ``live`` is set, in a batch whose
        capacity follows their number ``n`` (a device scalar: read
        here, site ``filter_rows``)."""
        from .streamjoin import _lane_spec
        with self._host_read("filter_rows") as sp:
            rows = int(n)
            if sp is not None:
                sp.attrs["rows"] = rows
                sp.attrs["lanes"] = cols.capacity
        out_cap = capacity_for(rows)
        key = ("compact", _lane_spec(cols), cols.capacity, out_cap)
        jitted, hit = PROGRAMS.program(
            "chain", key, lambda: make_compact_program(out_cap),
            "compact", key)
        return self._jit_call(jitted, (cols, live), "chain", hit)

    # ------------------------------------------------------------------
    # the dense aggregation (ops/groupby.py): GROUP BY one integer key
    # of a resident table whose values span less than the slots, with
    # the HAVING above it filtering the slots before the one compaction
    # ------------------------------------------------------------------
    _DENSE_PLAN_KINDS = {"sum", "count", "count_star", "min", "max",
                         "avg"}

    @staticmethod
    def _scan_column(sym: str, chain, scan: TableScanNode):
        """The scan column that ``sym`` (an output of the chain over
        ``scan``) is a plain copy of, or None."""
        for nd in chain:                # top-down
            if isinstance(nd, ProjectNode):
                e = nd.assignments.get(sym)
                if not isinstance(e, InputRef):
                    return None
                sym = e.name
        return scan.assignments.get(sym)

    def _try_dense_aggregation(self, node: AggregationNode,
                               above=()) -> Optional[Batch]:
        """``node`` (and the Filter/Project run ``above`` it, top-down)
        over a chain over a resident table, in the dense form, or None
        where that form is not the one to take. What decides, all of it
        observable: ONE group key that is a plain copy of an integer
        scan column, aggregates a scatter computes, the table resident
        as one batch, and the key's range: one counted read (site
        ``groupby_key_range``) of its least and greatest value, which
        have to span less than ``dense_slots(capacity)``. Then ONE
        program makes the group slots, applies ``above`` to them as a
        selection vector and counts; the slots that are left are
        compacted at the capacity of their number
        (``_compact_counted``). No sort, no ``nonzero``."""
        if not self.fragment_jit or self.scan_partition is not None \
                or id(node) in self._not_dense \
                or len(node.group_keys) != 1 or any(
                    a.distinct or a.kind not in self._DENSE_PLAN_KINDS
                    for a in node.aggregates.values()):
            return None
        # a node that declines is not asked again with another
        # ``above`` (its read would be made twice)
        self._not_dense.add(id(node))
        chain: List[PlanNode] = []
        cur = node.source
        while isinstance(cur, self._STREAM_CHAIN):
            chain.append(cur)
            cur = cur.source
        if not isinstance(cur, TableScanNode):
            return None
        key_col = self._scan_column(node.group_keys[0], chain, cur)
        from .streamjoin import agg_chunk_capacity
        if key_col is None or agg_chunk_capacity(self, cur) is not None:
            return None
        from .progkey import canonicalize_nodes
        n_above = len(above)
        canon = canonicalize_nodes(list(above) + [node] + chain)
        if canon is None:
            return None
        key = (canon.key, "dense", n_above)
        conn = self.catalogs.connector(cur.handle.catalog)
        par = int(self.session.get("task_concurrency")) or 1
        whole = read_table_cached(
            conn, cur.handle, sorted(set(cur.assignments.values())), par)
        if whole is None:
            return None
        kcol = whole.column(key_col)
        if not dense_key_lane(kcol):
            return None
        # the counted read: a superset of the live keys (the chain's
        # filters have not run), so a range that fits holds them all
        rkey = ("key_range", str(kcol.data.dtype), whole.capacity,
                kcol.valid is not None)
        ranger, rhit = PROGRAMS.program(
            "stream", rkey,
            lambda: (lambda b: dense_key_range(b, ["k"])),
            "key_range", rkey)
        key_range = self._jit_call(
            ranger, (Batch({"k": kcol}, whole.num_rows),), "stream", rhit)
        with self._host_read("groupby_key_range") as sp:
            keys = dense_keys(np.asarray(key_range), whole.capacity)
            if sp is not None:
                sp.attrs["fits"] = int(keys is not None)
                if keys is not None:
                    sp.attrs["ascending"] = int(keys.ascending)
                    sp.attrs["run"] = keys.run
        if keys is None:
            return None
        # whether the keys ascend, and in how short runs, is static:
        # a program each (its scatters promise sorted indices, or it
        # adds a run up row by row), so part of the key
        key += (keys.ascending, keys.run)
        if PROGRAMS.denied("stream", key):
            return None
        helper = self._detached()
        got = PROGRAMS.program(
            "stream", key,
            lambda: make_dense_program(
                helper, canon.nodes[n_above + 1:], canon.nodes[n_above],
                canon.nodes[:n_above], keys.ascending, keys.run),
            "stream_dense", key)
        if got is None:
            return None
        jitted, hit = got
        batch = Batch({sym: whole.column(col)
                       for sym, col in cur.assignments.items()},
                      whole.num_rows)
        binding = canon.binding(batch)
        try:
            slots, live, n = self._jit_call(
                jitted, (binding.rename_in(batch), keys.base),
                "stream", hit)
        except (_NotDense,) + UNTRACEABLE:
            PROGRAMS.deny("stream", key)
            return None
        self._not_dense.discard(id(node))
        return binding.rename_out(self._compact_counted(slots, live, n))

    _NONSTREAMABLE = {"min_by", "max_by", "approx_distinct",
                      "approx_percentile", "array_agg", "map_agg",
                      "histogram", "approx_most_frequent",
                      "approx_set", "merge", "map_union", "multimap_agg",
                      "numeric_histogram", "tdigest_agg", "qdigest_agg"}

    def _try_streaming_aggregation(self, node: AggregationNode):
        # kinds whose partials don't combine with a single-lane segment
        # op need all rows at once — no split-streaming for them
        if any(a.distinct or a.kind in self._NONSTREAMABLE
               for a in node.aggregates.values()):
            return None
        chain = []
        cur = node.source
        while isinstance(cur, self._STREAM_CHAIN):
            chain.append(cur)
            cur = cur.source
        if not isinstance(cur, TableScanNode):
            return None
        conn = self.catalogs.connector(cur.handle.catalog)
        par = int(self.session.get("task_concurrency")) or 1
        columns = sorted(set(cur.assignments.values()))
        # beyond-HBM chunking (exec/streamjoin.py): when the scan's
        # materialization estimate exceeds the memory budget (or
        # stream_chunk_rows forces it), split batches are further cut
        # into fixed-capacity chunks streamed through double-buffered
        # transfers, with periodic partial folding so the accumulated
        # partial set stays bounded too
        from .streamjoin import agg_chunk_capacity
        stream_cap = agg_chunk_capacity(self, cur)
        # whole-table fast path: when the table is (or fits) HBM-
        # resident, the filter->project->aggregate chain runs as ONE
        # device program over all rows — the hand-fused micro's shape —
        # instead of one dispatch per split
        whole = (None if self.scan_partition is not None
                 or stream_cap is not None
                 else read_table_cached(conn, cur.handle, columns, par))
        raws: Optional[List[Batch]] = None
        if whole is not None:
            raws = [whole]
        elif stream_cap is None:
            # the chunked branch never reads this split list —
            # host_scan_chunks enumerates (and share-filters) its own,
            # and an empty share simply yields zero partials below
            splits = conn.get_splits(cur.handle, par)
            if self.scan_partition is not None:
                part, nparts = self.scan_partition
                splits = [s for i, s in enumerate(splits)
                          if i % nparts == part]
                if not splits:
                    return None    # generic path emits the empty batch
            if len(splits) < 2 and self.scan_partition is None:
                return None
        partials: List[Batch] = []
        phys = post = None
        helper = self._detached()   # closures below are cached

        # canonical program (exec/progkey.py): under fragment_jit the
        # closures execute the CANONICAL node stack — renamed symbols /
        # reordered columns across queries land on one cached program
        # and one persistent-cache entry — with the input batches
        # renamed through the plan's binding and the output renamed
        # back once at the end. Plans outside the canonical subset
        # keep the original nodes and a per-execution program.
        canon = binding = None
        node_x, chain_x = node, chain
        if self.fragment_jit:
            from .progkey import canonicalize_nodes
            canon = canonicalize_nodes([node] + chain)
            if canon is not None:
                node_x, chain_x = canon.nodes[0], canon.nodes[1:]
        fkey = canon.key if canon is not None else None

        run, run_full = make_stream_runners(helper, chain_x, node_x)

        def bind(b: Batch) -> Batch:
            nonlocal binding
            if canon is None:
                return b
            if binding is None:
                binding = canon.binding(b)
            return binding.rename_in(b)

        def unbind(b: Batch) -> Batch:
            return b if binding is None else binding.rename_out(b)

        if raws is not None and len(raws) == 1 and self.fragment_jit:
            fullkey = None if fkey is None else (fkey, "full")
            got = PROGRAMS.program("stream", fullkey, lambda: run_full,
                                   "stream_full", fkey)
            if got is not None:
                full_jit, full_hit = got
                batch = bind(Batch(
                    {sym: raws[0].column(col)
                     for sym, col in cur.assignments.items()},
                    raws[0].num_rows))
                if fullkey is not None:
                    from .hotshapes import record_program
                    record_program("stream_full", fullkey, canon,
                                   batch, self.session)
                try:
                    return unbind(self._jit_call(
                        full_jit, (batch,), "stream", full_hit))
                except UNTRACEABLE:
                    if fullkey is not None:
                        PROGRAMS.deny("stream", fullkey)

        # one jitted program serves every split (uniform capacities);
        # the program is cached across QUERIES by canonical program
        # key so a repeated query skips re-trace + executable reload
        # (cost on the chip: not measured)
        run_jit = None
        jit_hit = False
        recorded = False
        if self.fragment_jit:
            got = PROGRAMS.program("stream", fkey, lambda: run,
                                   "stream", fkey)
            if got is not None:
                run_jit, jit_hit = got

        def consume(batch: Batch) -> Batch:
            nonlocal phys, post, recorded, run_jit, jit_hit
            batch = bind(batch)
            if fkey is not None and not recorded \
                    and not PROGRAMS.denied("stream", fkey):
                # deny-listed programs must not climb the pre-warm
                # ranking: every joining worker would burn a top-K
                # slot AOT-compiling a shape that cannot trace
                from .hotshapes import record_program
                record_program("stream", fkey, canon, batch,
                               self.session)
                recorded = True
            if phys is None:
                phys, post, _ = _lower_aggregates(node_x.aggregates,
                                                  batch)
            if run_jit is not None:
                try:
                    out = self._jit_call(run_jit, (batch,), "stream",
                                         jit_hit)
                    jit_hit = True   # later splits reuse the program
                except UNTRACEABLE:
                    run_jit = None
                    if fkey is not None:
                        PROGRAMS.deny("stream", fkey)
                    out = call_bound(run, batch)
            else:
                out = call_bound(run, batch)
            return out

        from ..ops.groupby import COMBINABLE_KINDS

        def make_finals():
            return [AggInput(COMBINABLE_KINDS[a.kind], a.output, None,
                             a.output) for a in phys]

        if stream_cap is not None:
            from .streamjoin import (_row_bytes, host_scan_chunks,
                                     run_streamed)
            # streamed peak: 2 in-flight chunk buffers + the bounded
            # partial set the fold keeps (<= 8 chunk-capacity partials)
            self._reserve_streamed(
                10 * stream_cap * _row_bytes(cur.schema),
                f"chunk-streamed aggregation over {cur.handle.table} "
                f"(chunk capacity {stream_cap})")

            def fold() -> None:
                # re-combine the accumulated partials into one batch
                # (combine kinds are idempotent under re-combination:
                # sum/min/max/any) so memory stays bounded by the
                # fold window, not the chunk count
                nonlocal partials
                m = device_concat(partials)
                fin = make_finals()
                if node_x.group_keys:
                    g = group_aggregate(m, list(node_x.group_keys),
                                        fin)
                else:
                    g = _pad_partial(global_aggregate(m, fin))
                partials = [g]

            def collect(out: Batch, i: int) -> None:
                partials.append(out)
                if len(partials) >= 8:
                    fold()

            run_streamed(self, "agg",
                         host_scan_chunks(self, cur, stream_cap),
                         lambda chunk, i: consume(chunk), collect)
            if not partials:
                return None    # empty scan: generic path emits empty
        else:
            for raw in (raws if raws is not None else
                        (self._read_split(conn, sp, columns)
                         for sp in splits)):
                partials.append(consume(Batch(
                    {sym: raw.column(col)
                     for sym, col in cur.assignments.items()},
                    raw.num_rows)))
        merged = device_concat(partials)
        finals = make_finals()
        if node_x.group_keys:
            out = group_aggregate(merged, list(node_x.group_keys),
                                  finals)
        else:
            out = global_aggregate(merged, finals)
        return unbind(_with_post(out, post, node_x))

    # ------------------------------------------------------------------
    # masked (selection-vector) filter -> aggregation fusion: filters
    # below an aggregation become a liveness mask consumed directly by
    # the aggregation kernels instead of a nonzero+gather compaction
    # (reference keeps selected-positions arrays inside PageProcessor for
    # the same reason — operator/project/PageProcessor.java; on TPU the
    # compaction gather costs seconds at SF1 row counts, the mask is
    # free)
    # ------------------------------------------------------------------
    def _masked_chain_eval(self, chain, b: Batch):
        """Evaluate a Filter/Project/Sample chain over ``b`` WITHOUT
        compacting: returns (columns, live-mask). Dead rows compute
        garbage values that the downstream mask consumer ignores."""
        live = b.row_valid()
        cols = dict(b.columns)
        cap = b.capacity
        for nd in reversed(chain):
            # num_rows=cap -> row_valid() is all-true inside expression
            # eval; the real liveness is tracked in `live`
            bb = Batch(cols, cap)
            if isinstance(nd, FilterNode):
                live = live & eval_predicate(nd.predicate, bb)
            elif isinstance(nd, SampleNode):
                from ..ops.hashing import mix64
                h = mix64(jnp.arange(cap, dtype=jnp.uint64))
                u = (h >> jnp.uint64(11)).astype(jnp.float64) \
                    / float(1 << 53)
                live = live & (u < nd.ratio)
            else:
                cols = {s: eval_expr(e, bb)
                        for s, e in nd.assignments.items()}
        return cols, live

    def _try_masked_filter_aggregation(self, node: AggregationNode):
        chain: List[PlanNode] = []
        cur = node.source
        while isinstance(cur, (FilterNode, ProjectNode, SampleNode)):
            chain.append(cur)
            cur = cur.source
        if not any(isinstance(n, (FilterNode, SampleNode))
                   for n in chain):
            return None
        base = self.execute(cur)

        def run(b: Batch) -> Batch:
            cols, live = self._masked_chain_eval(chain, b)
            nlive = jnp.sum(live.astype(jnp.int64))
            src = Batch(cols, nlive)
            phys, post, extra_cols = _lower_aggregates(
                node.aggregates, src)
            if extra_cols:
                c2 = dict(src.columns)
                c2.update(extra_cols)
                src = Batch(c2, nlive)
            if node.group_keys:
                out = group_aggregate(src, list(node.group_keys), phys,
                                      live=live)
            elif phys:
                out = global_aggregate(src, phys, live=live)
            else:
                return _single_row(src)
            return _with_post(out, post, node)

        if not self.fragment_jit:
            try:
                return run(base)
            except EvalError as e:
                raise QueryError(str(e)) from e
        key = ("masked", id(node))
        if key in self._no_jit_chains:
            return run(base)
        jitted = self._jit_chains.get(key)
        hit = jitted is not None
        _M_JIT.inc(cache="masked", result="hit" if hit else "miss")
        if jitted is None:
            # keyed by id(node): a per-query program, named without
            # a key (program names come from canonical keys only)
            jitted = named_jit(run, "masked", None)
            self._jit_chains[key] = jitted
        try:
            return self._jit_call(jitted, (base,), "masked", hit)
        except UNTRACEABLE:
            # host-materializing expressions in the chain: run eagerly
            self._no_jit_chains.add(key)
            return run(base)
        except EvalError as e:
            raise QueryError(str(e)) from e

    def _dispatch_apply(self, node: PlanNode, src: Batch) -> Batch:
        try:
            return getattr(self, "_apply_" + type(node).__name__)(
                node, src)
        except EvalError as e:
            raise QueryError(str(e)) from e

    def _chain_program(self, canon):
        """``(jitted, hit)`` of a canonical chain's program in the
        cross-query cache, None where its key is denied. The program
        executes the CANONICAL node stack (callers rename columns in
        and out through the plan's binding, exec/progkey.py): the
        traced jaxpr is identical across renamed plans, so jax's
        persistent compilation cache is effectively keyed on the
        canonical program too."""
        return PROGRAMS.program(
            "chain", canon.key,
            lambda: make_chain_program(self._detached(), canon.nodes),
            "chain", canon.key)

    def _run_chain_jit(self, key, chain, base: Batch,
                       canon=None) -> Optional[Batch]:
        """Run a traceable chain as one jitted program; None where the
        canonical program is denied. Canonical programs are shared
        ACROSS queries; a chain outside the canonical subset keeps its
        program per executor under its identity key (it cannot outlive
        its plan objects safely)."""
        if canon is None:
            jitted = self._jit_chains.get(key)
            hit = jitted is not None
            _M_JIT.inc(cache="chain", result="hit" if hit else "miss")
            if jitted is None:
                jitted = self._jit_chains[key] = named_jit(
                    make_chain_program(self, chain), "chain", None)
            return self._jit_call(jitted, (base,), "chain", hit)
        got = self._chain_program(canon)
        if got is None:
            return None
        jitted, hit = got
        binding = canon.binding(base)
        cb = binding.rename_in(base)
        from .hotshapes import record_program
        # record the SOLO canonical program: the hot shape the
        # fleet pre-warms is the chain itself, not the ragged
        # variant (whose capacity depends on who co-arrives)
        record_program("chain", key, canon, cb, self.session)
        out = self._try_ragged_chain(key, canon, cb)
        if out is None:
            out = self._jit_call(jitted, (cb,), "chain", hit)
        return binding.rename_out(out)

    # ------------------------------------------------------------------
    # ragged multi-query batching (tentpole, ISSUE 18): compatible
    # small canonical fragments from CONCURRENT queries coalesce into
    # one combined batch run by a single compiled program, with a
    # per-row provenance lane (__rq) demuxing result rows back to each
    # owning query. Telemetry stays per-query: each participant records
    # its own ragged_batch trace span and bumps its own counter; the
    # leader's executor carries the batch's device seconds and memory
    # reservation (an over-budget batch fails formation for everyone,
    # who then run solo under their own budgets).
    # ------------------------------------------------------------------
    def _try_ragged_chain(self, key: tuple, canon, cb: Batch
                          ) -> Optional[Batch]:
        """Offer a canonical chain dispatch for co-batching. Returns
        this query's demuxed output (canonical names — the caller's
        binding renames out), or None to run solo."""
        session = self.session
        try:
            if not bool(session.get("ragged_batching")):
                return None
            max_rows = int(session.get("ragged_batch_max_rows")) \
                or CONFIG.ragged_batch_rows
        except (KeyError, TypeError, ValueError):
            return None
        # only pure Filter/Project chains batch: Limit/Sort/TopN/
        # Sample/MarkDistinct have per-query cross-row semantics that
        # break under concatenation
        if not canon.nodes or not all(
                isinstance(nd, (FilterNode, ProjectNode))
                for nd in canon.nodes):
            return None
        n = cb.num_rows
        if not isinstance(n, int):
            return None     # device-resident count: syncing to form
            #                 a batch would stall the async pipeline
        # leave room for at least one batch-mate
        if n <= 0 or n * 2 > max_rows:
            return None
        if any(c.elements is not None or c.children is not None
               for c in cb.columns.values()):
            return None     # array/map/row lanes: concat delegates to
            #                 host-side complex merge — not worth it
        # compatibility signature: canonical program + column layout
        # (same canonical key from DIFFERENT tables can carry different
        # types) + catalog (one connector per batch)
        sig = (key, canon.literal_key, session.catalog,
               tuple((name, repr(c.type))
                     for name, c in cb.columns.items()))
        from .taskexec import ragged_batcher

        def run_group(items):
            return self._run_ragged_group(key, canon, items)

        t0 = time.perf_counter()
        ok, out = ragged_batcher().submit(
            sig, n, cb, run_group,
            wait=getattr(session, "slot_wait", None),
            max_rows=max_rows)
        if not ok:
            return None
        self.ragged_batched += 1
        tr = self.trace
        if tr is not None:
            tr.record("ragged_batch", t0, time.perf_counter(),
                      rows=n)
        return out

    def _run_ragged_group(self, key: tuple, canon,
                          items: List[Batch]) -> List[Batch]:
        """Leader-side group execution: combine members' canonical
        batches (+ provenance lane), run ONE compiled ragged program,
        demux rows back per member by lane value."""
        import numpy as np
        from ..columnar import Column, concat_batches
        from ..types import BIGINT
        from .progkey import RAGGED_LANE, ragged_nodes
        with self._host_read("ragged_rows"):
            ns = [b.num_rows_host() for b in items]
        total = sum(ns)
        combined = concat_batches(items)
        cap = combined.capacity
        # reserve-before-allocate on the LEADER (the thread that
        # executes): a batch the leader's query cannot afford fails
        # formation — every member then runs solo under its own budget
        self._reserve(cap, len(combined.columns) + 1, "ragged batch")
        lane = np.concatenate([
            np.repeat(np.arange(len(items), dtype=np.int64),
                      np.asarray(ns, dtype=np.int64)),
            # padding rows carry the sentinel len(items): no member's
            # demux selector can ever match them
            np.full(cap - total, len(items), dtype=np.int64)])
        ragged = Batch(
            {**combined.columns,
             RAGGED_LANE: Column(BIGINT, jnp.asarray(lane))}, total)
        if canon.slots:
            # co-batched queries bind the same literals (their signature
            # holds them); a varchar slot's code is looked up again in
            # the dictionary the concatenation merged
            from .literals import LiteralBinding
            lits = LiteralBinding([
                dc_replace(s, code_of=canon.mapping[s.code_of])
                if s.code_of is not None else s for s in canon.slots])
            ragged = lits.bind(ragged, ragged)
        jitted, hit = PROGRAMS.program(
            "ragged", ("ragged",) + tuple(key),
            lambda: make_chain_program(self._detached(),
                                       ragged_nodes(canon.nodes)),
            "ragged", key)
        out = self._jit_call(jitted, (ragged,), "ragged", hit)
        # demux: ONE host sync for the lane, then a per-member row
        # gather (the engine's own compaction primitive — dictionaries,
        # Int128 lanes and validity all route through Column.gather).
        # filter compaction is STABLE (mask_to_gather's nonzero is
        # ascending) and members' input rows are contiguous, so each
        # member's relative row order matches its solo run exactly.
        with self._host_read("ragged_demux"):
            n_out = out.num_rows_host()
            lane_out = np.asarray(
                jax.device_get(out.column(RAGGED_LANE).data))[:n_out]
        bare = Batch({k: c for k, c in out.columns.items()
                      if k != RAGGED_LANE}, out.num_rows)
        results = []
        for i in range(len(items)):
            sel = np.nonzero(lane_out == i)[0]
            k = len(sel)
            cap_i = capacity_for(k, minimum=8)
            idx = np.zeros(cap_i, dtype=np.int64)
            idx[:k] = sel
            results.append(bare.gather(jnp.asarray(idx), k))
        return results

    # ------------------------------------------------------------------
    # leaves
    # ------------------------------------------------------------------
    def _exec_TableScanNode(self, node: TableScanNode) -> Batch:
        conn = self.catalogs.connector(node.handle.catalog)
        columns = sorted(set(node.assignments.values()))
        par = int(self.session.get("task_concurrency")) or 1
        if self.scan_partition is not None:
            part, nparts = self.scan_partition
            splits = conn.get_splits(node.handle, par)
            mine = [s for i, s in enumerate(splits)
                    if i % nparts == part]
            if not mine:
                from ..columnar import batch_from_pylist
                return batch_from_pylist(
                    {s: [] for s in node.schema}, dict(node.schema))
            # reserve-before-allocate for the WORKER's split share too
            # (same discipline as the whole-table path below): an
            # oversized fragment fails with the actionable
            # EXCEEDED_LOCAL_MEMORY_LIMIT error instead of a raw HBM
            # OOM mid-concat
            if node.handle.constraint is None \
                    and node.handle.limit is None \
                    and hasattr(conn, "table_row_count"):
                total = conn.table_row_count(node.handle)
                if total:
                    share = -(-int(total) * len(mine) // len(splits))
                    self._reserve(share, len(columns),
                                  f"worker split share of "
                                  f"{node.handle.table} "
                                  f"(part {part}/{nparts})")
            batches = [self._read_split(conn, s, columns)
                       for s in mine]
            whole = (device_concat(batches) if len(batches) > 1
                     else batches[0])
            cols = {sym: whole.column(col)
                    for sym, col in node.assignments.items()}
            return Batch(cols, whole.num_rows)
        whole = read_table_cached(conn, node.handle, columns, par)
        if whole is None:
            # materializing the table for a downstream operator: check
            # the memory guard FIRST so an over-limit table fails with
            # the actionable EXCEEDED_LOCAL_MEMORY_LIMIT error instead
            # of exhausting HBM mid-concat (memory/MemoryPool.java's
            # reserve-before-allocate discipline)
            est = None
            if node.handle.constraint is None \
                    and node.handle.limit is None \
                    and hasattr(conn, "table_row_count"):
                # pushed-down constraints/limits shrink the result below
                # the table row count by an unknown factor — reserving
                # the full-table estimate would spuriously reject
                # selective scans (q6@sf100 keeps ~2% of rows)
                est = conn.table_row_count(node.handle)
            if est:
                self._reserve(int(est), len(columns),
                              f"table scan of {node.handle.table}")
            splits = conn.get_splits(node.handle, par)
            batches = [self._read_split(conn, s, columns)
                       for s in splits]
            whole = (device_concat(batches) if len(batches) > 1
                     else batches[0])
        cols = {sym: whole.column(col)
                for sym, col in node.assignments.items()}
        return Batch(cols, whole.num_rows)

    def _exec_ValuesNode(self, node: ValuesNode) -> Batch:
        data = {s: [row[i] for row in node.rows]
                for i, s in enumerate(node.schema)}
        return batch_from_pylist(data, dict(node.schema))

    # ------------------------------------------------------------------
    # row transforms
    # ------------------------------------------------------------------
    def _exec_FilterNode(self, node: FilterNode) -> Batch:
        return self._apply_FilterNode(node, self.execute(node.source))

    def _apply_FilterNode(self, node: FilterNode, src: Batch) -> Batch:
        mask = eval_predicate(node.predicate, src)
        return compact.filter_batch(src, mask)

    def _exec_ProjectNode(self, node: ProjectNode) -> Batch:
        return self._apply_ProjectNode(node, self.execute(node.source))

    def _apply_ProjectNode(self, node: ProjectNode, src: Batch) -> Batch:
        cols = {s: eval_expr(e, src)
                for s, e in node.assignments.items()}
        return Batch(cols, src.num_rows)

    def _exec_OutputNode(self, node: OutputNode) -> Batch:
        src = self.execute(node.source)
        return Batch({s: src.column(s) for s in node.symbols},
                     src.num_rows)

    def _exec_LimitNode(self, node: LimitNode) -> Batch:
        return self._apply_LimitNode(node, self.execute(node.source))

    def _apply_LimitNode(self, node: LimitNode, src: Batch) -> Batch:
        return compact.limit_batch(src, node.count)

    def _exec_OffsetNode(self, node: OffsetNode) -> Batch:
        return self._apply_OffsetNode(node, self.execute(node.source))

    def _apply_OffsetNode(self, node: OffsetNode, src: Batch) -> Batch:
        return compact.offset_batch(src, node.count)

    def _exec_SortNode(self, node: SortNode) -> Batch:
        return self._apply_SortNode(node, self.execute(node.source))

    def _apply_SortNode(self, node: SortNode, src: Batch) -> Batch:
        keys = [sort_ops.SortKey(k.symbol, k.ascending, k.nulls_first)
                for k in node.keys]
        return sort_ops.sort_batch(src, keys)

    def _exec_TopNNode(self, node: TopNNode) -> Batch:
        return self._apply_TopNNode(node, self.execute(node.source))

    def _apply_TopNNode(self, node: TopNNode, src: Batch) -> Batch:
        keys = [sort_ops.SortKey(k.symbol, k.ascending, k.nulls_first)
                for k in node.keys]
        return sort_ops.topn_batch(src, keys, node.count)

    def _exec_SampleNode(self, node: SampleNode) -> Batch:
        return self._apply_SampleNode(node, self.execute(node.source))

    def _apply_SampleNode(self, node: SampleNode, src: Batch) -> Batch:
        from ..ops.hashing import mix64
        h = mix64(jnp.arange(src.capacity, dtype=jnp.uint64))
        u = (h >> jnp.uint64(11)).astype(jnp.float64) / float(1 << 53)
        return compact.filter_batch(src, u < node.ratio)

    def _exec_AssignUniqueIdNode(self, node: AssignUniqueIdNode) -> Batch:
        return self._apply_AssignUniqueIdNode(
            node, self.execute(node.source))

    def _apply_AssignUniqueIdNode(self, node, src: Batch) -> Batch:
        cols = dict(src.columns)
        cols[node.symbol] = Column(
            BIGINT, jnp.arange(src.capacity, dtype=jnp.int64), None)
        return Batch(cols, src.num_rows)

    def _exec_EnforceSingleRowNode(self, node) -> Batch:
        src = self.execute(node.source)
        with self._host_read("single_row"):
            n = src.num_rows_host()
        if n > 1:
            raise QueryError(
                "Scalar sub-query has returned multiple rows")
        if n == 0:
            # one all-NULL row
            cols = {}
            for s, c in src.columns.items():
                cols[s] = dc_replace(
                    c, valid=jnp.zeros((c.capacity,), bool))
            return Batch(cols, 1)
        return src

    # ------------------------------------------------------------------
    # aggregation
    # ------------------------------------------------------------------
    def _exec_AggregationNode(self, node: AggregationNode) -> Batch:
        src = self.execute(node.source)
        if self.trace is None:
            return self._apply_AggregationNode(node, src)
        # an aggregation that no program took runs operation by
        # operation: its form is counted like a program's, site "eager"
        with noted_forms() as notes:
            out = self._apply_AggregationNode(node, src)
        for form, lanes in notes:
            _M_GROUPBYS.inc_at(("eager", form))
            _M_GROUPBY_LANES.inc_at(("eager", form), lanes)
        return out

    def _apply_AggregationNode(self, node: AggregationNode,
                               src: Batch) -> Batch:
        phys, post, extra_cols = _lower_aggregates(node.aggregates, src)
        if extra_cols:
            cols = dict(src.columns)
            cols.update(extra_cols)
            src = Batch(cols, src.num_rows)
        if node.group_keys:
            out = group_aggregate(src, list(node.group_keys), phys)
        else:
            out = global_aggregate(src, phys) if phys else \
                _single_row(src)
        return _with_post(out, post, node)

    def _exec_MarkDistinctNode(self, node: MarkDistinctNode) -> Batch:
        return self._apply_MarkDistinctNode(
            node, self.execute(node.source))

    def _apply_MarkDistinctNode(self, node: MarkDistinctNode,
                                src: Batch) -> Batch:
        from ..ops.groupby import _key_lanes
        lanes = _key_lanes(src, list(node.keys))
        order = jnp.lexsort(lanes[::-1])
        live_s = jnp.take(src.row_valid(), order)
        changed = jnp.zeros((src.capacity,), dtype=bool)
        for lane in lanes[1:]:
            s = jnp.take(lane, order)
            changed = changed | (s != jnp.roll(s, 1))
        first = jnp.arange(src.capacity) == 0
        boundary = (changed | first) & live_s
        marker = jnp.zeros((src.capacity,), bool).at[order].set(boundary)
        cols = dict(src.columns)
        cols[node.marker] = Column(BOOLEAN, marker, None)
        return Batch(cols, src.num_rows)

    def _exec_GroupIdNode(self, node) -> Batch:
        """plan/GroupIdNode.java: one copy of the input per grouping set;
        keys absent from a set become NULL; id column tags the set."""
        src = self.execute(node.source)
        copies = []
        for i, keys in enumerate(node.grouping_sets):
            keep = set(keys)
            cols = {}
            for s, c in src.columns.items():
                if s in node.all_keys and s not in keep:
                    cols[s] = dc_replace(
                        c, valid=jnp.zeros((c.capacity,), bool))
                else:
                    cols[s] = c
            cols[node.id_symbol] = Column(
                BIGINT, jnp.full((src.capacity,), i, jnp.int64), None)
            copies.append(Batch(cols, src.num_rows))
        return device_concat(copies)

    # ------------------------------------------------------------------
    def _exec_UnnestNode(self, node) -> Batch:
        """UNNEST: expand array rows into element rows (reference:
        operator/unnest/UnnestOperator.java). The expansion is the
        join output materialization's (ops/join.py run_positions) —
        per-row emit count = max array length, two-phase capacity."""
        src = self.execute(node.source)
        cap = src.capacity
        live = src.row_valid()
        arrs = {o: src.column(i) for o, i in node.unnest.items()}
        lens = {}
        for o, c in arrs.items():
            ln = jnp.asarray(c.data2).astype(jnp.int64)
            if c.valid is not None:
                ln = jnp.where(jnp.asarray(c.valid), ln, 0)
            lens[o] = ln
        count = None
        for ln in lens.values():
            count = ln if count is None else jnp.maximum(count, ln)
        count = jnp.where(live, count, 0)
        with self._host_read("unnest_total"):
            total = int(jnp.sum(count))
        out_cap = capacity_for(max(total, 1))
        self._reserve(out_cap, len(node.replicate) + len(arrs) + 1,
                      "unnest output")
        incl = jnp.cumsum(count)
        offs = incl - count
        i = jnp.arange(out_cap, dtype=jnp.int64)
        p = jnp.clip(join_ops.run_positions(incl, out_cap), 0, cap - 1)
        j = i - jnp.take(offs, p)
        cols: Dict[str, Column] = {}
        for s in node.replicate:
            cols[s] = src.column(s).gather(p)
        for o, c in arrs.items():
            el = c.elements
            ecap = int(jnp.asarray(el.data).shape[0])
            flat = jnp.take(jnp.asarray(c.data).astype(jnp.int64), p) + j
            flat = jnp.clip(flat, 0, ecap - 1)
            in_arr = j < jnp.take(lens[o], p)
            data = jnp.take(jnp.asarray(el.data), flat)
            valid = in_arr
            if el.valid is not None:
                valid = valid & jnp.take(jnp.asarray(el.valid), flat)
            d2 = (None if el.data2 is None
                  else jnp.take(jnp.asarray(el.data2), flat))
            cols[o] = Column(el.type, data, valid, el.dictionary, d2,
                             el.elements)
        if node.ordinality:
            cols[node.ordinality] = Column(BIGINT, j + 1, None)
        return Batch(cols, total)

    # ------------------------------------------------------------------
    # joins
    # ------------------------------------------------------------------
    @staticmethod
    def _mjoin_jittable(probe: Batch, build: Batch) -> bool:
        # nested ARRAY/MAP/ROW lanes keep the eager path (their AOT
        # payload cannot be rebuilt, and the win is in the flat TPC-H
        # lanes anyway)
        return not any(
            c.elements is not None or c.children is not None
            for c in list(probe.columns.values())
            + list(build.columns.values()))

    def _mjoin_counts(self, probe: Batch, build: Batch, pkeys, bkeys,
                      outer: bool):
        """Jitted count phase of the materialized join. Returns
        (start, count, order, [total, steps, exact, probe rows]) device
        arrays, or None on decline — the caller runs ops/join.py
        eagerly."""
        if not (self.fragment_jit
                and self._mjoin_jittable(probe, build)):
            return None
        from .streamjoin import _lane_spec
        key = mjoin_count_key(outer, pkeys, bkeys, _lane_spec(probe),
                              _lane_spec(build), probe.capacity,
                              build.capacity)
        got = PROGRAMS.program(
            "join", key,
            lambda: make_mjoin_count_program(pkeys, bkeys, outer),
            mjoin_kind(key), key)
        if got is None:     # denied: a prior trace hit host-only code
            return None
        jitted, hit = got
        try:
            return self._jit_call(jitted, (probe, build), "join", hit)
        except UNTRACEABLE:
            PROGRAMS.deny("join", key)
            return None

    def _read_join_total(self, tail) -> int:
        """The count program's one host read (``ops/join.py
        total_and_mode``, one row a shard on the mesh): its output
        total — the largest shard's — and beside it the steps its probe
        took, whether its directory was exact, whether a probe row
        read its bounds as one word and whether the bitmap directory
        served it, on every shard, and the join's shape: the probe
        side's live rows and the rows it puts out, summed over the
        shards (``steps``, ``exact``, ``packed``, ``bitmap``,
        ``probe_rows`` and ``total`` on the span, counted at /metrics:
        obs/metrics.py observe_span)."""
        with self._host_read("join_total") as sp:
            total, steps, exact, probe_rows, packed, bitmap = \
                np.asarray(tail).reshape(-1, 6).T
            if sp is not None:
                sp.attrs["steps"] = int(steps.max())
                sp.attrs["exact"] = int(exact.min())
                sp.attrs["packed"] = int(packed.min())
                sp.attrs["bitmap"] = int(bitmap.min())
                sp.attrs["probe_rows"] = int(probe_rows.sum())
                sp.attrs["total"] = int(total.sum())
        return int(total.max())

    def _expand(self, probe: Batch, build: Batch, start, count, order,
                jt: str, residual, out_cap: int, outputs,
                criteria=None) -> Batch:
        """A join's expand, handed only the lanes of ``probe`` and
        ``build`` that ``expand_lanes`` keeps: the jitted program where
        the count program ran (``criteria`` given), else eagerly. With a
        residual, ``jt`` is inner, and the candidates are filtered; the
        filter's own lanes leave in ``_repair_outer``."""
        out = None
        if criteria is not None:
            out = self._mjoin_expand(probe, build, start, count, order,
                                     jt, residual, out_cap, criteria,
                                     outputs)
        if out is None:
            lanes = expand_lanes(outputs, residual)
            out = join_ops.expand_join(narrow(probe, lanes),
                                       narrow(build, lanes), start, count,
                                       order, out_cap, jt)
            if residual is not None:
                out = compact.filter_batch(
                    out, eval_predicate(residual, out))
        return out

    def _mjoin_expand(self, probe: Batch, build: Batch, start, count,
                      order, jt: str, residual, out_cap: int,
                      criteria, outputs) -> Optional[Batch]:
        """Jitted expand phase (+ fused residual filter) over the lanes
        ``expand_lanes`` keeps of the two inputs. On first success the
        join's full two-program shape is recorded into the hot-shape
        registry (exec/hotshapes.py) so exec/aot.py can pre-compile
        BOTH phases into these same cache slots."""
        if not (self.fragment_jit
                and self._mjoin_jittable(probe, build)):
            return None
        from .streamjoin import _join_payload, _lane_spec
        pcols, bcols, kept = expand_columns(
            probe.columns, build.columns, expand_lanes(outputs, residual))
        p, b = Batch(pcols, probe.num_rows), Batch(bcols, build.num_rows)
        key = mjoin_expand_key(jt, repr(residual), _lane_spec(p),
                               _lane_spec(b), probe.capacity,
                               build.capacity, out_cap)
        got = PROGRAMS.program(
            "join", key,
            lambda: make_mjoin_expand_program(jt, residual, out_cap),
            mjoin_kind(key), key)
        if got is None:
            return None
        jitted, hit = got
        args = (p, b, jnp.asarray(start, jnp.int64),
                jnp.asarray(count, jnp.int64),
                jnp.asarray(order, jnp.int64))
        try:
            out = self._jit_call(
                jitted, args, "join", hit,
                form=join_ops.expand_form(probe.capacity, out_cap),
                lanes=kept)
        except UNTRACEABLE:
            PROGRAMS.deny("join", key)
            return None
        from .hotshapes import record_program

        def build_pl():
            return _join_payload(jt, criteria, residual, probe, build,
                                 out_cap, kind="join", outputs=outputs)
        # the registry key carries the join keys and both inputs' lanes
        # too: two joins sharing an expand program each need their own
        # count program compiled
        record_program(
            "join",
            ("mjoin", tuple(c.left for c in criteria),
             tuple(c.right for c in criteria), key, _lane_spec(probe),
             _lane_spec(build)),
            None, None, self.session, payload_fn=build_pl)
        return out

    def _exec_JoinNode(self, node: JoinNode) -> Batch:
        jt = node.join_type
        if jt == "right":
            flipped = JoinNode(node.right, node.left, "left",
                               tuple(join_ops and
                                     _flip_clause(c)
                                     for c in node.criteria),
                               node.filter, outputs=node.outputs)
            return self._exec_JoinNode(flipped)
        # beyond-HBM probe streaming (exec/streamjoin.py): when the
        # probe side is a scan chain whose working set exceeds the
        # memory budget, build the hash table once and stream probe
        # chunks through double-buffered host->device transfers
        # instead of materializing the probe (q18 at sf100: ~34GB of
        # lanes, more than one chip's HBM)
        from .streamjoin import maybe_stream_join
        streamed, pre_built = maybe_stream_join(self, node)
        if streamed is not None:
            return streamed
        left = self.execute(node.left)
        # a declined stream decision may have materialized the build
        # side already (the remaining-after-build check needs it):
        # reuse that batch instead of executing node.right twice
        right = (pre_built if pre_built is not None
                 else self.execute(node.right))
        outputs = node.outputs

        if jt == "cross" or not node.criteria:
            return self._cross_join(left, right, node.filter, jt, outputs)

        pkeys = [c.left for c in node.criteria]
        bkeys = [c.right for c in node.criteria]
        filt = join_verify_filter(left.columns, right.columns,
                                  pkeys, bkeys, node.filter)
        # the count program reads the keys of the whole inputs; the
        # expand is handed the lanes the plan above reads (and, with a
        # residual, the filter's inputs): ``_expand``
        if filt is None:
            outer = jt in ("left", "full")
            counted = self._mjoin_counts(left, right, pkeys, bkeys,
                                         outer)
            if counted is not None:
                start, count, order, tail = counted
                eff = None      # only the oversized path needs it
                total = self._read_join_total(tail)
            else:
                start, count, order = join_ops.match_counts(
                    left, right, pkeys, bkeys)
                live_p = left.row_valid()
                eff = jnp.where(live_p, jnp.maximum(count, 1), 0) \
                    if outer else count
                with self._host_read("join_total"):
                    total = int(jnp.sum(eff))
            width = _expand_width(left, right, expand_lanes(outputs))
            if total > CONFIG.max_batch_rows:
                if eff is None:
                    eff = jnp.where(left.row_valid(),
                                    jnp.maximum(count, 1), 0) \
                        if outer else count
                out = self._oversized_join(
                    left, right, start, count, eff, order, total,
                    width, "left" if outer else "inner", outputs=outputs)
            else:
                self._reserve(total, width, "join output")
                out = self._expand(
                    left, right, start, count, order,
                    "left" if outer else "inner", None,
                    capacity_for(total), outputs,
                    node.criteria if counted is not None else None)
            if jt == "full":
                out = self._append_right_unmatched(
                    out, left, right, pkeys, bkeys, outputs)
            return out
        # residual filter: expand as inner candidates with probe+build
        # position tracks, filter, then repair unmatched outer rows from
        # the *surviving* match sets (key-only counts are not enough —
        # a key match rejected by the filter must still null-extend)
        probe = self._with_pos(left, _PPOS) if jt in ("left", "full") \
            else left
        build = self._with_pos(right, _BPOS) if jt == "full" else right
        counted = self._mjoin_counts(probe, build, pkeys, bkeys, False)
        if counted is not None:
            start, count, order, tail = counted
            total = self._read_join_total(tail)
        else:
            start, count, order = join_ops.match_counts(
                probe, build, pkeys, bkeys)
            with self._host_read("join_total"):
                total = int(jnp.sum(count))
        width = _expand_width(probe, build, expand_lanes(outputs, filt))
        if total > CONFIG.max_batch_rows and jt == "inner":
            out = self._oversized_join(probe, build, start, count, count,
                                       order, total, width, "inner",
                                       residual=filt, outputs=outputs)
            return self._repair_outer(out, left, right, jt, outputs)
        self._reserve(total, width, "join candidates")
        out = self._expand(probe, build, start, count, order, "inner",
                           filt, capacity_for(total), outputs,
                           node.criteria if counted is not None else None)
        return self._repair_outer(out, left, right, jt, outputs)

    def _reserve(self, rows: int, n_lanes: int, what: str) -> None:
        limit = int(self.session.get("query_max_memory_per_node"))
        try:
            est = reserve_bytes(rows, n_lanes, limit, what)
        except MemoryLimitExceeded as e:
            raise QueryError(str(e)) from e
        self._account(est)

    def _reserve_streamed(self, nbytes: int, what: str) -> None:
        """Reserve a streamed operator's REAL footprint (build state +
        2 chunk buffers + 1 output chunk — exec/streamjoin.py), not
        the full-materialization estimate streaming exists to avoid.
        The cluster pool sees this figure too, so the low-memory
        killer judges streamed queries by what they actually hold."""
        limit = int(self.session.get("query_max_memory_per_node"))
        if nbytes > limit:
            raise QueryError(
                f"Query exceeded per-node memory limit of {limit} "
                f"bytes ({what} needs ~{nbytes} bytes even streamed); "
                "raise query_max_memory_per_node or lower "
                "stream_chunk_rows")
        self._account(int(nbytes))

    def _account(self, est: int) -> None:
        # largest single reservation = the query's peak-memory figure
        # reported in QueryCompletedEvent (capacity planning is the one
        # allocation decision point in this engine — config.py)
        self.peak_reserved_bytes = max(self.peak_reserved_bytes, est)
        mem = getattr(self.session, "memory", None)
        if mem is not None:
            # cluster memory governance (server/memory.py): the same
            # estimate feeds the coordinator's pool ledger; a per-query
            # cap breach or a low-memory kill of THIS query raises
            # here, in the reserving thread, with its Trino error name.
            # ONLY governance errors are rewrapped — an internal bug in
            # the manager must surface as an internal error, not
            # masquerade as a memory-limit breach
            from ..server.memory import MemoryGovernanceError
            try:
                mem.reserve(est)
            except MemoryGovernanceError as e:
                raise QueryError(str(e),
                                 error_name=e.error_name) from e

    def _oversized_join(self, probe: Batch, build: Batch, start, count,
                        eff, order, total: int, width: int,
                        jt: str, residual=None, outputs=None) -> Batch:
        """Join whose output exceeds the per-batch device budget:
        expand probe-row chunks device-side and accumulate the results
        in HOST memory (the spiller role — reference:
        operator/HashBuilderOperator.java:155-170 spill state machine /
        spiller/GenericPartitioningSpiller; on TPU the spill target is
        host RAM, the first rung of the HBM->host->disk ladder,
        SURVEY.md §5 checkpoint/resume). Requires spill_enabled, else
        the memory guard fires."""
        if not bool(self.session.get("spill_enabled")):
            self._reserve(total, width, "join output (spill disabled)")
        lanes = expand_lanes(outputs, residual)
        kprobe, kbuild = narrow(probe, lanes), narrow(build, lanes)
        with self._host_read("join_spill"):
            eff_np = np.asarray(eff)
            cum = np.cumsum(eff_np)
            budget = CONFIG.max_batch_rows
            n_live = probe.num_rows_host()
        chunks: List[Batch] = []
        lo = 0
        consumed = 0
        pcap = probe.capacity
        while lo < pcap and consumed < total:
            hi = int(np.searchsorted(cum, consumed + budget, "right"))
            hi = max(hi, lo + 1)
            chunk_rows = int(cum[hi - 1] - consumed)
            if chunk_rows == 0:
                lo = hi
                continue
            sel = jnp.arange(lo, hi, dtype=jnp.int64)
            # gathered rows are live iff their original position was in
            # the live prefix — gathered liveness is again a prefix
            sub_probe = kprobe.gather(sel, max(min(n_live, hi) - lo, 0))
            sub_start = jnp.take(jnp.asarray(start), sel)
            sub_count = jnp.take(jnp.asarray(count), sel)
            cap = capacity_for(max(chunk_rows, 1))
            out = join_ops.expand_join(
                sub_probe, kbuild, sub_start, sub_count, order, cap, jt)
            consumed += chunk_rows
            lo = hi
            if residual is not None:
                # filter each chunk on device BEFORE spilling so only
                # survivors reach host RAM
                mask = eval_predicate(residual, out)
                out = compact.filter_batch(out, mask)
                with self._host_read("join_spill"):
                    chunk_rows = out.num_rows_host()
                if chunk_rows == 0:
                    continue
            with self._host_read("join_spill"):
                spilled = _to_host(out, chunk_rows)
            nbytes = sum(_col_bytes(c) for c in spilled.columns.values())
            self.spilled_bytes += nbytes
            _M_SPILL.inc(nbytes)
            chunks.append(spilled)
        if not chunks:
            return _to_host(join_ops.expand_join(
                kprobe, kbuild, jnp.asarray(start),
                jnp.zeros_like(jnp.asarray(count)), order, 8, jt), 0)
        return _host_concat(chunks, sum(c.num_rows for c in chunks))

    def _cross_join(self, left: Batch, right: Batch, filt,
                    jt: str = "inner", outputs=None) -> Batch:
        """Cross / non-equi join (no equi criteria). For left/full outer
        variants, probe/build positions are tracked through the filter so
        unmatched rows null-extend (JoinNode with empty criteria in
        sql/planner/plan/JoinNode.java; NestedLoopJoinOperator.java)."""
        with self._host_read("cross_rows"):
            nl, nr = left.num_rows_host(), right.num_rows_host()
        total = nl * nr
        self._reserve(total, _expand_width(left, right,
                                           expand_lanes(outputs, filt)),
                      "cross join output")
        cap = capacity_for(max(total, 1))
        probe = self._with_pos(left, _PPOS) if jt in ("left", "full") \
            else left
        build = self._with_pos(right, _BPOS) if jt == "full" else right
        start, count, order = join_ops.cross_counts(probe, build)
        out = self._expand(probe, build, start, count, order, "inner",
                           filt, cap, outputs)
        return self._repair_outer(out, left, right, jt, outputs)

    def _with_pos(self, b: Batch, name: str) -> Batch:
        cols = dict(b.columns)
        cols[name] = Column(
            BIGINT, jnp.arange(b.capacity, dtype=jnp.int64), None)
        return Batch(cols, b.num_rows)

    def _repair_outer(self, out: Batch, left: Batch, right: Batch,
                      jt: str, outputs=None) -> Batch:
        """Strip position lanes and the lanes only the residual read
        (the join puts out ``outputs``); null-extend outer rows whose
        matches all died in the filter (surviving-match repair)."""
        live_out = out.row_valid()
        pp = (jnp.asarray(out.column(_PPOS).data)
              if jt in ("left", "full") else None)
        bb = (jnp.asarray(out.column(_BPOS).data)
              if jt == "full" else None)
        out = Batch({s: c for s, c in out.columns.items()
                     if s not in (_PPOS, _BPOS)
                     and (outputs is None or s in outputs)}, out.num_rows)
        if pp is not None:
            matched = jnp.zeros((left.capacity,), bool).at[
                jnp.where(live_out, pp, 0)].max(live_out)
            unmatched = left.row_valid() & ~matched
            out = device_concat(
                [out, self._null_extend(left, right, unmatched, outputs)])
        if bb is not None:
            matched_b = jnp.zeros((right.capacity,), bool).at[
                jnp.where(live_out, bb, 0)].max(live_out)
            unmatched_b = right.row_valid() & ~matched_b
            out = device_concat(
                [out, self._null_extend_right(left, right, unmatched_b,
                                              outputs)])
        return out

    def _null_lanes(self, side: Batch, capacity: int) -> Dict[str, Column]:
        """All-NULL columns of ``side``'s lanes at ``capacity``."""
        cols = {}
        for s, c in side.columns.items():
            with self._host_read("null_extend_dtype"):
                dt = np.asarray(c.data).dtype
            cols[s] = Column(c.type, jnp.zeros((capacity,), dtype=dt),
                             jnp.zeros((capacity,), bool), c.dictionary,
                             None if c.data2 is None else
                             jnp.zeros((capacity,), jnp.int64))
        return cols

    def _null_extend(self, left: Batch, right: Batch, row_mask,
                     outputs=None) -> Batch:
        """Rows of ``left`` where mask, with all-NULL right columns: the
        lanes of ``outputs`` (a side may put out none)."""
        idx, n = compact.mask_to_gather(row_mask & left.row_valid())
        cols = dict(narrow(left, outputs).gather(idx, n).columns)
        cols.update(self._null_lanes(narrow(right, outputs), idx.shape[0]))
        return Batch(cols, n)

    def _null_extend_right(self, left: Batch, right: Batch, row_mask,
                           outputs=None) -> Batch:
        """Rows of ``right`` where mask, with all-NULL left columns."""
        idx, n = compact.mask_to_gather(row_mask & right.row_valid())
        cols = self._null_lanes(narrow(left, outputs), idx.shape[0])
        cols.update(narrow(right, outputs).gather(idx, n).columns)
        return Batch(cols, n)

    def _append_right_unmatched(self, out: Batch, left: Batch,
                                right: Batch, pkeys, bkeys,
                                outputs=None) -> Batch:
        # FULL JOIN tail (no residual filter): right rows with no key
        # match, null-extended
        start, count, order = join_ops.match_counts(
            right, left, bkeys, pkeys)
        unmatched = right.row_valid() & (count == 0)
        pad = self._null_extend_right(left, right, unmatched, outputs)
        return device_concat([out, pad])

    def _exec_SemiJoinNode(self, node: SemiJoinNode) -> Batch:
        src = self.execute(node.source)
        filt = self.execute(node.filtering_source)
        data, valid = self._semi_join_mark(
            Batch({"k": src.column(node.source_key)}, src.num_rows),
            Batch({"k": filt.column(node.filtering_key)}, filt.num_rows))
        cols = dict(src.columns)
        cols[node.output] = Column(BOOLEAN, data, valid)
        return Batch(cols, src.num_rows)

    def _semi_join_mark(self, probe: Batch, build: Batch):
        """The mark of ``k IN (build's k)`` per probe row, (data,
        valid), as ONE cached program of the two key lanes (bucket
        ``join``, kind ``semi_join``: a ``dispatch`` span, a
        name in the trace); eagerly where fragments are not jitted or
        the keys cannot be traced."""
        if self.fragment_jit and self._mjoin_jittable(probe, build):
            from .streamjoin import _lane_spec
            key = ("semi_join", _lane_spec(probe), _lane_spec(build),
                   probe.capacity, build.capacity)
            got = PROGRAMS.program("join", key, lambda: semi_join_mark,
                                   "semi_join", key)
            if got is not None:
                try:
                    return self._jit_call(got[0], (probe, build), "join",
                                          got[1])
                except UNTRACEABLE:
                    PROGRAMS.deny("join", key)
        return semi_join_mark(probe, build)

    def _exec_SemiJoinMultiNode(self, node: SemiJoinMultiNode) -> Batch:
        src = self.execute(node.source)
        filt = self.execute(node.filtering_source)
        skeys = list(node.source_keys)
        fkeys = list(node.filtering_keys)
        residual = (join_verify_filter(src.columns, filt.columns,
                                       skeys, fkeys, node.filter)
                    if skeys else node.filter)
        if residual is None and skeys:
            matched, _, _, _ = join_ops.semi_join_mask(
                src, filt, skeys, fkeys)
            cols = dict(src.columns)
            cols[node.output] = Column(BOOLEAN, matched, None)
            return Batch(cols, src.num_rows)
        node = dc_replace(node, filter=residual)
        # residual filter path: expand candidate matches, filter, then
        # mark probe rows with surviving matches
        ppos = "__probe_pos$"
        scols = dict(src.columns)
        scols[ppos] = Column(BIGINT,
                             jnp.arange(src.capacity, dtype=jnp.int64),
                             None)
        probe = Batch(scols, src.num_rows)
        if skeys:
            start, count, order = join_ops.match_counts(
                probe, filt, skeys, fkeys)
        else:
            start, count, order = join_ops.cross_counts(probe, filt)
        with self._host_read("unnest_total"):
            with self._host_read("semijoin_total"):
                total = int(jnp.sum(count))
        cap = capacity_for(total)
        # the candidates carry what the filter reads and the position
        lanes = expand_lanes((), node.filter)
        cand = join_ops.expand_join(narrow(probe, lanes),
                                    narrow(filt, lanes), start, count,
                                    order, cap, "inner")
        if node.filter is not None:
            mask = eval_predicate(node.filter, cand)
        else:
            mask = cand.row_valid()
        pp = jnp.asarray(cand.column(ppos).data)
        live = cand.row_valid() & mask
        matched = jnp.zeros((src.capacity,), bool).at[
            jnp.where(live, pp, 0)].max(live)
        cols = dict(src.columns)
        cols[node.output] = Column(BOOLEAN, matched, None)
        return Batch(cols, src.num_rows)

    # ------------------------------------------------------------------
    # set operations
    # ------------------------------------------------------------------
    def _exec_UnionNode(self, node: UnionNode) -> Batch:
        parts = []
        for child, smap in zip(node.children, node.symbol_maps):
            b = self.execute(child)
            parts.append(Batch(
                {out: b.column(inner) for out, inner in smap.items()},
                b.num_rows))
        return device_concat(parts)

    def _exec_SetOpNode(self, node: SetOpNode) -> Batch:
        left = self.execute(node.left)
        right = self.execute(node.right)
        lb = Batch({o: left.column(i) for o, i in node.left_map.items()},
                   left.num_rows)
        rb = Batch({o: right.column(i)
                    for o, i in node.right_map.items()}, right.num_rows)
        return setop_batches(lb, rb, node.op, node.distinct,
                             list(node.schema))

    # ------------------------------------------------------------------
    # windows
    # ------------------------------------------------------------------
    def _exec_WindowNode(self, node: WindowNode) -> Batch:
        from .window import execute_window, window_traceable
        src = self.execute(node.source)
        if not (self.fragment_jit and window_traceable(node)):
            return execute_window(src, node)
        from .progkey import canonicalize_nodes
        canon = canonicalize_nodes([node])
        got = None if canon is None else PROGRAMS.program(
            "window", canon.key,
            lambda: make_window_program(canon.nodes[0]),
            "window", canon.key)
        if got is None:
            return execute_window(src, node)
        key = canon.key
        jitted, hit = got
        binding = canon.binding(src)
        cb = binding.rename_in(src)
        from .hotshapes import record_program
        record_program("window", key, canon, cb, self.session)
        try:
            out = self._jit_call(jitted, (cb,), "window", hit)
        except UNTRACEABLE:
            # a lane/function combination that materializes on host
            # despite the traceability gate: run eagerly ever after
            PROGRAMS.deny("window", key)
            return execute_window(src, node)
        return binding.rename_out(out)

    # ------------------------------------------------------------------
    def _exec_ExchangeNode(self, node: ExchangeNode) -> Batch:
        # single-process execution: exchanges are identity (M3 replaces
        # this with all_to_all / all_gather over the device mesh)
        return self.execute(node.source)

    def _exec_PartitionedOutputNode(self,
                                    node: PartitionedOutputNode) -> Batch:
        # the partitioning itself happens at the page boundary
        # (server/task_worker.py cuts the result into partition frames
        # with stage/repartition.py); executed directly — the
        # coordinator running a stage plan locally, a test harness —
        # the node is identity
        return self.execute(node.source)

    def _exec_RemoteSourceNode(self, node: RemoteSourceNode) -> Batch:
        """Reads this task's partition of every upstream stage task
        through the exchange hook (stage/exchange.py ExchangePuller).
        A pull failure is a retriable attempt failure — the stage
        scheduler re-dispatches the task, which re-pulls the committed
        upstream frames off the spool."""
        reader = self.exchange_reader
        if reader is None:
            raise QueryError(
                "RemoteSourceNode executed outside a stage exchange "
                "context (no exchange reader wired)")
        batches: List[Batch] = []
        for fid in node.fragment_ids:
            batches.extend(reader(int(fid)))
        if not batches:
            from ..columnar import empty_batch
            return empty_batch(node.schema)
        out = (device_concat(batches) if len(batches) > 1
               else batches[0])
        return out

    def _exec__Pre(self, node: "_Pre") -> Batch:
        return node.batch

    def _single_row(self, src: Batch) -> Batch:
        return _single_row(src)


def make_stream_parts(helper: "Executor", chain, node):
    """The two halves of a streamed aggregation over a chain +
    AggregationNode: ``partial(batch)`` (the chain as a selection
    vector, then the partial aggregation; returns the partial Batch
    with the lowered aggregates and their post-processing) and
    ``finish(partials, phys, post, live=None)`` (final combine +
    post-processing). One chip runs them back to back in ONE program
    (``make_stream_runners``); the mesh runs ``partial`` per shard,
    gathers the partial rows and runs ``finish`` on them
    (exec/distributed.py)."""

    def partial(b: Batch):
        # selection-vector execution: the filter chain becomes a
        # live mask consumed by the aggregation (no compaction).
        # Aggregates lower against the CHAIN OUTPUT columns
        # (projection-created symbols like checksum's arg live there,
        # not on the raw scan batch).
        cols, live = helper._masked_chain_eval(chain, b)
        src = Batch(cols, jnp.sum(live.astype(jnp.int64)))
        _p, _post, extra = _lower_aggregates(node.aggregates, src)
        if extra:
            c2 = dict(src.columns)
            c2.update(extra)
            src = Batch(c2, src.num_rows)
        if node.group_keys:
            out = group_aggregate(src, list(node.group_keys), _p,
                                  live=live)
        else:
            out = _pad_partial(global_aggregate(src, _p, live=live))
        return out, _p, _post

    def finish(out: Batch, _p, _post, live=None) -> Batch:
        from ..ops.groupby import COMBINABLE_KINDS
        fin = [AggInput(COMBINABLE_KINDS[a.kind], a.output, None,
                        a.output) for a in _p]
        if node.group_keys:
            out = group_aggregate(out, list(node.group_keys), fin,
                                  live=live)
        else:
            out = global_aggregate(out, fin, live=live)
        return _with_post(out, _post, node)

    return partial, finish


def semi_join_mark(probe: Batch, build: Batch):
    """``k IN (build's k)`` per probe row with SQL's three values,
    (data, valid): TRUE if matched; FALSE if the build side is empty;
    NULL if the probe key is NULL or the build side holds a NULL; else
    FALSE."""
    matched, key_null, build_null, nonempty = join_ops.semi_join_mask(
        probe, build, ["k"], ["k"])
    return matched, matched | ~nonempty | (~key_null & ~build_null)


def make_mask_program(helper: "Executor", nodes):
    """The program of a Filter/Project run (top-down) as a selection
    vector: ``(columns at the input's capacity, the mask of the rows
    that pass, their number)`` — the first half of a counted filter
    (``Executor._counted_filter``)."""

    def fn(b: Batch):
        cols, live = helper._masked_chain_eval(nodes, b)
        return (Batch(cols, b.capacity), live,
                jnp.sum(live.astype(jnp.int64)))

    return fn


def make_compact_program(out_cap: int):
    """The second half: the masked rows at ``out_cap`` lanes."""

    def fn(cols: Batch, live):
        return compact.compact_batch(cols, live, out_cap)

    return fn


def make_dense_program(helper: "Executor", chain, node, above,
                       ascending: bool, run: int):
    """The dense whole-table aggregation: the chain below ``node`` as a
    selection vector, the group slots of the ONE integer key
    (``ops/groupby.py dense_group_slots``, ``base`` its least value,
    ``ascending`` and ``run`` whether the table's keys ascend and in
    how short runs: the caller read all three: ``DenseKeys``), the
    aggregates' post-processing and the
    Filter/Project run ``above`` applied to the slots, again as a
    selection vector. Returns ``(the slots' columns, the mask of the
    groups that exist and pass, their number)``; the caller compacts
    at the capacity of that number."""
    from ..ops.groupby import (DenseKeys, dense_eligible,
                               dense_group_slots)

    def fn(b: Batch, base):
        cols, live = helper._masked_chain_eval(chain, b)
        src = Batch(cols, jnp.sum(live.astype(jnp.int64)))
        phys, post, extra = _lower_aggregates(node.aggregates, src)
        if extra:
            c2 = dict(src.columns)
            c2.update(extra)
            src = Batch(c2, src.num_rows)
        keys = list(node.group_keys)
        if not dense_eligible(src, keys, phys):
            raise _NotDense()
        note_form("dense", b.capacity)
        # the read spanned every row of the table: one the chain's
        # filters dropped keeps its slot and adds nothing there
        slots, exists = dense_group_slots(
            src, keys, phys, DenseKeys(base, ascending, run), live=live,
            spanned=b.row_valid())
        slots = _with_post(slots, post, node)
        out, passed = helper._masked_chain_eval(above, slots)
        passed = passed & exists
        return (Batch(out, slots.capacity), passed,
                jnp.sum(passed.astype(jnp.int64)))

    return fn


def make_chain_program(helper: "Executor", nodes):
    """The program of a traceable node chain (top-down order): every
    node applied bottom-up over one batch. Module-level, like the
    builders below, so the AOT compiler (exec/aot.py) rebuilds the
    EXACT closure the executor caches — a pre-warmed program and a
    live query trace the same jaxpr."""

    def fn(b: Batch) -> Batch:
        for nd in reversed(nodes):
            b = helper._dispatch_apply(nd, b)
        return b

    return fn


def make_window_program(wnode: WindowNode):
    """The program of one canonical WindowNode."""
    from .window import execute_window

    def fn(b: Batch) -> Batch:
        return execute_window(b, wnode)

    return fn


def make_stream_runners(helper: "Executor", chain, node):
    """Build the streaming-aggregation programs over a chain +
    AggregationNode: ``run`` (per-split partial aggregation) and
    ``run_full`` (whole-table partial + final combine + post-processing
    fused into ONE XLA computation — the shape of the hand-fused
    micro). Module-level so the AOT compiler (exec/aot.py) rebuilds
    the EXACT closures the executor caches — a pre-warmed program and
    a live query trace the same jaxpr."""
    partial, finish = make_stream_parts(helper, chain, node)

    def run(b: Batch) -> Batch:
        return partial(b)[0]

    def run_full(b: Batch) -> Batch:
        return finish(*partial(b))

    return run, run_full


# --------------------------------------------------------------------------
# materialized hash-join programs (the "join" AOT kind)
# --------------------------------------------------------------------------
# The eager join in _exec_JoinNode is already two-phase ("count, pick
# bucket, expand" — ops/join.py): the count phase is the only host
# sync, the expansion runs at a static capacity bucket. Each phase is
# therefore one traceable program; jitting them separately keeps the
# host-side total/bucket decision OUT of the traced code while every
# device op (lane hashing, directory probe, gather expansion, residual
# filtering) fuses. Builders are module-level so exec/aot.py rebuilds
# the EXACT closures the executor caches (progkey doctrine: one key
# per program, shared by the live path and the pre-warmer).

def mjoin_count_key(outer: bool, pkeys, bkeys, probe_spec, build_spec,
                    probe_cap: int, build_cap: int) -> tuple:
    return ("mjoin_count", bool(outer), tuple(pkeys), tuple(bkeys),
            probe_spec, build_spec, int(probe_cap), int(build_cap))


def mjoin_expand_key(jt: str, residual_repr: str, probe_spec,
                     build_spec, probe_cap: int, build_cap: int,
                     out_cap: int) -> tuple:
    return ("mjoin_expand", jt, residual_repr, probe_spec, build_spec,
            int(probe_cap), int(build_cap), int(out_cap))


def mjoin_kind(key: tuple) -> str:
    """The program kind of a materialized-join cache key."""
    return "join_count" if key[0] == "mjoin_count" else "join_expand"


def make_mjoin_count_program(pkeys, bkeys, outer: bool):
    """Phase 1: build-side sort and index + probe match counts + the
    effective output total. Everything downstream of the total is host
    policy (bucket choice, memory reserve, oversized spill), so the
    program ends exactly at the host-sync boundary: ONE int64[5],
    [total, the probe's bisection steps, whether the directory was
    exact, the probe side's live rows, whether the probe read one
    word a row], read in one transfer
    (``_read_join_total``). Output dtypes
    are pinned int64 — they cross into the separately-jitted expand
    program."""
    pkeys, bkeys = list(pkeys), list(bkeys)

    def fn(probe: Batch, build: Batch):
        start, count, side = join_ops.match_runs(
            probe, build, pkeys, bkeys)
        if outer:
            eff = jnp.where(probe.row_valid(),
                            jnp.maximum(count, 1), 0)
        else:
            eff = count
        return (start.astype(jnp.int64), count.astype(jnp.int64),
                side.order.astype(jnp.int64),
                join_ops.total_and_mode(eff, side, probe))

    return fn


def make_mjoin_expand_program(jt: str, residual, out_cap: int):
    """Phase 2: gather-expand the match set at the chosen capacity
    bucket; with a residual, the candidate expansion, predicate and
    compaction fuse into the same program (the streamed-join probe
    kernel's shape, minus the chunk loop)."""

    def fn(probe: Batch, build: Batch, start, count, order):
        out = join_ops.expand_join(probe, build, start, count, order,
                                   out_cap, "inner" if residual is not None
                                   else jt)
        if residual is None:
            return out
        mask = eval_predicate(residual, out)
        return compact.filter_batch(out, mask)

    return fn


def setop_tag(lb: Batch, rb: Batch):
    """Tag each side with per-side counters for the group-by counting
    kernel (reference rules: ImplementIntersectDistinctAsUnion,
    ImplementExceptAll). Shared by the local and distributed paths."""
    tagged = []
    for b, (lc, rc) in ((lb, (1, 0)), (rb, (0, 1))):
        cols = dict(b.columns)
        cols["__l$"] = Column(
            BIGINT, jnp.full((b.capacity,), lc, jnp.int64), None)
        cols["__r$"] = Column(
            BIGINT, jnp.full((b.capacity,), rc, jnp.int64), None)
        tagged.append(Batch(cols, b.num_rows))
    return tagged


SETOP_AGGS = (AggInput("sum", "__l$", output="__nl$"),
              AggInput("sum", "__r$", output="__nr$"))


def setop_keep_times(nl, nr, op: str, distinct: bool):
    """(keep-mask, replication-times|None) from the per-side counts —
    the set-op semantics in one place (EXCEPT ALL keeps rows with
    nl > nr replicated nl-nr times; INTERSECT ALL min(nl, nr))."""
    if op == "intersect":
        keep = (nl > 0) & (nr > 0)
    elif distinct:
        keep = (nl > 0) & (nr == 0)
    else:
        keep = nl > nr
    if distinct:
        return keep, None
    times = (jnp.minimum(nl, nr) if op == "intersect"
             else jnp.maximum(nl - nr, 0))
    return keep, times


def setop_batches(lb: Batch, rb: Batch, op: str, distinct: bool,
                  out_syms) -> Batch:
    """INTERSECT/EXCEPT [ALL] over two schema-aligned batches.
    Batch-level so the distributed executor can run the same kernel per
    shard after a hash repartition on all columns (its traced twin in
    exec/distributed.py differs only in concat + host syncs)."""
    both = device_concat(setop_tag(lb, rb))
    g = group_aggregate(both, out_syms, list(SETOP_AGGS))
    nl = jnp.asarray(g.column("__nl$").data)
    nr = jnp.asarray(g.column("__nr$").data)
    keep, times = setop_keep_times(nl, nr, op, distinct)
    out = compact.filter_batch(g, keep)
    if times is not None:
        times = jnp.take(times, compact.mask_to_gather(keep)[0])
        with active_span("host_read", site="setop_total"):
            total = int(jnp.sum(jnp.where(out.row_valid(), times, 0)))
        cap = capacity_for(max(total, 1))
        incl = jnp.cumsum(jnp.where(out.row_valid(), times, 0))
        p = jnp.clip(join_ops.run_positions(incl, cap), 0,
                     out.capacity - 1)
        out = out.gather(p, total)
    return Batch({s: out.column(s) for s in out_syms}, out.num_rows)


_TRACEABLE = (FilterNode, ProjectNode, LimitNode, OffsetNode, SortNode,
              TopNNode, SampleNode, AssignUniqueIdNode, MarkDistinctNode,
              AggregationNode)
Executor._STREAM_CHAIN = (FilterNode, ProjectNode, SampleNode)


def _pad_partial(b: Batch) -> Batch:
    """Pad a 1-row global-aggregate partial to capacity 8 so partials
    from every split concatenate uniformly."""
    cols = {}
    for s, c in b.columns.items():
        data = jnp.pad(jnp.asarray(c.data), (0, 8 - c.capacity))
        valid = (None if c.valid is None
                 else jnp.pad(jnp.asarray(c.valid), (0, 8 - c.capacity)))
        cols[s] = Column(c.type, data, valid, c.dictionary,
                         None if c.data2 is None else
                         jnp.pad(jnp.asarray(c.data2),
                                 (0, 8 - c.capacity)))
    return Batch(cols, b.num_rows)


def _flip_clause(c):
    from ..plan.nodes import JoinClause
    return JoinClause(c.right, c.left)


# --------------------------------------------------------------------------
# HBM-resident scan cache for immutable generator connectors: the
# "storage layer" of tpch/tpcds is deterministic, so table columns can
# live in device memory across queries — on TPU this removes the
# host->HBM re-upload (repeated scans become compute-only like the
# reference's OS-page-cached table files; the saving on the chip is
# not measured). Keyed per connector object; bounded by
# CONFIG.scan_cache_bytes, insertion-order eviction.
# --------------------------------------------------------------------------

import threading as _threading  # noqa: E402
import weakref as _weakref  # noqa: E402

_SCAN_CACHES: "_weakref.WeakKeyDictionary" = _weakref.WeakKeyDictionary()
_SCAN_CACHE_LOCK = _threading.Lock()


def _col_bytes(c: Column) -> int:
    """Bytes of the column's lanes on the fullest chip: a lane sharded
    across a mesh counts its per-chip share (the budgets it is held
    against are a chip's)."""
    total = 0
    for lane in (c.data, c.valid, c.data2):
        if lane is None:
            continue
        n = int(lane.nbytes)
        sharding = getattr(lane, "sharding", None)
        if sharding is not None and not lane.is_fully_replicated:
            n //= len(sharding.device_set)
        total += n
    return total


def _make_room(state: dict, size: int) -> None:
    """Evict a connector's oldest scan-cache entries until ``size``
    more bytes fit its budget (the caller holds the lock)."""
    while state["bytes"] + size > CONFIG.scan_cache_bytes \
            and state["order"]:
        old = state["entries"].pop(state["order"].pop(0), None)
        if old is not None:
            state["bytes"] -= sum(_col_bytes(c)
                                  for c in old["cols"].values())


def read_split_cached(conn, split, columns) -> Batch:
    """Split read through the per-connector HBM cache. Lanes are
    cached per (split, COLUMN), so overlapping projections of the same
    split share one device copy per column. The lock covers all state
    mutation — the coordinator runs one executor thread per query."""
    if not getattr(conn, "scan_cache_ok", False) \
            or CONFIG.scan_cache_bytes <= 0:
        return conn.read_split(split, columns)
    h = split.handle
    skey = (h.schema, h.table, split.part, split.part_count,
            h.constraint, h.limit)
    with _SCAN_CACHE_LOCK:
        state = _SCAN_CACHES.get(conn)
        if state is None:
            state = {"entries": {}, "order": [], "bytes": 0}
            _SCAN_CACHES[conn] = state
        entry = state["entries"].get(skey)
        missing = [c for c in columns
                   if entry is None or c not in entry["cols"]]
    if not missing:
        _M_SCAN.inc(cache="split", result="hit")
        with _SCAN_CACHE_LOCK:
            if skey in state["entries"]:
                _touch(state, skey)
            return Batch({c: entry["cols"][c] for c in columns},
                         entry["num_rows"])
    _M_SCAN.inc(cache="split", result="miss")
    on_dev = jax.default_backend() != "cpu"
    # the miss path, once per lane per process: read or generate the
    # missing lanes and pin them — waited for, so that the span (and
    # trino_tpu_scan_fill_seconds) holds the fill, not its dispatch
    with active_span("scan_fill", table=h.table, lanes=len(missing)):
        raw = conn.read_split(split, missing)
        if on_dev:
            raw = raw.on_device()          # pin the lanes in HBM
            jax.block_until_ready([c.data for c in raw.columns.values()])
    size = sum(_col_bytes(c) for c in raw.columns.values())
    with _SCAN_CACHE_LOCK:
        state = _SCAN_CACHES.get(conn)
        if state is None:
            state = {"entries": {}, "order": [], "bytes": 0}
            _SCAN_CACHES[conn] = state
        if size <= CONFIG.scan_cache_bytes:
            _make_room(state, size)
            entry = state["entries"].get(skey)
            if entry is None:
                entry = {"cols": {}, "num_rows": raw.num_rows}
                state["entries"][skey] = entry
                state["order"].append(skey)
            for name, col in raw.columns.items():
                if name not in entry["cols"]:
                    entry["cols"][name] = col
                    state["bytes"] += _col_bytes(col)
        entry = state["entries"].get(skey)
        _M_SCAN_BYTES.set(state["bytes"],
                          connector=getattr(conn, "name",
                                            type(conn).__name__))
        if entry is not None and all(c in entry["cols"]
                                     for c in columns):
            return Batch({c: entry["cols"][c] for c in columns},
                         entry["num_rows"])
    # cache too small for this split: serve the direct read (fill any
    # columns the raw read didn't cover)
    if all(c in raw.columns for c in columns):
        return Batch({c: raw.columns[c] for c in columns},
                     raw.num_rows)
    rest = conn.read_split(split, columns)
    return rest.on_device() if on_dev else rest


def cache_memory_bytes() -> int:
    """Bytes held by the shared HBM scan caches across every connector
    — the figure cross-query memory governance (server/memory.py +
    server/task_worker.py) folds into its pressure arithmetic: cached
    table lanes share the same device/host memory as query working
    sets, so a pool sized to the hardware must see them."""
    with _SCAN_CACHE_LOCK:
        scan = sum(int(state["bytes"])
                   for state in _SCAN_CACHES.values())
    # the result cache holds host-side rows, not HBM lanes, but it is
    # process memory the pressure ladder can shed — governance must
    # see it or it silently erodes the pool headroom
    try:
        from .resultcache import RESULT_CACHE
        return scan + RESULT_CACHE.bytes()
    except Exception:       # noqa: BLE001 — import cycles in teardown
        return scan


from ..obs.metrics import CACHE_PRESSURE_EVICTS as _M_CACHE_PRESSURE


def evict_cache_pressure(need_bytes: int) -> int:
    """Shed shared-cache memory under pressure, oldest entries first:
    the scan caches (byte-accounted) go first; if they cannot cover
    the deficit the structural jit-program caches drop their oldest
    half (entry sizes are opaque — compiled closures — so the jit
    relief is entry-counted, backed by the persistent XLA cache for
    recompiles) and the replicate fetch-once cache is cleared. Returns
    the scan-cache bytes actually freed. This is what makes the caches
    GOVERNED resources: a cache full of one query's programs/tables is
    evicted before the low-memory killer considers killing a neighbor
    query (ISSUE 14 tentpole part 3)."""
    need = max(int(need_bytes), 0)
    freed = 0
    with _SCAN_CACHE_LOCK:
        for conn, state in list(_SCAN_CACHES.items()):
            while state["order"] and freed < need:
                old_key = state["order"].pop(0)
                old = state["entries"].pop(old_key, None)
                if old is None:
                    continue
                sz = sum(_col_bytes(c) for c in old["cols"].values())
                state["bytes"] -= sz
                freed += sz
                _M_CACHE_PRESSURE.inc(cache="scan")
            _M_SCAN_BYTES.set(state["bytes"],
                              connector=getattr(conn, "name",
                                                type(conn).__name__))
            if freed >= need:
                break
    if freed < need:
        # byte-accounted caches first: the replicate fetch-once cache
        # frees measurable bytes before the opaque jit closures go
        try:
            from ..stage.exchange import evict_replicate_cache
            freed += evict_replicate_cache(need - freed)
        except Exception:       # noqa: BLE001 — relief is best-effort
            pass
    if freed < need:
        # the result cache sheds BEFORE the jit caches: cached rows
        # are merely saved latency, compiled programs are saved
        # compile storms — drop the cheaper-to-rebuild tier first
        try:
            from .resultcache import RESULT_CACHE
            before = len(RESULT_CACHE)
            freed += RESULT_CACHE.evict(need - freed)
            for _ in range(before - len(RESULT_CACHE)):
                _M_CACHE_PRESSURE.inc(cache="result")
        except Exception:   # noqa: BLE001 — relief is best-effort
            pass
    if freed < need:
        PROGRAMS.shed()
    return freed


def _whole_table_mode() -> bool:
    """Whole-table HBM residency: on by default on device backends,
    where a resident table turns a scan-filter-aggregate chain into
    ONE program over all rows in place of one dispatch per split (46
    splits for sf1 lineitem; the per-split cost on the chip is not
    measured). On CPU, split streaming keeps the
    working set cache-sized — the reference's page-at-a-time pipeline
    (operator/Driver.java) — so it stays the default there."""
    mode = os.environ.get("TRINO_TPU_WHOLE_TABLE", "auto")
    if mode == "auto":
        return jax.default_backend() != "cpu"
    return mode == "1"


def _touch(state: dict, key) -> None:
    """A hit keeps its entry: the eviction order is the order of last
    use (a table's base lanes, read by every derive, stay resident)."""
    order = state["order"]
    if order and order[-1] != key:
        order.remove(key)
        order.append(key)


def _table_key(h) -> tuple:
    """A whole-table entry's key in the scan cache: part -1."""
    return (h.schema, h.table, -1, 0, h.constraint, h.limit)


def read_table_cached(conn, handle, columns, par,
                      count: bool = True) -> Optional[Batch]:
    """Whole-table read through the HBM cache: all splits concatenated
    ONCE into a single device-resident Batch cached under part=-1, so
    every later scan of the table is a dictionary lookup — no per-split
    dispatch, no per-query re-concat. The whole-table entry supersedes
    the table's per-split entries (the concat copies the lanes, so
    keeping both would double-count the budget). Once the connector's
    pushed-down constraints change their literals, a constrained miss
    is derived from the table's base lanes (``_derive_constrained``),
    not filled.
    Returns None when the mode is off or the table exceeds the cache
    budget; callers fall back to split streaming. ``count=False``: a
    lookup of the scan cache's own (a derive's base), not a scan."""
    if not columns or not getattr(conn, "scan_cache_ok", False) \
            or CONFIG.scan_cache_bytes <= 0 or not _whole_table_mode():
        return None
    h = handle
    wkey = _table_key(h)
    with _SCAN_CACHE_LOCK:
        state = _SCAN_CACHES.get(conn)
        entry = state["entries"].get(wkey) if state else None
        missing = [c for c in columns
                   if entry is None or c not in entry["cols"]]
        if not missing:
            if count:
                _M_SCAN.inc(cache="table", result="hit")
            _touch(state, wkey)
            return Batch({c: entry["cols"][c] for c in columns},
                         entry["num_rows"])
    if h.constraint is not None:
        derived = _derive_constrained(conn, h, columns, par)
        if derived is not None:
            return derived
    splits = conn.get_splits(h, par)
    if len(splits) == 1:
        # a table of one split IS its split: no whole-table entry is
        # ever made for it, so the split's own lookup counts the hit or
        # the miss (a resident dimension is not a table-level miss)
        return read_split_cached(conn, splits[0], columns)
    if count:
        _M_SCAN.inc(cache="table", result="miss")
    # cheap pre-check from the handle's row estimate so an over-budget
    # table (inventory@sf10 is ~4GB of lanes) is never transiently
    # materialized whole in HBM just to discover it doesn't fit. Sized
    # on the MISSING columns only — an almost-fully-cached wide table
    # must stay admissible for its last few columns.
    est_rows = None
    if hasattr(conn, "table_row_count"):
        est_rows = conn.table_row_count(h)
    if est_rows:
        est = int(est_rows) * max(len(missing), 1) * 9  # data8+valid1
        if 2 * est > CONFIG.scan_cache_bytes:
            return None
    parts = [read_split_cached(conn, s, missing) for s in splits]
    total_bytes = sum(_col_bytes(c) for b in parts
                      for c in b.columns.values())
    # concat pads up to the next capacity bucket: budget 2x the raw size
    if 2 * total_bytes > CONFIG.scan_cache_bytes:
        return None
    whole = device_concat(parts)
    with _SCAN_CACHE_LOCK:
        state = _SCAN_CACHES.get(conn)
        if state is None:
            state = {"entries": {}, "order": [], "bytes": 0}
            _SCAN_CACHES[conn] = state
        for k in [k for k in state["order"]
                  if k[:2] == (h.schema, h.table) and k[2] >= 0]:
            old = state["entries"].pop(k, None)
            state["order"].remove(k)
            if old is not None:
                state["bytes"] -= sum(_col_bytes(c)
                                      for c in old["cols"].values())
        size = sum(_col_bytes(c) for c in whole.columns.values())
        _make_room(state, size)
        entry = state["entries"].get(wkey)
        if entry is None:
            entry = {"cols": {}, "num_rows": whole.num_rows}
            state["entries"][wkey] = entry
            state["order"].append(wkey)
        for name, col in whole.columns.items():
            if name not in entry["cols"]:
                entry["cols"][name] = col
                state["bytes"] += _col_bytes(col)
        _M_SCAN_BYTES.set(state["bytes"],
                          connector=getattr(conn, "name",
                                            type(conn).__name__))
        entry = state["entries"].get(wkey)
        if entry is not None and all(c in entry["cols"]
                                     for c in columns):
            return Batch({c: entry["cols"][c] for c in columns},
                         entry["num_rows"])
    # the budget evicted our own entry mid-insert: stream instead
    return None


def _derive_constrained(conn, h, columns, par) -> Optional[Batch]:
    """The lanes of a pushed-down constraint DERIVED from the table's
    resident base lanes (exec/scanderive.py), or None where the miss
    fills as before. A connector's scan cache fills every constraint
    until one constraint SHAPE meets a second distinct set of literals
    (a re-miss of the same set after an eviction refills); from then on
    it derives, and that first derive derives every shape it has met
    once, so each shape's program compiles while the literals first
    change (a benchmark's set-up: the validation set, then a drawn
    one), not at a later set."""
    from .scanderive import constraint_shape
    got = None if h.limit is not None else constraint_shape(h.constraint)
    if got is None:
        return None
    shape, values = got
    with _SCAN_CACHE_LOCK:
        state = _SCAN_CACHES.get(conn)
        if state is None:
            state = {"entries": {}, "order": [], "bytes": 0}
            _SCAN_CACHES[conn] = state
        shapes = state.setdefault("shapes", {})
        seen = shapes.get((h.schema, h.table, shape))
        if seen is None:
            seen = shapes[(h.schema, h.table, shape)] = {
                "first": h, "lanes": set()}
        seen["lanes"].update(columns)
        others = []
        if not state.get("vary"):
            if constraint_shape(seen["first"].constraint)[1] == values:
                return None
            state["vary"] = True
            others = [o for o in shapes.values() if o is not seen]
    for o in others:
        _derive_once(conn, o["first"], o, par)
    return _derive_once(conn, h, seen, par, columns)


def _derive_once(conn, h, seen, par, columns=None) -> Optional[Batch]:
    """ONE ``scan_derive`` program: ``h``'s constraint over the table's
    base lanes, the lanes its shape's entries deliver (``seen``) kept,
    compacted and cut to the shape's capacity: its first copy's, or the
    bucket of the rows where they outgrow it. The copy is kept in the
    scan cache under ``h`` (beside a copy already there, which holds
    the same rows); ``columns`` of it returned."""
    from .scanderive import (bound_vectors, constraint_shape,
                             make_derive_program, make_prefix_program)
    from .streamjoin import _lane_spec
    shape, values = constraint_shape(h.constraint)
    keep = tuple(sorted(seen["lanes"]))
    base = read_table_cached(
        conn, dc_replace(h, constraint=None),
        sorted(set(keep) | {c for c, _ in h.constraint.domains}), par,
        count=False)
    if base is None:
        return None
    key = ("scan_derive", shape, keep, _lane_spec(base), base.capacity)
    jitted, hit = PROGRAMS.program(
        "scan", key, lambda: make_derive_program(shape, keep),
        "scan_derive", key)
    _M_SCAN.inc(cache="table", result="miss")
    with active_span("scan_derive", table=h.table, rows_in=base.num_rows,
                     lanes=len(keep)) as sp:
        with dispatch_span(None, jitted.program, hit, "scan"):
            out, n = jitted(base, bound_vectors(values))
        with active_span("host_read", site="scan_derive"):
            rows = int(n)
        if sp is not None:
            sp.attrs["rows_out"] = rows
        with _SCAN_CACHE_LOCK:
            if "cap" not in seen:
                # the first copy: whole-table, or its one split's
                f = seen["first"]
                entries = _SCAN_CACHES[conn]["entries"]
                first = entries.get(_table_key(f)) or entries.get(
                    (f.schema, f.table, 0, 1, f.constraint, f.limit))
                seen["cap"] = 0 if first is None else next(
                    iter(first["cols"].values())).capacity
                # its row count as the fill gave it (a device count from
                # one split's filter): one signature for the programs
                seen["device_rows"] = first is not None and not isinstance(
                    first["num_rows"], int)
            seen["cap"] = cap = min(max(seen["cap"], capacity_for(rows)),
                                    out.capacity)
        if cap < out.capacity:
            pkey = ("scan_prefix", _lane_spec(out), out.capacity, cap)
            jitted, hit = PROGRAMS.program(
                "scan", pkey, lambda: make_prefix_program(cap),
                "scan_prefix", pkey)
            with dispatch_span(None, jitted.program, hit, "scan"):
                out = jitted(out)
    out = Batch(out.columns, n if seen["device_rows"] else rows)
    wkey = _table_key(h)
    size = sum(_col_bytes(c) for c in out.columns.values())
    with _SCAN_CACHE_LOCK:
        state = _SCAN_CACHES[conn]
        if size <= CONFIG.scan_cache_bytes:
            entry = state["entries"].get(wkey)
            if entry is None:
                _make_room(state, size)
                entry = {"cols": {}, "num_rows": out.num_rows}
                state["entries"][wkey] = entry
                state["order"].append(wkey)
            for name, col in out.columns.items():
                if name not in entry["cols"]:
                    entry["cols"][name] = col
                    state["bytes"] += _col_bytes(col)
            _M_SCAN_BYTES.set(state["bytes"],
                              connector=getattr(conn, "name",
                                                type(conn).__name__))
    if columns is None:
        return None
    return Batch({c: out.columns[c] for c in columns}, out.num_rows)


def _fill_sharded(conn, h, columns, mesh):
    """The sharded scan cache's miss path; split i of the table belongs
    to shard i mod n. Where the connector can give its rows as
    functions of a set of row indices (``shard_generator``: the tpch
    device generators), every shard is generated ON its own chip by two
    mesh programs (``_generate_sharded``); else every shard's splits
    are read on its own chip (``_read_sharded``). Waited for, so the
    ``scan_fill`` span holds the fill."""
    n = mesh.devices.size
    splits = conn.get_splits(h, n)
    make = getattr(conn, "shard_generator", None)
    gen = make(h, columns) if make is not None else None
    with active_span("scan_fill", table=h.table, lanes=len(columns),
                     shards=n):
        _M_SPLITS.inc(len(splits))
        sb = (_generate_sharded(gen, splits, mesh) if gen is not None
              else _read_sharded(conn, h, splits, columns, mesh))
        jax.block_until_ready([c.data for c in sb.columns.values()])
    return sb


def _read_sharded(conn, h, splits, columns, mesh):
    """Every shard's splits read on its own chip by its own host thread
    (a connector's reads are eager code with blocking counts, so the
    chips fill side by side only that way), glued there, and the global
    lanes assembled in place."""
    from concurrent.futures import ThreadPoolExecutor

    from ..parallel.mesh import shard_parts
    n = mesh.devices.size
    devices = list(mesh.devices.flat)

    def fill(d: int) -> Batch:
        with jax.default_device(devices[d]):
            parts = [conn.read_split(sp, columns) for sp in splits[d::n]]
            if not parts:
                from ..columnar import empty_batch
                meta = conn.get_table_metadata(h.schema, h.table)
                parts = [empty_batch({c.name: c.type for c in meta.columns
                                      if c.name in set(columns)})]
            part = device_concat(parts).on_device()
            jax.block_until_ready([c.data for c in part.columns.values()])
            return part

    with ThreadPoolExecutor(max_workers=n) as pool:
        return shard_parts(list(pool.map(fill, range(n))), mesh)


def _generate_sharded(gen, splits, mesh):
    """Every shard's rows from ``gen`` (connectors/tpch_device.py
    ``ShardGenerator``) on the shard's own chip: the row indices of its
    splits go in, a count program sizes the lanes, a second program
    makes them. Two compiles a scan, whatever the number of splits."""
    from ..parallel.mesh import AXIS, ShardedBatch, replicated, row_spec
    from ..parallel.spmd import P, mesh_call
    n = mesh.devices.size
    indices = [gen.order_indices(splits[d::n]) for d in range(n)]
    width = capacity_for(max(len(i) for i in indices), minimum=8)
    oi = jax.device_put(
        np.concatenate([np.pad(i, (0, width - len(i))) for i in indices]),
        row_spec(mesh))
    live = jax.device_put(np.asarray([len(i) for i in indices], np.int64),
                          replicated(mesh))
    # 100.0 as an OPERAND: see connectors/tpch_device.py _retailprice
    hundred = jax.device_put(np.float64(100.0), replicated(mesh))
    operands = (oi, live, hundred)

    def mine(vec):
        return vec[jax.lax.axis_index(AXIS)]

    def build_rows():
        return (lambda o, k, h: jax.lax.all_gather(
            gen.rows(o, mine(k), h), AXIS), (P(AXIS), P(), P()), P())

    with active_span("host_read", site="scan_rows"):
        totals = np.asarray(mesh_call("scan_rows", gen.key, mesh,
                                      operands, build_rows))
    cap = capacity_for(max(int(totals.max()), 1), minimum=8)

    def build_lanes():
        def f(o, k, h):
            out = gen.batch(o, mine(k), h, cap)
            return out.columns, jax.lax.all_gather(out.num_rows_device(),
                                                   AXIS)
        return f, (P(AXIS), P(), P()), (P(AXIS), P())

    cols, counts = mesh_call("scan_gen", (gen.key, cap), mesh, operands,
                             build_lanes)
    return ShardedBatch(cols, counts, mesh, cap)


def read_table_sharded(conn, handle, columns, mesh):
    """A table's lanes row-sharded across ``mesh`` and resident there:
    split i belongs to shard i mod n (``_fill_sharded``), and every
    later scan of the table under the same pushed-down constraint is a
    lookup. Lanes are cached per column under one entry of the
    connector's scan cache (budget and eviction as for its other
    entries; a sharded lane is charged its per-chip share). Returns a
    ``ShardedBatch`` of connector columns."""
    from ..parallel.mesh import ShardedBatch
    h = handle
    if not getattr(conn, "scan_cache_ok", False) \
            or CONFIG.scan_cache_bytes <= 0:
        return _fill_sharded(conn, h, list(columns), mesh)
    skey = (h.schema, h.table, -2, mesh.devices.size, h.constraint,
            h.limit, tuple(int(d.id) for d in mesh.devices.flat))

    def cached(entry):
        return ShardedBatch({c: entry["cols"][c] for c in columns},
                            entry["num_rows"], mesh, entry["per"])

    with _SCAN_CACHE_LOCK:
        state = _SCAN_CACHES.get(conn)
        entry = state["entries"].get(skey) if state else None
        missing = [c for c in columns
                   if entry is None or c not in entry["cols"]]
        if not missing:
            _M_SCAN.inc(cache="sharded", result="hit")
            return cached(entry)
    _M_SCAN.inc(cache="sharded", result="miss")
    sb = _fill_sharded(conn, h, missing, mesh)
    size = sum(_col_bytes(c) for c in sb.columns.values())
    with _SCAN_CACHE_LOCK:
        state = _SCAN_CACHES.get(conn)
        if state is None:
            state = {"entries": {}, "order": [], "bytes": 0}
            _SCAN_CACHES[conn] = state
        if size <= CONFIG.scan_cache_bytes:
            _make_room(state, size)
            entry = state["entries"].get(skey)
            if entry is None:
                entry = {"cols": {}, "num_rows": sb.num_rows,
                         "per": sb.per_shard_cap}
                state["entries"][skey] = entry
                state["order"].append(skey)
            for name, col in sb.columns.items():
                if name not in entry["cols"]:
                    entry["cols"][name] = col
                    state["bytes"] += _col_bytes(col)
            _M_SCAN_BYTES.set(state["bytes"],
                              connector=getattr(conn, "name",
                                                type(conn).__name__))
            if all(c in entry["cols"] for c in columns):
                return cached(entry)
    # the budget cannot hold these lanes: serve the read uncached
    if len(missing) == len(columns):
        return sb
    return _fill_sharded(conn, h, list(columns), mesh)


def _amf_post(sym: str, k: int):
    def post(out: Batch) -> Column:
        from .complex import top_k_map_entries
        return top_k_map_entries(out.column(sym), k)
    return post


def _single_row(src: Batch) -> Batch:
    return Batch({"__one$": Column(
        BIGINT, jnp.zeros((8,), jnp.int64), None)}, 1)


# --------------------------------------------------------------------------
# aggregate lowering (avg & friends -> segment-op primitives)
# --------------------------------------------------------------------------

def _with_post(out: Batch, post, node: AggregationNode) -> Batch:
    """``out`` with the aggregates' post-processing (``_lower_aggregates``:
    an avg from its sum and count ...) applied and the intermediate
    lanes dropped."""
    if not post:
        return out
    cols = dict(out.columns)
    for sym, fn in post.items():
        cols[sym] = fn(out)
    keep = set(node.group_keys) | set(node.aggregates)
    return Batch({s: c for s, c in cols.items() if s in keep},
                 out.num_rows)


def _lower_aggregates(aggregates: Dict[str, Aggregate], src: Batch):
    """Map logical aggregates onto the kernel-supported kinds
    (sum/count/count_star/min/max/any_value), returning
    (phys_aggs, post_fns, extra_columns). The decomposition mirrors the
    reference's accumulator states (e.g. avg = LongAndDoubleState,
    variance = CentralMomentsState —
    operator/aggregation/AverageAggregations.java, CentralMomentsState)."""
    phys: List[AggInput] = []
    post = {}
    extra: Dict[str, Column] = {}

    for sym, a in aggregates.items():
        kind = a.kind
        if kind == "count" and a.distinct:
            phys.append(AggInput("count_distinct", a.argument, a.mask,
                                 sym))
        elif kind in ("sum", "min", "max", "count", "count_star"):
            phys.append(AggInput(kind, a.argument, a.mask, sym))
        elif kind in ("any_value", "arbitrary"):
            phys.append(AggInput("any_value", a.argument, a.mask, sym))
        elif kind == "avg":
            ssym, csym = sym + "$sum", sym + "$cnt"
            phys.append(AggInput("sum", a.argument, a.mask, ssym))
            phys.append(AggInput("count", a.argument, a.mask, csym))
            post[sym] = _avg_post(ssym, csym, a.type)
        elif kind == "count_if":
            msym = sym + "$mask"
            arg = src.column(a.argument)
            m = jnp.asarray(arg.data).astype(bool)
            if arg.valid is not None:
                m = m & jnp.asarray(arg.valid)
            if a.mask is not None:
                mc = src.column(a.mask)
                mm = jnp.asarray(mc.data).astype(bool)
                if mc.valid is not None:
                    mm = mm & jnp.asarray(mc.valid)
                m = m & mm
            extra[msym] = Column(BOOLEAN, m, None)
            phys.append(AggInput("count_star", None, msym, sym))
        elif kind in ("bool_and", "every", "bool_or"):
            op = "min" if kind in ("bool_and", "every") else "max"
            phys.append(AggInput(op, a.argument, a.mask, sym))
        elif kind in ("stddev", "stddev_samp", "stddev_pop", "variance",
                      "var_samp", "var_pop"):
            bsym, d, bvalid = _stat_lane(src, a.argument, extra,
                                         sym + "$f")
            sqsym = sym + "$sq"
            extra[sqsym] = Column(DOUBLE, d * d, bvalid)
            ssym, csym, s2sym = sym + "$s", sym + "$c", sym + "$s2"
            phys.append(AggInput("sum", bsym, a.mask, ssym))
            phys.append(AggInput("count", bsym, a.mask, csym))
            phys.append(AggInput("sum", sqsym, a.mask, s2sym))
            pop = kind.endswith("_pop")
            sqrt = kind.startswith("stddev")
            post[sym] = _variance_post(ssym, csym, s2sym, pop, sqrt)
        elif kind == "geometric_mean":
            lsym = sym + "$ln"
            _, d, bvalid = _stat_lane(src, a.argument, extra, sym + "$f")
            extra[lsym] = Column(DOUBLE, jnp.log(d), bvalid)
            ssym, csym = sym + "$s", sym + "$c"
            phys.append(AggInput("sum", lsym, a.mask, ssym))
            phys.append(AggInput("count", lsym, a.mask, csym))
            post[sym] = _geomean_post(ssym, csym)
        elif kind in ("bitwise_and_agg", "bitwise_or_agg"):
            phys.append(AggInput(
                "bit_and" if kind == "bitwise_and_agg" else "bit_or",
                a.argument, a.mask, sym))
        elif kind in ("min_by", "max_by"):
            phys.append(AggInput(
                "argmin" if kind == "min_by" else "argmax",
                a.argument, a.mask, sym, input2=a.argument2))
        elif kind == "approx_distinct":
            phys.append(AggInput("count_distinct", a.argument, a.mask,
                                 sym))
        elif kind == "approx_set":
            # param (if present) is the requested max standard error;
            # translate to a bucket-count exponent once at plan time
            from ..ops.hll import (APPROX_SET_BUCKET_BITS,
                                   bucket_bits_for_error)
            b = (bucket_bits_for_error(float(a.param))
                 if a.param is not None else APPROX_SET_BUCKET_BITS)
            phys.append(AggInput("hll", a.argument, a.mask, sym,
                                 param=float(b)))
        elif kind == "merge":
            from ..types import QDigestType, TDigestType
            argt = src.column(a.argument).type
            mk = ("digest_merge"
                  if isinstance(argt, (TDigestType, QDigestType))
                  else "hll_merge")
            phys.append(AggInput(mk, a.argument, a.mask, sym))
        elif kind in ("tdigest_agg", "qdigest_agg"):
            phys.append(AggInput(
                "tdigest" if kind == "tdigest_agg" else "qdigest",
                a.argument, a.mask, sym, input2=a.argument2,
                param=a.param))
        elif kind == "array_agg":
            phys.append(AggInput("array_agg", a.argument, a.mask, sym))
        elif kind == "map_agg":
            phys.append(AggInput("map_agg", a.argument, a.mask, sym,
                                 input2=a.argument2))
        elif kind == "map_union":
            phys.append(AggInput("map_union", a.argument, a.mask, sym))
        elif kind == "multimap_agg":
            phys.append(AggInput("multimap_agg", a.argument, a.mask, sym,
                                 input2=a.argument2))
        elif kind == "numeric_histogram":
            phys.append(AggInput("numeric_histogram", a.argument, a.mask,
                                 sym, input2=a.argument2, param=a.param))
        elif kind == "histogram":
            phys.append(AggInput("histogram", a.argument, a.mask, sym))
        elif kind == "approx_most_frequent":
            # exact histogram then keep the k most frequent entries
            # (reference approximates with a stream summary —
            # operator/aggregation/approxmostfrequent/; exact is a
            # correct superset)
            phys.append(AggInput("histogram", a.argument, a.mask, sym))
            k = int(a.param) if a.param is not None else 3
            post[sym] = _amf_post(sym, k)
        elif kind == "approx_percentile":
            phys.append(AggInput("percentile", a.argument, a.mask, sym,
                                 param=a.param))
        elif kind == "checksum":
            # order-independent multiset hash: wraparound int64 sum of
            # per-row hashes; NULL contributes a fixed odd constant
            # (reference: operator/aggregation/ChecksumAggregation —
            # xxhash64-based, ours is the engine hash of ops/hashing.py)
            from ..ops.hashing import hash_column as _hcol, mix64 as _mix
            arg = src.column(a.argument)
            hsym = sym + "$h"
            h = _hcol(arg.data, arg.valid)
            if arg.data2 is not None:
                h = h * jnp.uint64(31) + _hcol(arg.data2, arg.valid)
            valid_row = (jnp.ones((h.shape[0],), bool)
                         if arg.valid is None else jnp.asarray(arg.valid))
            h = jnp.where(valid_row, h,
                          jnp.uint64(0x9E3779B97F4A7C15))
            extra[hsym] = Column(BIGINT, h.astype(jnp.int64), None)
            phys.append(AggInput("sum", hsym, a.mask, sym))
        elif kind in ("corr", "covar_samp", "covar_pop", "regr_slope",
                      "regr_intercept"):
            # sum-of-products lowering over PAIRWISE-valid rows
            # (reference: CovarianceAggregation / CorrelationAggregation
            # / RegressionAggregation states)
            _, yd, yv = _stat_lane(src, a.argument, extra, sym + "$fy")
            _, xd, xv = _stat_lane(src, a.argument2, extra, sym + "$fx")
            pv = None
            for v in (yv, xv):
                if v is not None:
                    v = jnp.asarray(v)
                    pv = v if pv is None else pv & v
            names = {}
            lanes = {"y": yd, "x": xd, "xy": xd * yd, "xx": xd * xd}
            if kind == "corr":
                lanes["yy"] = yd * yd
            for tag, d in lanes.items():
                lsym = f"{sym}${tag}"
                extra[lsym] = Column(DOUBLE, d, pv)
                ssym = f"{sym}$s{tag}"
                phys.append(AggInput("sum", lsym, a.mask, ssym))
                names[tag] = ssym
            csym = sym + "$n"
            phys.append(AggInput("count", f"{sym}$x", a.mask, csym))
            post[sym] = _bivariate_post(kind, names, csym)
        elif kind in ("skewness", "kurtosis"):
            bsym, d, bvalid = _stat_lane(src, a.argument, extra,
                                         sym + "$f")
            names = {}
            for p, tag in ((2, "2"), (3, "3"), (4, "4")):
                if p == 4 and kind != "kurtosis":
                    continue
                lsym = f"{sym}$p{tag}"
                extra[lsym] = Column(DOUBLE, d ** p, bvalid)
                ssym = f"{sym}$s{tag}"
                phys.append(AggInput("sum", lsym, a.mask, ssym))
                names[tag] = ssym
            ssym, csym = sym + "$s1", sym + "$n"
            phys.append(AggInput("sum", bsym, a.mask, ssym))
            phys.append(AggInput("count", bsym, a.mask, csym))
            post[sym] = _moments_post(kind, ssym, names, csym)
        else:
            raise QueryError(f"aggregate '{kind}' not implemented")
    return phys, post, extra


def _stat_lane(src: Batch, name: str, extra: Dict[str, Column],
               tag: str):
    """(symbol, f64 lane, validity) of a numeric input for the
    statistical aggregates — DECIMAL lanes are unscaled to doubles
    (their storage is the scaled integer)."""
    col = src.column(name)
    d = jnp.asarray(col.data).astype(jnp.float64)
    if isinstance(col.type, DecimalType):
        if col.data2 is not None:
            raise QueryError(
                "statistical aggregates over DECIMAL(p>18) are not "
                "supported")
        d = d / (10.0 ** col.type.scale)
        extra[tag] = Column(DOUBLE, d, col.valid)
        return tag, d, col.valid
    return name, d, col.valid


def _bivariate_post(kind: str, s: Dict[str, str], csym: str):
    """corr/covar/regr finishers from pairwise sums. Formulas match the
    reference accumulator states (CovarianceState etc.)."""
    def fn(out: Batch) -> Column:
        n = jnp.asarray(out.column(csym).data).astype(jnp.float64)
        sy = jnp.asarray(out.column(s["y"]).data).astype(jnp.float64)
        sx = jnp.asarray(out.column(s["x"]).data).astype(jnp.float64)
        sxy = jnp.asarray(out.column(s["xy"]).data).astype(jnp.float64)
        sxx = jnp.asarray(out.column(s["xx"]).data).astype(jnp.float64)
        nn = jnp.maximum(n, 1.0)
        co = sxy - sx * sy / nn          # n * cov_pop
        mxx = sxx - sx * sx / nn         # n * var_pop(x)
        if kind == "covar_pop":
            data, valid = co / nn, n > 0
        elif kind == "covar_samp":
            data, valid = co / jnp.maximum(n - 1.0, 1.0), n > 1
        elif kind == "corr":
            syy = jnp.asarray(out.column(s["yy"]).data).astype(
                jnp.float64)
            myy = syy - sy * sy / nn
            denom = jnp.sqrt(mxx * myy)
            data = co / jnp.where(denom > 0.0, denom, 1.0)
            valid = (n > 1) & (denom > 0.0)
        elif kind == "regr_slope":
            data = co / jnp.where(mxx > 0.0, mxx, 1.0)
            valid = (n > 0) & (mxx > 0.0)
        else:  # regr_intercept
            slope = co / jnp.where(mxx > 0.0, mxx, 1.0)
            data = (sy - slope * sx) / nn
            valid = (n > 0) & (mxx > 0.0)
        return Column(DOUBLE, data, valid)
    return fn


def _moments_post(kind: str, ssym: str, s: Dict[str, str], csym: str):
    """skewness/kurtosis from raw power sums via central moments
    (reference: CentralMomentsState + DoubleSkewness/Kurtosis)."""
    def fn(out: Batch) -> Column:
        n = jnp.asarray(out.column(csym).data).astype(jnp.float64)
        s1 = jnp.asarray(out.column(ssym).data).astype(jnp.float64)
        s2 = jnp.asarray(out.column(s["2"]).data).astype(jnp.float64)
        s3 = jnp.asarray(out.column(s["3"]).data).astype(jnp.float64)
        nn = jnp.maximum(n, 1.0)
        m2 = s2 - s1 * s1 / nn
        m3 = s3 - 3.0 * s1 * s2 / nn + 2.0 * s1 ** 3 / (nn * nn)
        if kind == "skewness":
            denom = jnp.where(m2 > 0.0, m2, 1.0) ** 1.5
            data = jnp.sqrt(nn) * m3 / denom
            valid = (n > 2) & (m2 > 0.0)
        else:
            s4 = jnp.asarray(out.column(s["4"]).data).astype(jnp.float64)
            m4 = (s4 - 4.0 * s1 * s3 / nn + 6.0 * s1 * s1 * s2 / (nn * nn)
                  - 3.0 * s1 ** 4 / (nn ** 3))
            m2s = jnp.where(m2 > 0.0, m2, 1.0)
            data = (nn * (nn + 1.0) / jnp.maximum(
                (nn - 1.0) * (nn - 2.0) * (nn - 3.0), 1.0)
                * (nn * m4 / (m2s * m2s))
                - 3.0 * (nn - 1.0) ** 2 / jnp.maximum(
                    (nn - 2.0) * (nn - 3.0), 1.0))
            valid = (n > 3) & (m2 > 0.0)
        return Column(DOUBLE, data, valid)
    return fn


def _avg_post(ssym, csym, rtype):
    def fn(out: Batch) -> Column:
        s = out.column(ssym)
        c = out.column(csym)
        cnt = jnp.asarray(c.data).astype(jnp.float64)
        valid = cnt > 0
        if isinstance(rtype, DecimalType) and s.data2 is not None:
            # Int128 sum: rescale sum-scale -> result-scale, then one
            # exact HALF_UP division by the count. A result scale
            # BELOW the sum scale folds the 10^k into the divisor so
            # the value rounds ONCE (divide-then-rescale rounded
            # twice, off by one ulp at .x45 boundaries — round-5
            # advisor nit). Reference: DecimalAverageAggregation.java
            from ..ops import int128 as i128
            lo = jnp.asarray(s.data).astype(jnp.int64)
            hi = jnp.asarray(s.data2).astype(jnp.int64)
            shift = rtype.scale - s.type.scale
            lo, hi = i128.rescale(lo, hi, max(shift, 0))
            cn = jnp.maximum(jnp.asarray(c.data).astype(jnp.int64), 1)
            if shift < 0:
                lo, hi = i128.div128_round_half_up_scaled(
                    lo, hi, cn, -shift)
            else:
                lo, hi = i128.div128_round_half_up_pair(
                    lo, hi, cn, jnp.zeros_like(cn))
            if rtype.is_short:
                return Column(rtype, lo, valid)
            return Column(rtype, lo, valid, data2=hi)
        num = jnp.asarray(s.data).astype(jnp.float64)
        if isinstance(s.type, DecimalType):
            num = num / (10.0 ** s.type.scale)
        data = num / jnp.maximum(cnt, 1.0)
        if isinstance(rtype, DecimalType):
            q = (jnp.sign(data) *
                 jnp.floor(jnp.abs(data) * 10.0 ** rtype.scale + 0.5))
            return Column(rtype, q.astype(jnp.int64), valid)
        if rtype is REAL:
            return Column(rtype, data.astype(jnp.float32), valid)
        return Column(rtype, data, valid)
    return fn


def _variance_post(ssym, csym, s2sym, pop: bool, sqrt: bool):
    def fn(out: Batch) -> Column:
        s = jnp.asarray(out.column(ssym).data).astype(jnp.float64)
        n = jnp.asarray(out.column(csym).data).astype(jnp.float64)
        s2 = jnp.asarray(out.column(s2sym).data).astype(jnp.float64)
        m2 = s2 - s * s / jnp.maximum(n, 1.0)
        denom = jnp.maximum(n if pop else n - 1.0, 1.0)
        v = m2 / denom
        v = jnp.maximum(v, 0.0)
        data = jnp.sqrt(v) if sqrt else v
        valid = n > (0.0 if pop else 1.0)
        return Column(DOUBLE, data, valid)
    return fn


def _geomean_post(ssym, csym):
    def fn(out: Batch) -> Column:
        s = jnp.asarray(out.column(ssym).data).astype(jnp.float64)
        n = jnp.asarray(out.column(csym).data).astype(jnp.float64)
        return Column(DOUBLE, jnp.exp(s / jnp.maximum(n, 1.0)), n > 0)
    return fn


# --------------------------------------------------------------------------
# host spill helpers (HBM -> host RAM accumulation for oversized joins)
# --------------------------------------------------------------------------

def _to_host(b: Batch, n: int) -> Batch:
    """Materialize the live prefix of ``b`` on host (numpy lanes) —
    the spill write. LazyBlock in reverse: device memory is released,
    re-upload happens lazily when a kernel touches the column."""
    cols = {}
    for s, c in b.columns.items():
        data = np.asarray(c.data)[:n].copy()
        valid = None if c.valid is None else np.asarray(c.valid)[:n].copy()
        d2 = None if c.data2 is None else np.asarray(c.data2)[:n].copy()
        cols[s] = Column(c.type, data, valid, c.dictionary, d2)
    return Batch(cols, n)


def _host_concat(chunks: Sequence[Batch], total: int) -> Batch:
    """Concatenate host-resident chunks into one host Batch."""
    cap = capacity_for(max(total, 1), minimum=8)
    names = chunks[0].names
    cols: Dict[str, Column] = {}
    for name in names:
        cs = [c.column(name) for c in chunks]
        typ = cs[0].type
        dic = cs[0].dictionary
        if dic is not None and any(c.dictionary is not dic
                                   for c in cs[1:]):
            merged = dic
            remaps = [np.arange(len(merged), dtype=np.int32)]
            for c in cs[1:]:
                merged, _, ro = merged.merge(c.dictionary)
                remaps.append(ro)
            lanes = [np.take(rm, np.asarray(c.data).astype(np.int32))
                     for c, rm in zip(cs, remaps)]
            dic = merged
        else:
            lanes = [np.asarray(c.data) for c in cs]
        data = np.concatenate(lanes)
        data = np.pad(data, (0, cap - len(data)))
        valid = None
        if any(c.valid is not None for c in cs):
            vl = [np.ones(len(np.asarray(c.data)), bool)
                  if c.valid is None else np.asarray(c.valid)
                  for c in cs]
            valid = np.pad(np.concatenate(vl), (0, cap - total))
        d2 = None
        if any(c.data2 is not None for c in cs):
            l2 = [np.zeros(len(np.asarray(c.data)), np.int64)
                  if c.data2 is None else np.asarray(c.data2)
                  for c in cs]
            d2 = np.pad(np.concatenate(l2), (0, cap - total))
        cols[name] = Column(typ, data, valid, dic, d2)
    return Batch(cols, total)


# --------------------------------------------------------------------------
# device concat (local exchange merge)
# --------------------------------------------------------------------------

def device_concat(parts: Sequence[Batch]) -> Batch:
    """Concatenate live prefixes of Batches on device.

    The gather indices are host-computed from (host) row counts — this is
    the local-exchange merge point (reference: operator/exchange/
    LocalExchange.java), a natural host sync."""
    parts = list(parts)
    if len(parts) == 1:
        return parts[0]
    with active_span("host_read", site="concat_rows"):
        counts = [p.num_rows_host() for p in parts]
    total = sum(counts)
    if total > CONFIG.max_batch_rows and any(
            isinstance(next(iter(p.columns.values())).data, np.ndarray)
            for p in parts):
        # an oversized part already spilled to host: keep the merge on
        # host RAM instead of re-materializing everything on device
        return _host_concat([_to_host(p, n)
                             for p, n in zip(parts, counts)], total)
    cap = capacity_for(max(total, 1))
    names = parts[0].names
    out_cols: Dict[str, Column] = {}
    for name in names:
        cols = [p.column(name) for p in parts]
        typ = cols[0].type
        if cols[0].elements is not None or cols[0].children is not None:
            # pooled (ARRAY/MAP/ROW) columns merge host-side with
            # rebased offsets (exec/complex.py)
            from .complex import concat_columns_host
            out_cols[name] = concat_columns_host(cols, counts, cap)
            continue
        if is_string(typ):
            merged = cols[0].dictionary
            remaps = [np.arange(len(merged), dtype=np.int32)]
            for c in cols[1:]:
                merged, _, ro = merged.merge(c.dictionary)
                remaps.append(ro)
            lanes = [jnp.take(jnp.asarray(rm),
                              jnp.asarray(c.data).astype(jnp.int32),
                              mode="clip")
                     for c, rm in zip(cols, remaps)]
        else:
            dt = np.asarray(cols[0].data).dtype
            lanes = [jnp.asarray(c.data).astype(dt) for c in cols]
        glued = jnp.concatenate(lanes)
        # host-computed index of each part's live prefix
        idx_parts = []
        offset = 0
        for c, n in zip(cols, counts):
            idx_parts.append(np.arange(n, dtype=np.int64) + offset)
            offset += c.capacity
        idx = np.concatenate(idx_parts) if idx_parts else \
            np.zeros(0, np.int64)
        idx = np.pad(idx, (0, cap - len(idx)))
        data = jnp.take(glued, jnp.asarray(idx), mode="clip")
        any_valid = any(c.valid is not None for c in cols)
        valid = None
        if any_valid:
            vlanes = [jnp.ones((c.capacity,), bool) if c.valid is None
                      else jnp.asarray(c.valid) for c in cols]
            valid = jnp.take(jnp.concatenate(vlanes), jnp.asarray(idx),
                             mode="clip")
        d2 = None
        if any(c.data2 is not None for c in cols):
            from ..columnar import hi_lane_or_fill
            d2 = jnp.take(
                jnp.concatenate([hi_lane_or_fill(c) for c in cols]),
                jnp.asarray(idx), mode="clip")
        out_cols[name] = Column(typ, data, valid,
                                merged if is_string(typ) else None, d2)
    return Batch(out_cols, total)
