"""Canonical program keys: one shared canonicalizer for every
compiled-program cache.

Reference parity: the reference keys its generated-bytecode caches on
RowExpression trees (sql/gen/ExpressionCompiler.java:56) — two queries
whose expressions are structurally equal share one compiled class no
matter what the analyzer named their symbols. Here the compiled unit
is an XLA program and the cache has THREE layers that must agree on
identity:

1. the in-process program cache (``PROGRAMS`` below: one bucket per
   metric label, every compiled program of the engine),
2. jax's own per-callable trace cache (keyed on the pytree treedef —
   which includes Batch COLUMN NAMES and their order, columnar.py
   ``_batch_flatten``),
3. jax's persistent compilation cache on disk (config.py), keyed on
   the serialized HLO.

Plain structural fingerprints (the old ``_node_fingerprint`` keys)
miss on all three layers whenever the planner renames a symbol
(``l_quantity$3`` vs ``l_quantity$7`` for the same scan) or emits the
same projection with a different column order — identical programs,
full re-trace, full XLA recompile. This module fixes identity at the
root: a traceable node chain is REWRITTEN over canonical symbol names
(``c0, c1, ...`` in execution-order first use), producing

- a canonical **key** (the fingerprint of the canonicalized nodes) for
  the in-process caches and the hot-shape registry,
- canonical **nodes** the cached closure actually executes, so the
  traced jaxpr/HLO — and with it layers 2 and 3 — is byte-identical
  across renamed plans (the persistent cache is thereby effectively
  keyed on the canonical program too), and
- a per-plan **binding** that renames input batch columns to canonical
  names before the call and the output back after it.

Capacity buckets are deliberately ABSENT from the key: jax
specializes per input shape under one callable, and the power-of-two
bucketing of config.capacity_for already collapses minor cardinality
changes onto the same shapes. A literal VALUE is absent too: where a
comparison or an addition-like call has one (a constant, or a
column-free subtree the host folds to one value), the canonical node
holds a typed slot, ``rex.Param``, and the program takes the value as
an argument (exec/literals.py): two texts that differ only in literals
share one key, one trace and one compiled program, and the binding
carries each plan's values. What fixes a shape or a dictionary stays a
constant of the key (LIMIT and TopN counts, IN lists, LIKE patterns,
interval units, a literal no slot can hold); ``literal_key`` is what
the slots hold in one plan, for identities of a RESULT rather than of
a program (the result cache, co-batched queries).

Program NAMES (``program_name`` / ``named_jit``): every cached program
is jitted under a function named ``<kind>_<key8>`` — kind = the cache
the program lives in (with its role where one cache holds several:
``join_count``, ``join_expand``), key8 = 8 hex of the canonical key —
with a ``jax.named_scope`` of the same name inside, so the profiler's
``XLA Modules`` line reads ``jit_join_count_1a2b3c4d`` and every
operation's metadata names the program it belongs to. The name is a
function of the canonical key ONLY (no ``id()``, no counter, no path):
it is part of the lowered module, so the same query in two processes
must lower to byte-identical HLO or the persistent compile cache
misses. Programs without a canonical key are named ``<kind>_local``.
"""

from __future__ import annotations

import hashlib
import re
import threading
from dataclasses import replace as dc_replace
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from ..columnar import Batch
from ..config import CONFIG
from .literals import (LITERAL_SLOTS, LiteralBinding, LiteralSlot,
                       host_fold, literal_scope, slot_dtype,
                       unbind, value_slot_type)
from ..obs.metrics import (CACHE_PRESSURE_EVICTS, JIT_CACHE_LOOKUPS,
                           METRICS)
from ..plan.nodes import (Aggregate, AggregationNode, AssignUniqueIdNode,
                          FilterNode, LimitNode, MarkDistinctNode,
                          OffsetNode, PlanNode, ProjectNode,
                          RemoteSourceNode, SampleNode, SortKey,
                          SortNode, TopNNode, WindowFunction, WindowNode)
from ..rex import (VOLATILE_FNS, Call, CaseExpr, Cast, Const, InputRef,
                   Lambda, Param, RowExpr, expr_volatile, walk)
from ..types import VARCHAR, is_string


_ADDRESS = re.compile(r" at 0x[0-9a-fA-F]+")


def program_name(kind: str, key) -> str:
    """``<kind>_<key8>``; ``<kind>_local`` for a program whose key is
    per-process (``key=None``: plans outside the canonical subset)."""
    if key is None:
        return f"{kind}_local"
    # a default object repr inside a key would carry an address: never
    # let one reach a name that is baked into the compiled module
    text = _ADDRESS.sub("", repr(key))
    return f"{kind}_{hashlib.sha256(text.encode()).hexdigest()[:8]}"


def named_jit(fn, kind: str, key, **jit_kwargs):
    """``jax.jit(fn)`` under the program's name. The returned callable
    carries ``program`` = ``<kind>:<key8>`` for the dispatch spans
    (exec/executor.py ``device_call``)."""
    name = program_name(kind, key)

    def program(*args, **kwargs):
        # a bound input batch carries the program's literal vectors
        # (exec/literals.py): they are arguments, the slots read them
        args, literals = unbind(args)
        with jax.named_scope(name), literal_scope(literals):
            return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    jitted = jax.jit(program, **jit_kwargs)
    jitted.program = f"{kind}:{name[len(kind) + 1:]}"
    return jitted


# what a trace raises where a program touches host-only evaluation
# (row-materializing string functions, host-side frame math): the
# caller denies the key and runs eagerly from then on
UNTRACEABLE = (jax.errors.TracerArrayConversionError,
               jax.errors.ConcretizationTypeError)

_M_JIT_EVICT = METRICS.counter(
    "trino_tpu_jit_cache_evictions_total",
    "Structural jitted-program cache entries evicted at capacity "
    "(TRINO_TPU_JIT_CACHE_ENTRIES)")


class ProgramCache:
    """Cross-query cache of jitted programs: the ONE place that says
    which compiled program serves a (bucket, key), how many are kept
    and which are refused. One bucket per label of
    ``trino_tpu_jit_cache_total{cache}``, keyed by canonical program
    key, each keeping ``CONFIG.jit_cache_entries`` programs, oldest out
    first; beside each bucket the keys it refuses (programs whose
    trace touched host-only evaluation). Reference analog: the
    generated-class caches of sql/gen/ExpressionCompiler.java (keyed
    on RowExpression trees). Query threads, worker task threads and
    the pre-warm thread (exec/aot.py) share it: every mutation is
    under the one lock (this module is on the race-lint cross-module
    allowlist, analysis/lint.py), a lookup takes none."""

    BUCKETS = ("chain", "stream", "ragged", "join", "window",
               "streamjoin", "repartition", "spmd", "scan")
    # what memory pressure halves (exec/executor.py
    # evict_cache_pressure): the plan-program buckets
    SHED = ("chain", "stream", "ragged")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._programs: Dict[str, Dict[tuple, object]] = {
            b: {} for b in self.BUCKETS}
        self._denied: Dict[str, set] = {b: set() for b in self.BUCKETS}

    def program(self, bucket: str, key, build: Callable[[], Callable],
                kind: str, name_key):
        """``(jitted, hit)`` for ``key`` in ``bucket``, or None where
        the key is denied. On a miss ``build()`` gives the function,
        which is jitted under the name of (``kind``, ``name_key``) and
        kept. ``key`` must name everything the function closes over;
        ``None`` means it cannot be named: the program is built for
        this call alone and nothing is counted."""
        if key is None:
            return named_jit(build(), kind, name_key), False
        if key in self._denied[bucket]:
            return None
        jitted = self._programs[bucket].get(key)
        hit = jitted is not None
        JIT_CACHE_LOOKUPS.inc_at((bucket, "hit" if hit else "miss"))
        if not hit:
            jitted = self.put(bucket, key,
                              named_jit(build(), kind, name_key))
        return jitted, hit

    def put(self, bucket: str, key, jitted):
        """Keep ``jitted`` under ``key`` and return what the slot holds:
        the program that got there first where two threads built the
        same one (its trace is the one already paid for)."""
        programs = self._programs[bucket]
        with self._lock:
            kept = programs.get(key)
            if kept is not None:
                return kept
            limit = max(int(CONFIG.jit_cache_entries), 1)
            while len(programs) >= limit:
                programs.pop(next(iter(programs)))
                _M_JIT_EVICT.inc()
            programs[key] = jitted
        return jitted

    def resident(self, bucket: str, key) -> bool:
        with self._lock:
            return key in self._programs[bucket]

    def deny(self, bucket: str, key) -> None:
        """Refuse ``key`` from now on and drop its program."""
        with self._lock:
            self._programs[bucket].pop(key, None)
            self._denied[bucket].add(key)

    def denied(self, bucket: str, key) -> bool:
        return key in self._denied[bucket]

    def shed(self) -> int:
        """Memory-pressure relief: drop the oldest half of each
        ``SHED`` bucket (entry sizes are opaque, so the relief is
        entry-counted; the persistent XLA cache backs recompiles)."""
        dropped = 0
        with self._lock:
            for bucket in self.SHED:
                programs = self._programs[bucket]
                for _ in range(len(programs) // 2):
                    programs.pop(next(iter(programs)))
                    dropped += 1
        if dropped:
            CACHE_PRESSURE_EVICTS.inc(dropped, cache="jit")
        return dropped

    def clear(self, bucket: Optional[str] = None) -> None:
        """Forget programs and refusals (a fresh process, for tests)."""
        with self._lock:
            for b in (bucket,) if bucket is not None else self.BUCKETS:
                self._programs[b].clear()
                self._denied[b].clear()


PROGRAMS = ProgramCache()


class _NotCanonical(Exception):
    """Node/expression outside the canonicalizable subset (volatile
    calls, unknown node kinds): callers fall back to identity keys."""


class _SymbolMap:
    """Deterministic symbol renaming: first use (in execution order)
    wins ``c<i>``. The map is a bijection — two distinct source
    symbols can never alias one canonical name. Beside it the
    program's literal slots, in the order the canonicalizer meets
    them, and the symbols the chain itself produces (a varchar slot
    codes against an INPUT lane's dictionary only)."""

    __slots__ = ("names", "slots", "produced")

    def __init__(self) -> None:
        self.names: Dict[str, str] = {}
        self.slots: List[LiteralSlot] = []
        self.produced: set = set()

    def sym(self, name: str) -> str:
        got = self.names.get(name)
        if got is None:
            got = f"c{len(self.names)}"
            self.names[name] = got
        return got

    def slot(self, e: RowExpr, code_of: Optional[str] = None
             ) -> Optional[Param]:
        """A new slot for the literal value ``e``, or None where the
        program's ``LITERAL_SLOTS`` are taken (the literal stays
        baked)."""
        if len(self.slots) >= LITERAL_SLOTS:
            return None
        dtype = slot_dtype(e.type)
        index = sum(1 for s in self.slots if s.dtype == dtype)
        self.slots.append(LiteralSlot(dtype, index, e, code_of))
        return Param(index, e.type, dtype,
                     None if code_of is None else self.sym(code_of))


# calls whose literal operands are VALUES (no handler reads them as a
# constant): comparisons and the exact or addition-like arithmetic.
# Division stays baked (the chip's float64 division is approximate).
_SLOTTED_CALLS = frozenset({
    "=", "<>", "<", "<=", ">", ">=", "+", "-", "*",
    "decimal_+", "decimal_-", "date_add_interval", "date_sub_interval"})


def _string_input(e: RowExpr, m: _SymbolMap) -> Optional[InputRef]:
    """The input lane a varchar operand reads as it lies (a plain
    reference, or a string-to-string cast of one: the cast keeps its
    codes and dictionary), or None."""
    while isinstance(e, Cast) and is_string(e.type) \
            and is_string(e.arg.type):
        e = e.arg
    if isinstance(e, InputRef) and is_string(e.type) \
            and e.name not in m.produced:
        return e
    return None


def _literal_operand(e: RowExpr) -> bool:
    """A constant or a column-free subtree that folds on the host to
    one value of a slot type."""
    if isinstance(e, Const):
        return e.value is not None and value_slot_type(e.type)
    if not isinstance(e, (Call, Cast)) or not value_slot_type(e.type) \
            or expr_volatile(e):
        return False
    if any(isinstance(x, InputRef) for x in walk(e)):
        return False
    return host_fold(e) is not None


def _canon_operands(e: Call, m: _SymbolMap) -> Tuple[RowExpr, ...]:
    if e.fn in ("=", "<>") and len(e.args) == 2:
        # a varchar literal against a dictionary lane: the slot holds
        # its code, compared with the lane's codes as they lie, so the
        # literal's length (which decides whether the planner casts the
        # lane) leaves the program as it is
        for i, a in enumerate(e.args):
            if not (isinstance(a, Const) and isinstance(a.value, str)
                    and is_string(a.type)):
                continue
            lane = _string_input(e.args[1 - i], m)
            p = None if lane is None else m.slot(
                Const(a.value, VARCHAR), lane.name)
            if p is not None:
                ref = _canon_expr(lane, m)
                return (ref, p) if i else (p, ref)
    out = []
    for a in e.args:
        p = m.slot(a) if _literal_operand(a) else None
        out.append(p if p is not None else _canon_expr(a, m))
    return tuple(out)


def _canon_expr(e: RowExpr, m: _SymbolMap) -> RowExpr:
    if isinstance(e, InputRef):
        return InputRef(m.sym(e.name), e.type)
    if isinstance(e, Const):
        return e
    if isinstance(e, Call):
        if e.fn in VOLATILE_FNS:
            raise _NotCanonical(e.fn)
        if e.fn in _SLOTTED_CALLS:
            return Call(e.fn, _canon_operands(e, m), e.type)
        return Call(e.fn, tuple(_canon_expr(a, m) for a in e.args),
                    e.type)
    if isinstance(e, Cast):
        return Cast(_canon_expr(e.arg, m), e.type, e.safe)
    if isinstance(e, CaseExpr):
        return CaseExpr(tuple((_canon_expr(c, m), _canon_expr(v, m))
                              for c, v in e.whens),
                        None if e.default is None
                        else _canon_expr(e.default, m), e.type)
    if isinstance(e, Lambda):
        # lambda params are fresh symbols referenced via InputRef in
        # the body — they rename through the same map
        return Lambda(tuple(m.sym(p) for p in e.params),
                      _canon_expr(e.body, m), e.type)
    raise _NotCanonical(type(e).__name__)


def _canon_aggregate(a: Aggregate, m: _SymbolMap) -> Aggregate:
    return Aggregate(
        a.kind,
        None if a.argument is None else m.sym(a.argument),
        a.type, a.distinct,
        None if a.mask is None else m.sym(a.mask),
        None if a.argument2 is None else m.sym(a.argument2),
        a.param)


def _canon_node(nd: PlanNode, m: _SymbolMap) -> PlanNode:
    """Rebuild one chain node over canonical symbols (source link left
    untouched — chain execution dispatches per node, never through
    ``.source``)."""
    if isinstance(nd, FilterNode):
        return dc_replace(nd, predicate=_canon_expr(nd.predicate, m))
    if isinstance(nd, ProjectNode):
        # input symbols rename before output symbols: every InputRef of
        # every assignment maps first, THEN the assignment targets —
        # keeps pass-through projections (x -> x) idempotent
        exprs = {s: _canon_expr(e, m) for s, e in nd.assignments.items()}
        m.produced.update(s for s, e in nd.assignments.items()
                          if e != InputRef(s, e.type))
        return dc_replace(nd, assignments={m.sym(s): e
                                           for s, e in exprs.items()})
    if isinstance(nd, (SampleNode, LimitNode, OffsetNode)):
        return nd
    if isinstance(nd, SortNode):
        return dc_replace(nd, keys=tuple(
            SortKey(m.sym(k.symbol), k.ascending, k.nulls_first)
            for k in nd.keys))
    if isinstance(nd, TopNNode):
        return dc_replace(nd, keys=tuple(
            SortKey(m.sym(k.symbol), k.ascending, k.nulls_first)
            for k in nd.keys))
    if isinstance(nd, AssignUniqueIdNode):
        m.produced.add(nd.symbol)
        return dc_replace(nd, symbol=m.sym(nd.symbol))
    if isinstance(nd, MarkDistinctNode):
        m.produced.add(nd.marker)
        return dc_replace(nd, keys=tuple(m.sym(k) for k in nd.keys),
                          marker=m.sym(nd.marker))
    if isinstance(nd, AggregationNode):
        if nd.group_id_symbol is not None:
            raise _NotCanonical("grouping-set aggregation")
        m.produced.update(nd.aggregates)
        return dc_replace(
            nd,
            group_keys=tuple(m.sym(k) for k in nd.group_keys),
            aggregates={m.sym(out): _canon_aggregate(a, m)
                        for out, a in nd.aggregates.items()})
    if isinstance(nd, WindowNode):
        # inputs before outputs (same discipline as ProjectNode):
        # partition/order keys and per-function argument symbols map
        # first, then the function output symbols
        part = tuple(m.sym(s) for s in nd.partition_by)
        order = tuple(SortKey(m.sym(k.symbol), k.ascending,
                              k.nulls_first) for k in nd.order_by)
        fns = {out: _canon_window_fn(f, m)
               for out, f in nd.functions.items()}
        m.produced.update(nd.functions)
        return dc_replace(nd, partition_by=part, order_by=order,
                          functions={m.sym(out): f
                                     for out, f in fns.items()})
    raise _NotCanonical(type(nd).__name__)


def _canon_window_fn(f: WindowFunction, m: _SymbolMap) -> WindowFunction:
    return dc_replace(
        f,
        argument=None if f.argument is None else m.sym(f.argument),
        offset=None if f.offset is None else m.sym(f.offset),
        default=None if f.default is None else m.sym(f.default))


def node_fingerprint(nd: PlanNode) -> Optional[tuple]:
    """Serialize every field a jitted evaluation of this node depends
    on (row expressions are frozen dataclasses — repr() is total).
    Returns None for node types outside the whitelist or volatile
    expressions; callers fall back to per-query identity keys. A
    collision between genuinely different plans would reuse the wrong
    program, so any new field on these nodes MUST be added here."""
    from ..rex import expr_volatile
    if isinstance(nd, FilterNode):
        if expr_volatile(nd.predicate):
            return None
        return ("F", repr(nd.predicate))
    if isinstance(nd, ProjectNode):
        if any(expr_volatile(e) for e in nd.assignments.values()):
            return None
        return ("P", tuple((s, repr(e))
                           for s, e in nd.assignments.items()))
    if isinstance(nd, SampleNode):
        return ("S", nd.method, nd.ratio)
    if isinstance(nd, LimitNode):
        return ("L", nd.count, nd.partial)
    if isinstance(nd, OffsetNode):
        return ("O", nd.count)
    if isinstance(nd, SortNode):
        return ("So", nd.keys)
    if isinstance(nd, TopNNode):
        return ("T", nd.count, nd.keys, nd.step)
    if isinstance(nd, AssignUniqueIdNode):
        return ("U", nd.symbol)
    if isinstance(nd, MarkDistinctNode):
        return ("M", nd.marker, nd.keys)
    if isinstance(nd, AggregationNode):
        return ("A", tuple(nd.group_keys), nd.step, nd.group_id_symbol,
                tuple((out, a.kind, a.argument, a.argument2, a.mask,
                       a.distinct, a.param, repr(a.type))
                      for out, a in nd.aggregates.items()))
    if isinstance(nd, WindowNode):
        return ("W", tuple(nd.partition_by), nd.order_by,
                tuple((out, f.kind, f.argument, repr(f.type),
                       f.frame_unit, f.frame_start, f.frame_end,
                       f.offset, f.default, f.frame_start_value,
                       f.frame_end_value)
                      for out, f in nd.functions.items()))
    return None


class Binding:
    """Per-plan rename shim around one canonical program: actual input
    columns -> canonical names before the call, canonical output names
    -> this plan's names after it. Columns the chain never references
    (pass-through lanes under a filter) extend the map in sorted
    original-name order — deterministic for a given input schema, so
    every split of one scan binds identically. The plan's literal
    values ride the renamed input batch (exec/literals.py): the
    program's ``Param`` slots read them."""

    __slots__ = ("fwd", "inv", "literals")

    def __init__(self, mapping: Dict[str, str],
                 columns: Sequence[str],
                 slots: Sequence[LiteralSlot] = ()) -> None:
        self.fwd = dict(mapping)
        for name in sorted(c for c in columns if c not in self.fwd):
            self.fwd[name] = f"x{len(self.fwd)}"
        self.inv = {v: k for k, v in self.fwd.items()}
        self.literals = LiteralBinding(slots)

    def rename_in(self, b: Batch) -> Batch:
        cols = sorted(b.columns, key=lambda c: self.fwd[c])
        return self.literals.bind(
            b, Batch({self.fwd[c]: b.columns[c] for c in cols},
                     b.num_rows))

    def rename_out(self, b: Batch) -> Batch:
        return Batch({self.inv.get(s, s): c
                      for s, c in b.columns.items()}, b.num_rows)


class CanonicalProgram:
    """A canonicalized traceable node stack (top-down order) + its
    cache key, the plan's symbol map and its literal slots."""

    __slots__ = ("key", "nodes", "mapping", "slots")

    def __init__(self, key: tuple, nodes: List[PlanNode],
                 mapping: Dict[str, str],
                 slots: Sequence[LiteralSlot] = ()) -> None:
        self.key = key
        self.nodes = nodes          # top-down, like the executor chain
        self.mapping = mapping      # original symbol -> canonical
        self.slots = tuple(slots)   # the literals the key leaves out

    @property
    def literal_key(self) -> tuple:
        """What the slots hold in THIS plan: with ``key``, the identity
        of a result (the result cache, co-batched queries), where
        ``key`` alone is the identity of a program."""
        return tuple((repr(s.expr), s.code_of) for s in self.slots)

    def binding(self, b: Batch) -> Binding:
        return Binding(self.mapping, list(b.columns), self.slots)

    def wire_fragment(self, input_schema: Dict[str, object]) -> dict:
        """Serialize the canonical stack as a plan fragment rooted in
        its top node over a schema-carrying RemoteSourceNode leaf —
        the hot-shape registry's transport form (plan/serde.py), which
        a pre-warming worker decodes back into the exact closure the
        executor would build (exec/aot.py)."""
        from ..plan.serde import to_jsonable
        body: PlanNode = RemoteSourceNode((), dict(input_schema),
                                          "gather")
        for nd in reversed(self.nodes):
            body = dc_replace(nd, source=body)
        return to_jsonable(body)


# ---- ragged multi-query batching (exec/taskexec.py RaggedBatcher +
# exec/executor.py _try_ragged_chain) ---------------------------------

# per-row provenance lane of a ragged batch: which co-batched query
# (by part index) owns the row. Prefixed so it can never collide with
# a canonical (c<i>) or extension (x<i>) symbol.
RAGGED_LANE = "__rq"


def ragged_nodes(nodes_top_down: Sequence[PlanNode]) -> List[PlanNode]:
    """Thread the provenance lane through a canonical chain: the lane
    column rides every FilterNode for free (filter_batch gathers ALL
    columns), but a ProjectNode drops unreferenced columns — so each
    one re-emits the lane as a pass-through assignment. Callers gate
    batchability to Filter/Project chains (Limit/Sort/TopN/Sample have
    per-query cross-row semantics that break under concatenation)."""
    from ..types import BIGINT
    out: List[PlanNode] = []
    for nd in nodes_top_down:
        if isinstance(nd, ProjectNode):
            out.append(dc_replace(nd, assignments={
                **nd.assignments,
                RAGGED_LANE: InputRef(RAGGED_LANE, BIGINT)}))
        else:
            out.append(nd)
    return out


def peel_wire_fragment(root: PlanNode) -> Tuple[List[PlanNode], Dict]:
    """Inverse of ``wire_fragment``: (top-down node stack, input
    schema) from a decoded fragment."""
    nodes: List[PlanNode] = []
    nd = root
    while not isinstance(nd, RemoteSourceNode):
        nodes.append(nd)
        nd = nd.source
    return nodes, dict(nd.schema)


def canonicalize_nodes(nodes_top_down: Sequence[PlanNode]
                       ) -> Optional[CanonicalProgram]:
    """Canonicalize a traceable node stack (top-down, the executor's
    chain order — for the streaming-aggregation program the
    AggregationNode leads). Returns None when any node or expression
    falls outside the canonical subset; callers keep per-query
    identity keys for those."""
    m = _SymbolMap()
    canon: List[PlanNode] = []
    try:
        # execution order (bottom-up): input symbols take the low
        # canonical indices, so the data-flow reading of c0.. matches
        # what the program consumes first
        for nd in reversed(list(nodes_top_down)):
            canon.append(_canon_node(nd, m))
    except _NotCanonical:
        return None
    canon.reverse()
    fps = tuple(node_fingerprint(n) for n in canon)
    if any(f is None for f in fps):
        return None
    return CanonicalProgram(fps, canon, dict(m.names), m.slots)
