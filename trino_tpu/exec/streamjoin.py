"""Beyond-HBM execution: morsel-streamed operators with double-buffered
host->device transfer.

Reference parity: the reference streams pages through operators by
construction (operator/Driver.java's pull loop never materializes a
table), so "working set exceeds memory" is a spill concern there, not
an executor-mode concern. This engine's whole-column execution model
(columnar.py) materializes an operator's entire input in device
memory — which caps query scale at one chip's HBM (q18 at sf100 was
not attempted: ~34GB of q18 lanes exceeds single-chip HBM).

This module is the morsel-driven answer (tensor-runtime query
processing, PAPERS arxiv 2203.01877: operator-as-tensor-program chunk
streaming): when a probe/scan side's full-materialization estimate
exceeds the memory budget, the operator streams fixed-capacity chunks
instead of materializing —

- **hash join**: the build side is materialized and sorted ONCE in
  device memory (ops/join.py build_side — the engine's "hash table");
  probe-side chunks then stream through one jitted
  count-and-expand program per canonical chunk capacity, with
  ``jax.device_put`` on chunk N+1 issued while the program runs on
  chunk N (the async-copy double-buffering of SNIPPETS [1]/[3], on the
  host->HBM edge). Match outputs spill to host per chunk (the existing
  oversized-join discipline).
- **scan -> filter -> project chains**: chunks stream through the
  canonical chain program (exec/progkey.py — the same program the
  unstreamed chain path compiles), outputs host-concatenated.
- **streaming aggregation** (exec/executor.py
  ``_try_streaming_aggregation``) reuses the chunk source + the
  double-buffered loop here, with periodic partial folding so the
  accumulated partial set stays bounded.

Every chunk shares ONE canonical capacity, so every chunk hits the same
compiled program (jax specializes per shape under one callable; the
first chunk traces, the rest are dispatches). Chunk capacity comes
from ``stream_chunk_rows`` (session) / ``TRINO_TPU_STREAM_CHUNK_ROWS``,
or is auto-derived from the memory budget when 0.

Memory governance: a streamed operator reserves its **streamed peak**
(build state + 2 chunk buffers + 1 output chunk) instead of the
full-materialization estimate — the PR 10 cluster pool sees what the
operator actually holds, so the low-memory killer stops shooting
queries streaming can serve.

Limits (fall back to the materialized path): FULL joins, string
columns CREATED by the probe chain (a chain-minted dictionary per
chunk would re-trace every chunk; strings read off the scan stream
through the per-stream canonical layout of ``_StreamDictEncoder``),
nested (ARRAY/MAP/ROW) scan columns, and semi joins.

Shared-runtime code: the probe programs (bucket "streamjoin") are
inserted by query executor threads and the worker pre-warm thread
concurrently — through the one program cache and its lock
(exec/progkey.py PROGRAMS; this module is on the race-lint
cross-module allowlist, analysis/lint.py)."""

from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Optional, Sequence, \
    Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Batch, Column, StringDictionary, empty_batch
from ..config import CONFIG, capacity_for
from ..obs.metrics import (METRICS, STREAM_CHUNKS, STREAM_H2D_BYTES,
                           STREAM_OVERLAPPED)
from ..plan.nodes import (FilterNode, JoinNode, PlanNode, ProjectNode,
                          RemoteSourceNode, TableScanNode)
from ..rex import Call as _RCall, InputRef, and_all
from ..types import BOOLEAN, DecimalType
from .progkey import PROGRAMS, UNTRACEABLE


# --------------------------------------------------------------------------
# engagement: when does an operator stream?
# --------------------------------------------------------------------------

def chunk_rows_setting(session) -> int:
    """``stream_chunk_rows``: > 0 forces streaming at that chunk size
    (tests/bench pin the capacity); 0 = auto-engage on budget breach;
    < 0 disables streaming entirely (the operator escape hatch — the
    engine falls back to the materialized path and its memory
    errors)."""
    try:
        return int(session.get("stream_chunk_rows"))
    except KeyError:
        return 0


def memory_budget(ex) -> int:
    """The effective streaming budget: the per-node limit, tightened by
    whatever cluster governance binds this query (query_max_memory /
    group soft limit / pool size via QueryMemoryContext.budget_bytes).
    A query that would breach the POOL un-streamed must engage
    streaming too — the pool killer only sees reservations, and the
    whole point is to reserve the streamed peak instead."""
    limit = int(ex.session.get("query_max_memory_per_node"))
    mem = getattr(ex.session, "memory", None)
    fn = getattr(mem, "budget_bytes", None)
    if callable(fn):
        try:
            b = fn()
            if b:
                limit = min(limit, int(b))
        except Exception:       # noqa: BLE001 — governance is advisory
            pass
    return limit


def scan_chain(node: PlanNode):
    """(chain, scan) when ``node`` heads a Filter/Project-only chain
    over a TableScanNode — the streamable shape (row-local operators
    only: Sample is position-dependent, Limit/Sort are global)."""
    chain: List[PlanNode] = []
    cur = node
    while isinstance(cur, (FilterNode, ProjectNode)):
        chain.append(cur)
        cur = cur.source
    if not isinstance(cur, TableScanNode):
        return None
    return chain, cur


def _col_streamable(t) -> bool:
    name = str(t.name)
    return not (name.startswith("array(") or name.startswith("map(")
                or name.startswith("row("))


def _split_connector(ex, scan: TableScanNode):
    """The scan's connector when it supports split iteration (the
    chunk source needs get_splits/read_split); None otherwise —
    coordinator-state catalogs (system.runtime, information_schema)
    never stream."""
    try:
        conn = ex.catalogs.connector(scan.handle.catalog)
    except Exception:           # noqa: BLE001
        return None
    if not hasattr(conn, "get_splits") \
            or not hasattr(conn, "read_split"):
        return None
    return conn


def stream_gate(ex, scan: TableScanNode):
    """The engagement preconditions every streamed operator shares:
    None when streaming is impossible for this scan (no split-capable
    connector, unstreamable column types, streaming disabled); else
    (forced chunk rows, memory budget, scan estimate). Operator-
    specific rules (the join's remaining-after-build check, the
    chain/agg est-vs-budget comparison) layer on top — ONE gate, so
    the three streamed operators cannot drift."""
    if _split_connector(ex, scan) is None:
        return None
    if not all(_col_streamable(t) for t in scan.schema.values()):
        return None
    forced = chunk_rows_setting(ex.session)
    if forced < 0:
        return None             # streaming disabled for this session
    return forced, memory_budget(ex), scan_estimate(ex, scan)


def scan_estimate(ex, scan: TableScanNode) -> Optional[int]:
    """Full-materialization estimate of the scan in bytes — the SAME
    rows x lanes x 8 figure ``_exec_TableScanNode`` would reserve, so
    streaming engages exactly where the reserve would raise. None when
    the connector cannot estimate (pushed-down constraint/limit)."""
    try:
        conn = ex.catalogs.connector(scan.handle.catalog)
    except Exception:           # noqa: BLE001
        return None
    if scan.handle.constraint is not None or scan.handle.limit is not None:
        return None
    if not hasattr(conn, "table_row_count") \
            or not hasattr(conn, "get_splits"):
        return None
    rows = conn.table_row_count(scan.handle)
    if not rows:
        return None
    return int(rows) * max(len(set(scan.assignments.values())), 1) * 8


def _row_bytes(schema: Dict[str, object]) -> int:
    """Per-row device bytes of one chunk of this schema (data lane +
    validity + the Int128/tz hi lane where the type carries one)."""
    total = 0
    for t in schema.values():
        total += 9              # 8B data + 1B validity
        if (isinstance(t, DecimalType) and not t.is_short) \
                or str(t.name).endswith("with time zone"):
            total += 8
    return max(total, 9)


def _pick_chunk_capacity(forced: int, avail_bytes: int,
                         per_row: int) -> Optional[int]:
    """Canonical chunk capacity: the forced setting, or the largest
    power of two whose streamed footprint fits ``avail_bytes``.
    None when not even the minimum chunk fits."""
    if forced > 0:
        return capacity_for(min(forced, CONFIG.max_batch_rows),
                            minimum=8)
    cap = 8
    while cap * 2 * per_row <= avail_bytes \
            and cap * 2 <= CONFIG.max_batch_rows:
        cap *= 2
    if cap * per_row > avail_bytes:
        return None
    return cap


# --------------------------------------------------------------------------
# chunk source: host-resident fixed-capacity morsels off the scan
# --------------------------------------------------------------------------

def _slice_chunk(raw: Batch, assignments: Dict[str, str], lo: int,
                 hi: int, cap: int) -> Batch:
    """Rows [lo, hi) of the split, padded to the canonical chunk
    capacity, renamed to the scan's output symbols. Lanes land as host
    numpy (np.asarray on a device lane downloads — the streamed path
    deliberately stages through host RAM, that is the point)."""
    cols: Dict[str, Column] = {}
    n = hi - lo
    for sym, col in assignments.items():
        c = raw.column(col)

        def cut(lane):
            a = np.asarray(lane)[lo:hi]
            if n < cap:
                a = np.concatenate(
                    [a, np.zeros(cap - n, dtype=a.dtype)])
            return a

        cols[sym] = Column(
            c.type, cut(c.data),
            None if c.valid is None else cut(c.valid),
            c.dictionary,
            None if c.data2 is None else cut(c.data2))
    return Batch(cols, n)


def host_scan_chunks(ex, scan: TableScanNode, chunk_cap: int
                     ) -> Iterator[Batch]:
    """Yield host chunks of the scan at the canonical capacity,
    respecting the worker's split share (``ex.scan_partition``)."""
    conn = ex.catalogs.connector(scan.handle.catalog)
    columns = sorted(set(scan.assignments.values()))
    par = int(ex.session.get("task_concurrency")) or 1
    splits = conn.get_splits(scan.handle, par)
    if ex.scan_partition is not None:
        part, nparts = ex.scan_partition
        splits = [s for i, s in enumerate(splits)
                  if i % nparts == part]
    for sp in splits:
        raw = ex._read_split(conn, sp, columns)
        n = raw.num_rows_host()
        # stage the split on HOST once: np.asarray per chunk over a
        # device-resident lane would re-download the whole split per
        # chunk. The split staging buffer lives in host RAM (the spill
        # medium — exempt from the device budget); device-side
        # generator connectors that materialize splits directly in HBM
        # remain the device round's open item (ROADMAP item 2)
        raw = Batch(
            {name: Column(
                c.type, np.asarray(c.data),
                None if c.valid is None else np.asarray(c.valid),
                c.dictionary,
                None if c.data2 is None else np.asarray(c.data2))
             for name, c in raw.columns.items()}, n)
        for lo in range(0, n, chunk_cap):
            yield _slice_chunk(raw, scan.assignments, lo,
                               min(lo + chunk_cap, n), chunk_cap)


def _batch_nbytes(b: Batch) -> int:
    total = 0
    for c in b.columns.values():
        for lane in (c.data, c.valid, c.data2):
            if lane is not None:
                total += int(np.asarray(lane).nbytes)
    return total


def _h2d(b: Batch) -> Batch:
    """Upload one chunk's lanes (jax.device_put is asynchronous — the
    DMA overlaps whatever the device is already running)."""
    cols = {}
    for s, c in b.columns.items():
        cols[s] = Column(
            c.type, jax.device_put(c.data),
            None if c.valid is None else jax.device_put(c.valid),
            c.dictionary,
            None if c.data2 is None else jax.device_put(c.data2))
    return Batch(cols, b.num_rows)


# per-streamed-operator cap on stream_chunk trace spans (the tail is
# summarized): span trees ride worker task-status JSON, so unbounded
# per-chunk spans would make status size linear in chunk count
_MAX_CHUNK_SPANS = 32


def run_streamed(ex, op: str, host_iter: Iterable[Batch],
                 dispatch, collect) -> Tuple[int, int]:
    """The double-buffered chunk loop shared by every streamed
    operator. Per chunk: ``dispatch(device_chunk, i)`` launches the
    compute (async under jax dispatch), then chunk i+1's host prep +
    ``jax.device_put`` are issued while that compute is in flight, and
    only then ``collect(result, i)`` host-syncs chunk i's output — the
    transfer for the NEXT chunk rides under the CURRENT chunk's
    compute (the double-buffer contract). Returns (chunks, h2d bytes)
    and records them in the stream metrics + the executor's per-query
    counters + the current stats frame."""
    it = iter(host_iter)
    # device-timing suppression: _jit_call's block-until-ready device
    # attribution would serialize this loop's double-buffered overlap
    # — streamed dispatches run unsynced (wall-only spans)
    ex._stream_depth += 1
    try:
        return _stream_loop(ex, op, it, dispatch, collect)
    finally:
        ex._stream_depth -= 1


def _stream_loop(ex, op: str, it, dispatch,
                 collect) -> Tuple[int, int]:
    """The body of ``run_streamed`` (split out so the device-timing
    suppression wraps it in one try/finally)."""
    import time as _time
    from contextlib import nullcontext
    trace = ex.trace
    host = next(it, None)
    nchunks = h2d = overlapped = 0
    cur = None
    if host is not None:
        h2d += _batch_nbytes(host)
        cur = _h2d(host)
    while cur is not None:
        # cooperative cancellation/deadline at CHUNK granularity: a
        # streamed operator is one plan node running for thousands of
        # chunks, so the between-plan-nodes check in Executor.execute
        # alone would let a killed/deadlined query stream to the end
        cancel = getattr(ex.session, "cancel", None)
        if cancel is not None and cancel.is_set():
            from .executor import QueryError
            raise QueryError("Query was canceled")
        deadline = getattr(ex.session, "deadline", None)
        if deadline is not None and _time.monotonic() > deadline:
            from .executor import QueryError
            raise QueryError(
                "Query exceeded the maximum run time "
                "(query_max_run_time)",
                error_name="EXCEEDED_TIME_LIMIT")
        yld = getattr(ex.session, "split_yield", None)
        if yld is not None:
            # shared split scheduler (exec/taskexec.py): a streamed
            # chunk is the quantum — a thousand-chunk stream yields
            # its runner slot to higher-priority queries per chunk
            # instead of owning the worker to completion
            yld()
        # per-chunk spans are capped: a million-chunk stream must not
        # hold (and ship, via worker task status) a Span per chunk —
        # the tail is summarized in one stream_tail span below
        cm = (trace.span("stream_chunk", op=op, chunk=nchunks)
              if trace is not None and nchunks < _MAX_CHUNK_SPANS
              else nullcontext())
        with cm:
            out = dispatch(cur, nchunks)
            nxt_host = next(it, None)
            nxt = None
            if nxt_host is not None:
                h2d += _batch_nbytes(nxt_host)
                nxt = _h2d(nxt_host)        # overlaps chunk N's compute
                overlapped += 1
            collect(out, nchunks)
        cur = nxt
        nchunks += 1
    if trace is not None and nchunks > _MAX_CHUNK_SPANS:
        now = _time.perf_counter()
        trace.record("stream_tail", now, now, op=op,
                     elided_chunks=nchunks - _MAX_CHUNK_SPANS)
    if nchunks:
        STREAM_CHUNKS.inc(nchunks, op=op)
        STREAM_H2D_BYTES.inc(h2d)
        if overlapped:
            STREAM_OVERLAPPED.inc(overlapped)
        ex.stream_chunks += nchunks
        ex.stream_h2d_bytes += h2d
        if ex.collect_stats and ex._frames:
            frame = ex._frames[-1]
            frame["stream_chunks"] = \
                frame.get("stream_chunks", 0) + nchunks
            frame["stream_h2d"] = frame.get("stream_h2d", 0) + h2d
    return nchunks, h2d


def agg_chunk_capacity(ex, scan: TableScanNode) -> Optional[int]:
    """Chunk capacity for the streaming-aggregation path
    (exec/executor.py ``_try_streaming_aggregation``), or None when
    chunking should not engage (fits the budget, unstreamable
    columns, or not even a minimal chunk fits)."""
    gate = stream_gate(ex, scan)
    if gate is None:
        return None
    forced, budget, est = gate
    if forced <= 0 and (est is None or est <= budget):
        return None
    # 2 in-flight chunks + the bounded partial fold window (~8 chunk-
    # capacity partials of at most the input's lane width)
    per_row = 10 * _row_bytes(scan.schema)
    return _pick_chunk_capacity(forced, budget, per_row)


# --------------------------------------------------------------------------
# the chain program (shared by streamed chains and streamed join probes)
# --------------------------------------------------------------------------

def make_chain_runner(ex, chain: Sequence[PlanNode]):
    """callable(Batch) -> Batch applying the Filter/Project chain
    bottom-up over one chunk. Under fragment_jit the closure executes
    the CANONICAL node stack through the cross-query chain cache
    (exec/progkey.py — the same program, and the same cache slot, the
    unstreamed chain path compiles), so streamed chunks amortize with
    everything else; otherwise eager per chunk. Also returns a
    recorder that registers the chunk shape with the hot-shape
    registry once (so pre-warming workers AOT-compile the chunk-sized
    chain program too)."""
    if not chain:
        return (lambda b: b), (lambda b: None)
    chain = list(chain)

    def eager(b: Batch) -> Batch:
        for nd in reversed(chain):
            b = ex._dispatch_apply(nd, b)
        return b

    if not ex.fragment_jit:
        return eager, (lambda b: None)
    from .progkey import canonicalize_nodes
    canon = canonicalize_nodes(chain)
    if canon is None:
        return eager, (lambda b: None)
    key = canon.key
    state = {"binding": None, "prog": None}

    def run(b: Batch) -> Batch:
        if state["prog"] is None:       # one lookup per operator
            state["prog"] = ex._chain_program(canon) or (None, False)
        jitted, hit = state["prog"]
        if jitted is None:              # denied
            return eager(b)
        if state["binding"] is None:
            state["binding"] = canon.binding(b)
        binding = state["binding"]
        try:
            out = ex._jit_call(jitted, (binding.rename_in(b),),
                               "chain", hit)
            state["prog"] = (jitted, True)  # later chunks ride it
            return binding.rename_out(out)
        except UNTRACEABLE:
            PROGRAMS.deny("chain", key)
            state["prog"] = (None, False)
            return eager(b)

    def record(b: Batch) -> None:
        if PROGRAMS.denied("chain", key):
            return
        from .hotshapes import record_program
        if state["binding"] is None:
            state["binding"] = canon.binding(b)
        record_program("chain", key, canon,
                       state["binding"].rename_in(b), ex.session)

    return run, record


# --------------------------------------------------------------------------
# streamed scan -> filter -> project chains
# --------------------------------------------------------------------------

def maybe_stream_chain(ex, node: PlanNode) -> Optional[Batch]:
    """Chunk-stream a Filter/Project chain whose scan's
    full-materialization estimate exceeds the budget (or when
    ``stream_chunk_rows`` forces chunking). Returns the chain output
    (host-resident), or None when streaming does not engage."""
    sc = scan_chain(node)
    if sc is None:
        return None
    chain, scan = sc
    if not chain:
        return None
    gate = stream_gate(ex, scan)
    if gate is None:
        return None
    forced, budget, est = gate
    if forced <= 0 and (est is None or est <= budget):
        return None
    # 2 in-flight input chunks + 1 retained output chunk — the output
    # carries the CHAIN's schema, which a projection can widen beyond
    # the scan's
    per_row = 2 * _row_bytes(scan.schema) \
        + _row_bytes(chain[0].output_schema())
    chunk_cap = _pick_chunk_capacity(forced, budget, per_row)
    if chunk_cap is None:
        return None                 # not even a minimal chunk fits
    ex._reserve_streamed(
        chunk_cap * per_row,
        f"streamed scan chain over {scan.handle.table} "
        f"(chunk capacity {chunk_cap})")
    run, record = make_chain_runner(ex, chain)
    from .executor import _host_concat, _to_host
    outs: List[Batch] = []
    total = 0

    def dispatch(chunk: Batch, i: int):
        if i == 0:
            record(chunk)
        return run(chunk)

    def collect(out: Batch, i: int):
        nonlocal total
        n = out.num_rows_host()
        if n:
            outs.append(_to_host(out, n))
            total += n

    run_streamed(ex, "chain", host_scan_chunks(ex, scan, chunk_cap),
                 dispatch, collect)
    if not outs:
        return empty_batch(chain[0].output_schema())
    return _host_concat(outs, total)


# --------------------------------------------------------------------------
# streamed hash probe join
# --------------------------------------------------------------------------

def _type_inexact(t) -> bool:
    """Type-level twin of executor._keys_inexact: True when the uint64
    equality lane cannot be bijective for a key of this type (float,
    Int128 decimal low lane, tz hi lane)."""
    if isinstance(t, DecimalType):
        return not t.is_short
    if str(t.name).endswith("with time zone"):
        return True
    try:
        return np.dtype(t.np_dtype).kind == "f"
    except Exception:           # noqa: BLE001
        return True


def _verify_filter_types(pschema, bschema, pkeys, bkeys, filt):
    """join_verify_filter from plan types (no batches yet): append
    key-equality conjuncts when the hash lane is inexact."""
    inexact = len(pkeys) > 1 or any(
        _type_inexact(pschema[k]) for k in pkeys) or any(
        _type_inexact(bschema[k]) for k in bkeys)
    if not inexact:
        return filt
    eqs = [_RCall("=", (InputRef(pk, pschema[pk]),
                        InputRef(bk, bschema[bk])), BOOLEAN)
           for pk, bk in zip(pkeys, bkeys)]
    return and_all(([filt] if filt is not None else []) + eqs)


def _lane_spec(b: Batch) -> tuple:
    """Hashable description of a batch's lanes — the part of the jit
    signature the in-process cache key must capture (names/order =
    treedef, dtypes, validity/hi-lane presence, dictionary-ness)."""
    out = []
    for s, c in b.columns.items():
        out.append((s, str(np.dtype(c.data.dtype)),
                    c.valid is not None,
                    None if c.data2 is None
                    else str(np.dtype(c.data2.dtype)),
                    c.dictionary is not None))
    return tuple(out)


def _spec_from_payload(cols: List[dict]) -> tuple:
    return tuple((str(e["name"]), str(e["dtype"]), bool(e.get("valid")),
                  (None if not e.get("data2") else str(e["data2"])),
                  e.get("dict") is not None) for e in cols)


def join_program_key(jt: str, pkeys, bkeys, residual_repr: str,
                     probe_spec: tuple, build_spec: tuple,
                     chunk_cap: int, build_cap: int,
                     out_cap: int, lanes=None, word_bits=None) -> tuple:
    return ("streamjoin", jt, tuple(pkeys), tuple(bkeys),
            residual_repr, probe_spec, build_spec,
            int(chunk_cap), int(build_cap), int(out_cap),
            None if lanes is None else tuple(sorted(lanes)), word_bits)


_PPOS = "__probe_pos$"


def make_probe_program(jt: str, pkeys: Sequence[str],
                       bkeys: Sequence[str], residual, out_cap: int,
                       lanes=None):
    """The per-chunk probe kernel: match counts against the prebuilt,
    indexed build side (ops/join.py probe_runs) + output expansion at
    a STATIC capacity, fused into one traceable function -> every
    chunk of one streamed join runs the same compiled program. The
    expansion gathers the chunk's and the build side's lanes in
    ``lanes`` alone (executor.py ``expand_lanes``; None: all). Returns
    (out_batch, total_matches) — the total is the overflow signal the
    host checks (a chunk whose matches exceed ``out_cap`` reruns
    through a grown program). Module-level so exec/aot.py rebuilds the
    EXACT closure for worker pre-warm."""
    from ..ops import compact, join as join_ops
    from .executor import expand_columns
    from .expr import eval_predicate
    pkeys = list(pkeys)
    outer = jt == "left"

    def fn(chunk: Batch, build: Batch, side: join_ops.BuildSide):
        key_p, usable_p = join_ops.equality_lane(chunk, pkeys)
        left, count = join_ops.probe_runs(side, key_p, usable_p)
        order = side.order
        probe = chunk
        if residual is not None and outer:
            cols = dict(chunk.columns)
            from ..types import BIGINT
            cols[_PPOS] = Column(
                BIGINT, jnp.arange(chunk.capacity, dtype=jnp.int64),
                None)
            probe = Batch(cols, chunk.num_rows)
        pcols, bcols, _ = expand_columns(probe.columns, build.columns,
                                         lanes)
        probe = Batch(pcols, chunk.num_rows)
        build = Batch(bcols, build.num_rows)
        if residual is None:
            live_p = chunk.row_valid()
            eff = (jnp.where(live_p, jnp.maximum(count, 1), 0)
                   if outer else count)
            total = jnp.sum(eff)
            out = join_ops.expand_join(
                probe, build, left, count, order, out_cap,
                "left" if outer else "inner")
            return out, total
        total = jnp.sum(count)
        cand = join_ops.expand_join(probe, build, left, count, order,
                                    out_cap, "inner")
        mask = eval_predicate(residual, cand)
        out = compact.filter_batch(cand, mask)
        return out, total

    return fn


def _join_payload(jt, criteria, residual, chunk: Batch, build: Batch,
                  out_cap: int, kind: str = "streamjoin", outputs=None
                  ) -> Optional[dict]:
    """AOT transport form of one hash-join program set: the join shape
    as a wire fragment (JoinNode over two schema-carrying RemoteSource
    leaves, ``filter`` holding the FULL residual incl. hash-verify
    conjuncts, and the join's ``outputs``: what its expand gathers) +
    both sides' lane specs at their capacities. Shared by
    the streamed probe program (kind="streamjoin") and the
    materialized two-phase programs (kind="join" — exec/executor.py);
    for the latter ``chunk`` is the whole probe batch. None when a
    side carries lanes the AOT rebuilder cannot fabricate (nested
    columns, large dictionaries)."""
    from ..plan.serde import to_jsonable
    from .hotshapes import MAX_DICT_ENTRIES

    def side(b: Batch):
        cols = []
        schema = {}
        for name, c in b.columns.items():
            if c.elements is not None or c.children is not None:
                return None, None
            ent: Dict[str, object] = {
                "name": name,
                "dtype": str(np.dtype(c.data.dtype)),
                "valid": c.valid is not None,
                "data2": (None if c.data2 is None
                          else str(np.dtype(c.data2.dtype)))}
            if c.dictionary is not None:
                vals = list(c.dictionary.values)
                if len(vals) > MAX_DICT_ENTRIES:
                    return None, None
                ent["dict"] = [None if v is None else str(v)
                               for v in vals]
            cols.append(ent)
            schema[name] = c.type
        return cols, schema

    pcols, pschema = side(chunk)
    bcols, bschema = side(build)
    if pcols is None or bcols is None:
        return None
    frag = JoinNode(RemoteSourceNode((), pschema, "gather"),
                    RemoteSourceNode((), bschema, "gather"),
                    jt, tuple(criteria), residual, outputs=outputs)

    def nrows_kind(b: Batch) -> str:
        return ("int" if isinstance(b.num_rows, int)
                else str(np.dtype(b.num_rows.dtype)))

    return {"kind": kind,
            "fragment": to_jsonable(frag),
            "probe_cols": pcols, "build_cols": bcols,
            "chunk_capacity": int(chunk.capacity),
            "build_capacity": int(build.capacity),
            "probe_num_rows": nrows_kind(chunk),
            "build_num_rows": nrows_kind(build),
            "out_capacity": int(out_cap)}


def aot_entry(payload: dict):
    """(cache key, probe fn, aval args) for exec/aot.py: rebuild the
    exact probe program a streamed join would run from a hot-shape
    payload, with ShapeDtypeStruct avals standing in for the chunk,
    the build side, and the sorted build state."""
    from ..plan.serde import from_jsonable
    from .aot import _aval_batch

    frag = from_jsonable(payload["fragment"])
    if not isinstance(frag, JoinNode):
        raise ValueError("streamjoin payload fragment is not a join")
    pschema = dict(frag.left.schema)
    bschema = dict(frag.right.schema)
    pkeys = [c.left for c in frag.criteria]
    bkeys = [c.right for c in frag.criteria]
    chunk_cap = int(payload["chunk_capacity"])
    build_cap = int(payload["build_capacity"])
    out_cap = int(payload["out_capacity"])
    word_bits = payload.get("bitmap_word_bits")
    from .executor import expand_lanes
    lanes = expand_lanes(frag.outputs, frag.filter)
    key = join_program_key(
        frag.join_type, pkeys, bkeys, repr(frag.filter),
        _spec_from_payload(payload["probe_cols"]),
        _spec_from_payload(payload["build_cols"]),
        chunk_cap, build_cap, out_cap, lanes, word_bits)
    fn = make_probe_program(frag.join_type, pkeys, bkeys, frag.filter,
                            out_cap, lanes)
    chunk = _aval_batch({"cols": payload["probe_cols"],
                         "capacity": chunk_cap,
                         "num_rows": payload.get("probe_num_rows",
                                                 "int")}, pschema)
    build = _aval_batch({"cols": payload["build_cols"],
                         "capacity": build_cap,
                         "num_rows": payload.get("build_num_rows",
                                                 "int")}, bschema)
    from ..ops import join as join_ops
    side = jax.eval_shape(
        lambda b: join_ops.build_side(b, bkeys, word_bits), build)
    return key, fn, (chunk, build, side)


class _StreamDictEncoder:
    """Canonical per-stream code layout for probe-side string columns.

    Every split/chunk read off the connector carries its own
    StringDictionary — a STATIC aux of the Batch pytree, so a fresh
    identity per chunk would re-trace the chain and probe programs on
    every chunk. The encoder fixes ONE stream-level dictionary per
    column (join-key columns are seeded with the BUILD side's
    dictionary, so remapped probe codes compare directly against the
    prebuilt sorted key lane — the per-chunk align_string_keys merge
    of the materialized path, hoisted to stream setup) and host-remaps
    each chunk's codes into that layout inside the double-buffer
    window. Chunks introducing genuinely new values extend the layout
    append-only: existing codes never move, ONE re-trace per extension
    instead of one per chunk, and values absent from the build
    dictionary get codes past its length — codes the sorted build
    lane cannot contain, so they match nothing, exactly what string
    equality requires."""

    def __init__(self, seeds: Dict[str, StringDictionary]):
        self._dicts: Dict[str, StringDictionary] = dict(seeds)

    def encode(self, chunk: Batch) -> Batch:
        cols = dict(chunk.columns)
        changed = False
        for name, c in chunk.columns.items():
            if c.dictionary is None:
                continue
            d = self._dicts.get(name)
            if d is None:
                self._dicts[name] = c.dictionary
                continue
            if c.dictionary is d:
                continue
            idx = d.index
            vals = c.dictionary.values
            remap = np.empty(len(vals), dtype=np.int32)
            fresh = []
            for i, s in enumerate(vals):
                code = idx.get(s)
                if code is None:
                    fresh.append((i, s))
                else:
                    remap[i] = code
            if fresh:
                ext = list(d.values)
                nidx = dict(idx)
                for i, s in fresh:
                    remap[i] = len(ext)
                    nidx[s] = len(ext)
                    ext.append(s)
                d = StringDictionary(np.asarray(ext, dtype=object),
                                     nidx)
                self._dicts[name] = d
            codes = np.take(remap,
                            np.asarray(c.data).astype(np.int32))
            cols[name] = Column(c.type, codes, c.valid, d, c.data2)
            changed = True
        return Batch(cols, chunk.num_rows) if changed else chunk


def maybe_stream_join(ex, node: JoinNode
                      ) -> Tuple[Optional[Batch], Optional[Batch]]:
    """Chunk-stream the probe side of a hash join whose probe scan
    does not fit the budget REMAINING after the build side: build
    once, stream probe chunks through double-buffered transfers and
    ONE compiled probe program, accumulate match output on host.
    Returns (streamed result, None) on engagement; on decline,
    (None, build batch) when the decision required materializing the
    build side (the caller reuses it instead of re-executing), else
    (None, None)."""
    jt = node.join_type
    if jt not in ("inner", "left") or not node.criteria:
        return None, None
    sc = scan_chain(node.left)
    if sc is None:
        return None, None
    chain, scan = sc
    gate = stream_gate(ex, scan)
    if gate is None:
        return None, None
    pschema = chain[0].output_schema() if chain \
        else scan.output_schema()
    bschema = node.right.output_schema()
    # nested columns cannot chunk-slice; string columns stream through
    # the per-stream canonical dictionary layout (_StreamDictEncoder)
    # — but only when read off the SCAN: a string column the chain
    # creates would mint a fresh dictionary per chunk (a re-trace per
    # chunk), so those decline to the materialized path. The BUILD
    # side may carry dictionaries freely: it is materialized once,
    # its identity is stable.
    from ..types import is_string
    if not all(_col_streamable(t) for t in pschema.values()):
        return None, None
    if not all(_col_streamable(t) for t in scan.schema.values()):
        return None, None
    if any(is_string(t) and s not in scan.schema
           for s, t in pschema.items()):
        return None, None
    pkeys = [c.left for c in node.criteria]
    bkeys = [c.right for c in node.criteria]
    if any(k not in pschema for k in pkeys) \
            or any(k not in bschema for k in bkeys):
        return None, None
    forced, budget, est = gate
    if forced <= 0 and (est is None or 4 * est <= budget):
        # heuristic pre-decline: the exact remaining-after-build rule
        # below requires materializing the build FIRST, which reorders
        # execution for every join — so probes under a quarter of the
        # budget skip it. The corner this concedes: a build consuming
        # >3/4 of the budget next to a fitting probe materializes both
        # (per-reservation accounting, same as the pre-streaming
        # engine) instead of streaming
        return None, None
    residual = _verify_filter_types(pschema, bschema, pkeys, bkeys,
                                    node.filter)
    from .executor import expand_columns, expand_lanes, narrow
    lanes = expand_lanes(node.outputs, residual)

    # build once: the engine's hash table is the sorted key lane, its
    # permutation and its int32 directory and run lengths (ops/join.py
    # build_side: HashBuilderOperator's table, HBM-resident for the
    # whole stream)
    from ..ops import join as join_ops
    from .executor import _col_bytes, _host_concat, _to_host
    build = ex.execute(node.right)
    build_bytes = sum(_col_bytes(c) for c in build.columns.values()) \
        + 3 * build.capacity * 8
    # and its bitmap directory's words, a table of up to the head's
    # 2^24 whatever the rows: held where it takes at most half of what
    # the budget leaves beside the rest, else one word (the side keeps
    # its exact and hashed forms)
    word_bits = join_ops._bitmap_word_bits(build.capacity)
    if 8 << word_bits > budget - build_bytes:
        word_bits = 0
    build_bytes += 4 * ((1 << word_bits) + 1)
    # the exact engagement rule: stream iff the probe does not fit in
    # what the budget leaves after the (capacity-rounded) build state
    # — the materialized path would hold probe + build concurrently
    if forced <= 0 and (est is None
                        or est <= max(budget - build_bytes, 0)):
        return None, build
    side = join_ops.build_side(build, bkeys, word_bits)
    # probe-side canonical dictionaries: key columns seed from the
    # BUILD dictionary so remapped probe codes compare directly
    # against the sorted build key lane just computed
    enc = _StreamDictEncoder(
        {pk: build.column(bk).dictionary
         for pk, bk in zip(pkeys, bkeys)
         if build.column(bk).dictionary is not None})

    probe_row = _row_bytes(pschema) + _row_bytes(scan.schema)
    out_row = _row_bytes(pschema) + _row_bytes(bschema) + 8
    per_row = 2 * probe_row + out_row
    chunk_cap = _pick_chunk_capacity(
        forced, max(budget - build_bytes, 0), per_row)
    if chunk_cap is None:
        return None, build      # build alone exhausts the budget
    state = {"out_cap": chunk_cap, "prog": None, "prog_cap": None,
             "probe_spec": None, "hit": None, "eager": False,
             "recorded": False, "kept": None}
    ex._reserve_streamed(
        build_bytes + chunk_cap * per_row,
        f"streamed join (build {build_bytes}B + chunk capacity "
        f"{chunk_cap})")

    chain_run, chain_record = make_chain_runner(ex, chain)
    outs: List[Batch] = []
    total_rows = 0

    def program():
        """(callable, key, eager?) for the current output capacity —
        rebuilt ONLY when the capacity grows: the key derivation
        (residual repr, lane-spec walks) is host work sitting in the
        double-buffer window, so it must not repeat per chunk. Jitted
        programs live in the cross-query cache (exec/progkey.py
        doctrine: one key per program, shared across queries)."""
        if state["prog"] is not None \
                and state["prog_cap"] == state["out_cap"]:
            return state["prog"]
        key = join_program_key(
            jt, pkeys, bkeys, repr(residual), state["probe_spec"],
            _lane_spec(build), chunk_cap, build.capacity,
            state["out_cap"], lanes, word_bits)
        fn = make_probe_program(jt, pkeys, bkeys, residual,
                                state["out_cap"], lanes)
        got = None if state["eager"] else PROGRAMS.program(
            "streamjoin", key, lambda: fn, "streamjoin", key)
        if got is None:
            entry = (fn, key, True)
        else:
            jitted, state["hit"] = got
            entry = (jitted, key, False)
        state["prog"], state["prog_cap"] = entry, state["out_cap"]
        return entry

    def run_chunk(probe_chunk: Batch):
        if state["probe_spec"] is None:
            state["probe_spec"] = _lane_spec(probe_chunk)
            # what the expand gathers of the two inputs (the chunk's
            # position lane, where one is added, counts on both sides)
            state["kept"] = expand_columns(
                probe_chunk.columns, build.columns, lanes)[2]
        jitted, key, eager = program()
        args = (probe_chunk, build, side)
        if eager:                   # deny/fallback path
            return jitted(*args)
        try:
            out = ex._jit_call(
                jitted, args, "streamjoin", bool(state["hit"]),
                form=join_ops.expand_form(probe_chunk.capacity,
                                          state["out_cap"]),
                lanes=state["kept"])
            state["hit"] = True     # later chunks ride the program
            if not state["recorded"]:
                state["recorded"] = True
                from .hotshapes import record_program

                def build_pl():
                    payload = _join_payload(jt, node.criteria, residual,
                                            probe_chunk, build,
                                            state["out_cap"],
                                            outputs=node.outputs)
                    if payload is not None:
                        payload["bitmap_word_bits"] = word_bits
                    return payload
                record_program("streamjoin", key, None, None,
                               ex.session, payload_fn=build_pl)
            return out
        except UNTRACEABLE:
            PROGRAMS.deny("streamjoin", key)
            state["eager"] = True
            state["prog"] = None
            fn = make_probe_program(jt, pkeys, bkeys, residual,
                                    state["out_cap"], lanes)
            return fn(*args)

    def dispatch(chunk: Batch, i: int):
        if i == 0:
            chain_record(chunk)
        b = chain_run(chunk)
        out, total = run_chunk(b)
        return b, out, total

    def collect(res, i: int):
        nonlocal total_rows
        b, out, total = res
        total = int(total)
        if total > state["out_cap"]:
            # a hot probe chunk overflowed the output bucket: grow the
            # capacity (monotone — later chunks keep the larger
            # program) and re-expand this chunk. The grown buffer is
            # REAL device residency, so it goes through the same
            # reserve discipline as the initial streamed peak — an
            # ungoverned regrow would be exactly the invisible OOM
            # streaming exists to prevent
            grown = capacity_for(total)
            ex._reserve_streamed(
                build_bytes + 2 * chunk_cap * probe_row
                + grown * out_row,
                f"streamed join output growth to {grown} rows "
                "(one probe chunk matched more build rows than the "
                "output bucket holds; lower stream_chunk_rows)")
            state["out_cap"] = grown
            out, total = run_chunk(b)
        if residual is not None:
            out = ex._repair_outer(out, b, build, jt, node.outputs)
        n = out.num_rows_host()
        if n:
            outs.append(_to_host(out, n))
            total_rows += n

    run_streamed(ex, "join",
                 (enc.encode(c)
                  for c in host_scan_chunks(ex, scan, chunk_cap)),
                 dispatch, collect)
    if not outs:
        # zero matches / empty probe: synthesize the joined schema
        # with an honest zero-row expansion
        chunk0 = chain_run(_h2d(empty_batch(
            {s: scan.schema[s] for s in scan.assignments})))
        z = jnp.zeros((chunk0.capacity,), jnp.int64)
        out_lanes = expand_lanes(node.outputs)
        out = join_ops.expand_join(narrow(chunk0, out_lanes),
                                   narrow(build, out_lanes), z, z,
                                   side.order, 8, "inner")
        return _to_host(out, 0), None
    return _host_concat(outs, total_rows), None
