"""Per-query distributed trace: a span tree with real span identity.

Reference parity: the reference records queryStats stage timings
(QueryStateMachine's queued/analysis/planning/execution durations) and
exposes them in /v1/query; OpenTelemetry spans landed on the same
boundaries (io.opentelemetry.api wiring in DispatchManager /
SqlQueryExecution) with W3C ``traceparent`` context propagation into
the task protocol. Here a ``QueryTrace`` rides on the Session: the
runner opens parse/plan/optimize/execute spans, the executor nests
jit_trace vs device_execute children under execute, and the remote/
stage schedulers pre-mint a span id per dispatched task, ship it as a
``traceparent`` (header + task-payload field), and merge the worker's
reported subtree back ID-PRESERVING — a worker span is born with the
query's 128-bit trace id and its true 64-bit parent span id, so the
merged tree is one distributed trace, not a clock-rebased collage.
On a tensor runtime the jit_trace/dispatch split is the headline
number — compilation/dispatch dominates latency (PAPERS.md "Query
Processing on Tensor Computation Runtimes"), and a wall-clock total
cannot show it.

One rule: a span times what the HOST does; a program's device time is
read on the device trace (the ``jit_<kind>_<key8>`` module that follows
its ``tpusql:dispatch`` annotation, both carrying ``program``). Only
EXPLAIN ANALYZE (``QueryTrace.analyze``) waits per program:
``device_execute`` with ``device_ms``, the HOST clock from dispatch to
outputs ready — an upper bound on the program's device time, not a
device measurement — which it rolls up per node and stage.

The span list of a served query (ROOT spans, in order; ``PHASES``):
``submit`` (POST body read -> tracker.submit returns), ``queued``
(submit -> the query thread's first line; opened on the HTTP thread,
closed on the query thread), ``parse``, ``plan``, ``optimize``,
``execute``, ``fetch`` (device-to-host fetch of the result rows),
``persist`` (restart-recovery spool), ``finish`` (terminal bookkeeping
after the client is released) on the query thread, and ``respond``
(payload + JSON + socket write, one per POST or poll that carries data
or the terminal state) on the HTTP thread. Under ``execute``:
``dispatch`` / ``jit_trace`` per dispatched program (attrs
``program=<kind>:<key8>``, ``cache``; no wait: the call to its return),
``host_read`` (attr ``site``) per blocking device-to-host read of the
executor — the last one ``node_rows``, the plan's output waited for and
every node's row count in one transfer — and ``scan_fill`` (attrs
``table``, ``lanes``) per scan-cache miss. Under EXPLAIN ANALYZE
``device_execute`` (attr ``device_ms``) takes ``dispatch``'s place and
``host_read[node_fence]`` ends every plan node.

One clock: every span opened through ``span()`` also enters a
``jax.profiler.TraceAnnotation("tpusql:<name>", query_id=, span_id=)``
— under a profiler session the span is IN the device trace, on the
profiler's clock; without one a TraceMe is a flag test. The
``time.time_ns()`` anchors stay (OTLP needs them). ``on_close`` is the
ONE hook through which closed spans feed the counters at ``/metrics``
(obs/metrics.py ``observe_span``), set where the trace is born.

Concurrency: the open-span stack is a per-thread structure
(``threading.local``), so a span opened on a fragment-dispatch thread
can never nest under whatever the executor thread happens to have open
— the pre-identity implementation shared one stack across threads and
had exactly that race. Cross-thread attachment is explicit: pass
``parent=`` to ``span()``/``record()``. The lock only guards child-
list appends, which concurrent threads do hit.
"""

from __future__ import annotations

import os
import random
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _Annotation

# the fixed set of span names that feed trino_tpu_query_phase_seconds
# (no spaces: the label value is read back by line-oriented scrapers)
ROOT_PHASES = ("submit", "queued", "parse", "plan", "optimize",
               "execute", "fetch", "persist", "respond", "finish")
EXECUTE_PHASES = ("device_execute", "jit_trace", "host_read",
                  "scan_fill", "exchange", "dispatch", "scan_derive")
PHASES = ROOT_PHASES + EXECUTE_PHASES
ANNOTATION_PREFIX = "tpusql:"

# the traces with a span open on THIS thread, innermost last: lets
# code without a Session in reach (the scan cache, the connectors'
# device generators) attach spans to the query it is running for
_ACTIVE = threading.local()


def _active_stack() -> list:
    st = getattr(_ACTIVE, "stack", None)
    if st is None:
        st = _ACTIVE.stack = []  # tt-lint: ignore[race-attr-write] threading.local attribute: each thread writes its OWN slot
    return st


def active_trace() -> Optional["QueryTrace"]:
    """The trace whose span is innermost open on the calling thread."""
    st = getattr(_ACTIVE, "stack", None)
    return st[-1] if st else None


def active_span(name: str, **attrs):
    """``span(name)`` on the calling thread's active trace; a no-op
    context outside a traced query."""
    tr = active_trace()
    return tr.span(name, **attrs) if tr is not None else nullcontext()


def new_trace_id() -> str:
    """128-bit W3C trace id (32 lowercase hex chars)."""
    return os.urandom(16).hex()


# span ids come from a process-local generator seeded once from the
# OS: os.urandom per span is a system call that RELEASES THE GIL, and a
# query thread that opens a span would hand the interpreter to whatever
# thread waits for it (measured: the HTTP thread answered the client
# before the query thread's terminal bookkeeping had begun)
_SPAN_IDS = random.Random(os.urandom(16))


def new_span_id() -> str:
    """64-bit W3C span id (16 lowercase hex chars)."""
    return f"{_SPAN_IDS.getrandbits(64):016x}"


def format_traceparent(trace_id: str, span_id: str) -> str:
    """W3C Trace Context header value (version 00, sampled)."""
    return f"00-{trace_id}-{span_id}-01"


def parse_traceparent(value: object) -> Optional[Tuple[str, str]]:
    """(trace_id, parent_span_id) from a ``traceparent`` value, or
    None when malformed — propagation is best-effort, a corrupt header
    must never fail a task."""
    if not isinstance(value, str):
        return None
    parts = value.strip().split("-")
    if len(parts) != 4:
        return None
    _, trace_id, span_id, _ = parts
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16)
        int(span_id, 16)
    except ValueError:
        return None
    return trace_id, span_id


@dataclass
class Span:
    name: str
    start_s: float                      # perf_counter at open
    end_s: Optional[float] = None       # perf_counter at close
    attrs: Dict[str, object] = field(default_factory=dict)
    children: List["Span"] = field(default_factory=list)
    # identity (the distributed half): 64-bit span id, minted at
    # creation or preserved off the wire; parent_id is only stored for
    # REMOTE parents (a local parent is the tree edge itself)
    span_id: str = field(default_factory=new_span_id)
    parent_id: Optional[str] = None
    # absolute wall-clock anchors (unix nanos), preserved across the
    # wire so a worker span keeps ITS host's clock instead of being
    # rebased onto the coordinator's — the id-preserving merge is also
    # a clock-preserving one
    start_unix_ns: Optional[int] = None
    end_unix_ns: Optional[int] = None
    # the open profiler annotation (None for spans that were recorded
    # already timed, grafted, or closed on another thread)
    _ann: Optional[object] = field(default=None, repr=False,
                                   compare=False)

    @property
    def wall_s(self) -> float:
        return (self.end_s or self.start_s) - self.start_s

    def to_dict(self, origin_s: float,
                origin_unix_ns: Optional[int] = None) -> dict:
        d = {"name": self.name,
             "startMillis": round((self.start_s - origin_s) * 1000, 3),
             "wallMillis": round(self.wall_s * 1000, 3),
             "spanId": self.span_id}
        if self.parent_id:
            d["parentSpanId"] = self.parent_id
        start_ns = self.start_unix_ns
        if start_ns is None and origin_unix_ns is not None:
            start_ns = origin_unix_ns + int(
                (self.start_s - origin_s) * 1e9)
        if start_ns is not None:
            d["startUnixNanos"] = int(start_ns)
            end_ns = self.end_unix_ns
            if end_ns is None:
                end_ns = start_ns + int(self.wall_s * 1e9)
            d["endUnixNanos"] = int(end_ns)
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.children:
            d["children"] = [c.to_dict(origin_s, origin_unix_ns)
                             for c in self.children]
        return d

    @classmethod
    def from_dict(cls, d: dict, origin_s: float = 0.0) -> "Span":
        start = origin_s + d.get("startMillis", 0.0) / 1000.0
        sp = cls(d.get("name", "?"), start,
                 start + d.get("wallMillis", 0.0) / 1000.0,
                 dict(d.get("attrs", {})))
        sid = d.get("spanId")
        if sid:
            sp.span_id = str(sid)
        pid = d.get("parentSpanId")
        if pid:
            sp.parent_id = str(pid)
        if d.get("startUnixNanos") is not None:
            sp.start_unix_ns = int(d["startUnixNanos"])
        if d.get("endUnixNanos") is not None:
            sp.end_unix_ns = int(d["endUnixNanos"])
        sp.children = [cls.from_dict(c, origin_s)
                       for c in d.get("children", [])]
        return sp


class QueryTrace:
    """The span tree of one query. ``span(name)`` is a context manager
    nesting under the calling THREAD's innermost open span (explicit
    ``parent=`` overrides); ``record``/``graft`` attach pre-timed
    spans (worker-reported subtrees arrive whole, ids intact). Born
    with a 128-bit trace id — or, on a worker, with the QUERY's trace
    id and the dispatching span's id from the ``traceparent`` the task
    payload carried, so every span this trace mints already belongs to
    the distributed trace."""

    def __init__(self, query_id: str = "",
                 trace_id: Optional[str] = None,
                 parent_span_id: Optional[str] = None,
                 on_close: Optional[Callable[[Span], None]] = None,
                 origin_s: Optional[float] = None,
                 analyze: bool = False):
        self.query_id = query_id
        # EXPLAIN ANALYZE: every program is waited for and timed
        # (``device_execute``, ``device_ms``) and every plan node fenced
        # — the explicit analysis; a served query waits for neither
        self.analyze = analyze
        self.trace_id = trace_id or new_trace_id()
        # the REMOTE parent: root spans opened here carry it as their
        # parentSpanId, which is what makes the coordinator-side merge
        # id-preserving instead of positional
        self.parent_span_id = parent_span_id
        # called with every span that closes or is recorded here: the
        # one place where spans feed counters (no second set of timers)
        self.on_close = on_close
        now_s, now_ns = time.perf_counter(), time.time_ns()
        # ``origin_s``: a perf_counter reading BEFORE the trace existed
        # (the POST's arrival) that the first span is back-dated to
        self.origin_s = now_s if origin_s is None else origin_s
        self.origin_unix_ns = now_ns - int((now_s - self.origin_s) * 1e9)
        self.roots: List[Span] = []
        self._tls = threading.local()   # per-thread open-span stack
        self._lock = threading.Lock()

    # -- clock mapping -------------------------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []  # tt-lint: ignore[race-attr-write] threading.local attribute: each thread writes its OWN slot by construction — thread isolation is the whole point
        return st

    def perf_from_unix_ns(self, ns: int) -> float:
        """Map an absolute unix-nanos timestamp onto this trace's
        perf_counter timebase (the rendering clock)."""
        return self.origin_s + (ns - self.origin_unix_ns) / 1e9

    # -- W3C context ---------------------------------------------------
    def traceparent(self, span_id: Optional[str] = None) -> str:
        """The ``traceparent`` value naming ``span_id`` (default: the
        calling thread's innermost open span) as the remote parent."""
        if span_id is None:
            cur = self.current()
            span_id = cur.span_id if cur is not None else new_span_id()
        return format_traceparent(self.trace_id, span_id)

    parse_traceparent = staticmethod(parse_traceparent)
    new_span_id = staticmethod(new_span_id)

    # -- structured construction --------------------------------------
    def span(self, name: str, parent: Optional[Span] = None,
             root: bool = False, start_s: Optional[float] = None,
             **attrs) -> "_SpanCtx":
        """``root=True`` opens a ROOT span whatever the calling thread
        has open (the explicit form of "no parent"); ``start_s``
        back-dates the span to a perf_counter reading taken before it
        could be opened."""
        return _SpanCtx(self, name, attrs, parent, root, start_s)

    def begin(self, name: str, **attrs) -> Span:
        """Open a ROOT span that ANOTHER thread will ``end``: it joins
        no thread's stack and carries no profiler annotation (a TraceMe
        begins and ends on one thread)."""
        return self._open(name, attrs, root=True, push=False)

    def end(self, sp: Span) -> None:
        self._close(sp)

    def _open(self, name: str, attrs: Dict[str, object],
              parent: Optional[Span] = None, root: bool = False,
              start_s: Optional[float] = None,
              push: bool = True) -> Span:
        sp = Span(name, time.perf_counter(), attrs=dict(attrs))
        sp.start_unix_ns = time.time_ns()
        if start_s is not None:
            sp.start_unix_ns -= int((sp.start_s - start_s) * 1e9)
            sp.start_s = start_s
        stack = self._stack()
        if parent is None and not root:
            parent = stack[-1] if stack else None
        if parent is None and self.parent_span_id:
            sp.parent_id = self.parent_span_id
        with self._lock:
            (parent.children if parent is not None
             else self.roots).append(sp)
        if push:
            stack.append(sp)
            _active_stack().append(self)
            # on the profiler's clock too (a flag test without a
            # profiler session)
            # a dispatch names its program, so a reader of the
            # profile pairs it with the ``jit_<kind>_<key8>`` module
            # that follows on the device
            named = ({} if "program" not in attrs
                     else {"program": attrs["program"]})
            sp._ann = _Annotation(ANNOTATION_PREFIX + name,
                                  query_id=self.query_id,
                                  span_id=sp.span_id, **named)
            sp._ann.__enter__()
        return sp

    def _close(self, sp: Span, dropped: bool = False) -> None:
        sp.end_s = time.perf_counter()
        sp.end_unix_ns = time.time_ns()
        if sp._ann is not None:
            sp._ann.__exit__(None, None, None)
            sp._ann = None
        stack = self._stack()
        if stack and stack[-1] is sp:
            stack.pop()
            active = _active_stack()
            if active and active[-1] is self:
                active.pop()
        if dropped:
            # a root span that turned out to time nothing worth
            # keeping (a poll that carried neither data nor the
            # terminal state): it leaves no trace and feeds no counter
            with self._lock:
                if sp in self.roots:
                    self.roots.remove(sp)
            return
        self._closed(sp)

    def _closed(self, sp: Span) -> None:
        if self.on_close is not None:
            try:
                self.on_close(sp)
            except Exception:   # noqa: BLE001 — telemetry never fails
                pass            # the query it describes

    def current(self) -> Optional[Span]:
        stack = self._stack()
        return stack[-1] if stack else None

    def record(self, name: str, start_s: float, end_s: float,
               parent: Optional[Span] = None,
               span_id: Optional[str] = None, **attrs) -> Span:
        """Attach an already-timed span under ``parent`` (or the
        calling thread's innermost open span). ``span_id`` installs a
        PRE-MINTED id — the dispatch path mints the id before task
        submit so the worker's spans can be born pointing at it.
        Safe from fragment-dispatch threads."""
        sp = Span(name, start_s, end_s, dict(attrs))
        if span_id:
            sp.span_id = span_id
        sp.start_unix_ns = self.origin_unix_ns + int(
            (start_s - self.origin_s) * 1e9)
        sp.end_unix_ns = self.origin_unix_ns + int(
            (end_s - self.origin_s) * 1e9)
        if parent is None:
            parent = self.current()
        if parent is None and self.parent_span_id:
            sp.parent_id = self.parent_span_id
        with self._lock:
            (parent.children if parent is not None
             else self.roots).append(sp)
        self._closed(sp)
        return sp

    def graft(self, parent: Optional[Span], spans: List[dict],
              base_s: Optional[float] = None) -> None:
        """Attach worker-reported span dicts — the ID-PRESERVING
        merge: span/parent ids survive the wire, and spans carrying
        absolute unix-nanos anchors keep their own host's clock
        (mapped onto this trace's timebase for rendering). Legacy
        dicts without anchors fall back to rebasing the subtree at
        ``base_s`` (default = parent start)."""
        if parent is not None and base_s is None:
            base_s = parent.start_s
        for d in spans:
            sp = Span.from_dict(d, base_s if base_s is not None
                                else self.origin_s)
            self._realign(sp)
            if sp.parent_id is None and parent is not None:
                sp.parent_id = parent.span_id
            with self._lock:
                (parent.children if parent is not None
                 else self.roots).append(sp)

    def _realign(self, sp: Span) -> None:
        if sp.start_unix_ns is not None:
            start = self.perf_from_unix_ns(sp.start_unix_ns)
            end = (self.perf_from_unix_ns(sp.end_unix_ns)
                   if sp.end_unix_ns is not None
                   else start + sp.wall_s)
            sp.start_s, sp.end_s = start, end
        for c in sp.children:
            self._realign(c)

    # -- rendering ------------------------------------------------------
    def to_dicts(self) -> List[dict]:
        return [r.to_dict(self.origin_s, self.origin_unix_ns)
                for r in self.roots]

    def all_spans(self) -> List[Span]:
        """Depth-first flattening of the whole tree (the OTLP
        exporter's input — OTLP spans are a flat list linked by
        parentSpanId)."""
        out: List[Span] = []

        def walk(sp: Span) -> None:
            out.append(sp)
            for c in sp.children:
                walk(c)

        for r in self.roots:
            walk(r)
        return out

    def lines(self) -> List[str]:
        """Indented text rendering for EXPLAIN ANALYZE."""
        out: List[str] = []

        def walk(sp: Span, depth: int) -> None:
            attrs = ""
            if sp.attrs:
                attrs = " " + ", ".join(
                    f"{k}={v}" for k, v in sorted(sp.attrs.items()))
            out.append(f"{'   ' * depth}- {sp.name}: "
                       f"{sp.wall_s * 1000:.2f}ms{attrs}")
            for c in sp.children:
                walk(c, depth + 1)

        for r in self.roots:
            walk(r, 0)
        return out


def dispatch_span(trace: Optional[QueryTrace], program: str,
                  hit: bool = True, cache: Optional[str] = None,
                  **attrs):
    """The span of ONE device-program dispatch — the single helper
    behind every dispatch, so each is a span and a count: ``dispatch``
    (``hit``: the call to its return, the host's work of handing the
    program to the device) or ``jit_trace`` (first call: trace +
    compile + dispatch), with ``program=<kind>:<key8>`` and what else
    the dispatcher knows of the program (``attrs``: a join's expand
    program carries ``form``). Under EXPLAIN ANALYZE
    (``trace.analyze``) a hit is ``device_execute``, and the caller
    waits for the outputs inside it. ``trace=None`` falls back to the
    calling thread's active trace; outside a traced query it is a
    no-op context (``as`` yields None)."""
    if trace is None:
        trace = active_trace()
    if trace is None:
        return nullcontext()
    name = ("jit_trace" if not hit
            else "device_execute" if trace.analyze else "dispatch")
    return trace.span(name, cache=cache or program.split(":", 1)[0],
                      program=program, **attrs)


def null_span(name: str, **attrs):
    """Drop-in for ``QueryTrace.span`` when no trace is installed —
    callers write ``sp = trace.span if trace else null_span`` and keep
    one code path."""
    return nullcontext()


class _SpanCtx:
    __slots__ = ("_trace", "_name", "_attrs", "_parent", "_root",
                 "_start_s", "span", "dropped")

    def __init__(self, trace: QueryTrace, name: str, attrs,
                 parent: Optional[Span] = None, root: bool = False,
                 start_s: Optional[float] = None):
        self._trace = trace
        self._name = name
        self._attrs = attrs
        self._parent = parent
        self._root = root
        self._start_s = start_s
        self.span: Optional[Span] = None
        self.dropped = False    # set inside the block: see _close

    def __enter__(self) -> Span:
        self.span = self._trace._open(self._name, self._attrs,
                                      self._parent, self._root,
                                      self._start_s)
        return self.span

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None and self.span is not None:
            self.span.attrs.setdefault("error", exc_type.__name__)  # tt-lint: ignore[race-attr-mutate] an open span belongs to the thread that opened it; readers render it after close
        self._trace._close(self.span, self.dropped)
