"""Coordinator hot-shape registry: ranked LRU of compiled program
shapes, the feed for worker pre-warm.

Reference parity: there is no direct Trino analog — the closest is the
coordinator's global (cross-query) dynamic-filter/statistics state —
because the JVM pays its bytecode-generation cost in milliseconds. On
a tensor runtime the equivalent cost is 30-90s of XLA compile per
fragment shape (ROADMAP item 1), so WHICH shapes a cluster runs is
operationally precious state: the registry records every structural
program the process compiles (canonical key from exec/progkey.py +
capacity-bucketed aval spec), ranks entries by hit count with LRU
recency as the tiebreak/eviction order, and serves the top-K at
``GET /v1/hotshapes`` on the coordinator. A joining worker pulls the
list during its announce handshake and AOT-compiles the top-K on a
background thread BEFORE advertising itself warm (exec/aot.py,
server/task_worker.py) — so a fresh worker's first fragment of a hot
query executes at device speed instead of trace speed.

Workers feed their locally-recorded shapes back to the coordinator in
task status payloads (``hotShapes``), so the coordinator's registry
covers every DISPATCHED fragment's shapes, not only what its own
combine stage compiled.

Recorded kinds span the FULL warm path (exec/aot.py dispatches on
``payload["kind"]``): ``chain`` / ``stream`` / ``stream_full``
(canonical fragment programs), ``streamjoin`` (the streamed-probe
chunk kernel), ``join`` (the materialized hash join's count + expand
program pair), ``window`` (execute_window over one canonical
WindowNode), and ``repartition`` (the exchange bucketing kernel —
signature-only, no fragment).

Shared-runtime code: the registry is mutated by query executor
threads, task threads, and HTTP handler threads concurrently — every
method takes the registry lock (and the module is on the race-lint
cross-module allowlist, analysis/lint.py)."""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Dict, List, Optional

import numpy as np

from ..config import CONFIG
from ..obs.metrics import METRICS

_M_RECORDS = METRICS.counter(
    "trino_tpu_hot_shapes_recorded_total",
    "Hot-shape registry records by outcome",
    ("outcome",))           # new | hit | merged | unsupported
_M_SIZE = METRICS.gauge(
    "trino_tpu_hot_shapes",
    "Program shapes currently tracked by the hot-shape registry")

# registry entries a pathological query may create: past this budget a
# query keeps HITTING existing entries but registers no new ones (a
# generated-SQL storm of one-off shapes must not evict the fleet's
# genuinely hot programs). Session-gated per query (prewarm_enabled /
# hot_shape_top_k, session.py).
_BUDGET_ATTR = "_hot_shapes_recorded"


class HotShapeRegistry:
    """Ranked LRU of (canonical key -> AOT-able payload) entries."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        import uuid
        self._lock = threading.Lock()
        self._capacity = (capacity if capacity is not None
                          else CONFIG.hot_shape_entries)
        # key -> entry dict; OrderedDict end == most recently touched
        self._entries: "OrderedDict[str, dict]" = OrderedDict()
        self._seq = 0
        # identity stamped on exported deltas: when a worker shares
        # the process (and therefore THIS registry) with the scheduler
        # — single-host runners, tests, the bench fault/mpp legs —
        # merging its status delta back in would double-count every
        # worker-side sighting. merge() drops self-originated entries.
        self.origin = uuid.uuid4().hex[:12]

    # -- write side ----------------------------------------------------
    def record(self, kind: str, key: str,
               payload_fn: Callable[[], Optional[dict]],
               hits: int = 1) -> Optional[str]:
        """Count a sighting of ``key``; on first sight materialize the
        AOT payload (``payload_fn`` returns None for shapes the AOT
        path cannot rebuild — oversized dictionaries, nested columns —
        which are not registered at all). Returns "new" when this call
        created the entry, "hit" when it re-ranked an existing one,
        None when the shape is unsupported."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                ent["hits"] += hits
                self._seq += 1
                ent["seq"] = self._seq
                self._entries.move_to_end(key)
                _M_RECORDS.inc(outcome="hit")
                return "hit"
        # payload built OUTSIDE the lock: serde encoding walks the
        # whole canonical fragment
        payload = payload_fn()
        if payload is None:
            _M_RECORDS.inc(outcome="unsupported")
            return None
        with self._lock:
            ent = self._entries.get(key)
            self._seq += 1
            if ent is not None:         # raced another recorder
                ent["hits"] += hits
                ent["seq"] = self._seq
                self._entries.move_to_end(key)
                _M_RECORDS.inc(outcome="hit")
                return "hit"
            new_ent = {"kind": kind, "key": key,
                       "hits": hits, "seq": self._seq,
                       "payload": payload}
            self._entries[key] = new_ent
            while len(self._entries) > max(self._capacity, 1):
                # rank-aware eviction: coldest (fewest hits), oldest-
                # touched among ties — never the entry just admitted
                # (every newcomer starts at 1 hit and would otherwise
                # evict itself, starving the registry of fresh shapes)
                victims = [e for e in self._entries.values()
                           if e is not new_ent]
                if not victims:
                    break
                v = min(victims, key=lambda e: (e["hits"], e["seq"]))
                del self._entries[v["key"]]
            _M_RECORDS.inc(outcome="new")
            _M_SIZE.set(len(self._entries))
            return "new"

    def merge(self, entries: List[dict]) -> int:
        """Absorb entries exported by another process (worker task
        status riding back to the coordinator). Defensive: a malformed
        entry is skipped, never raises into the status path."""
        n = 0
        for e in entries or ():
            try:
                if e.get("origin") == self.origin:
                    # exported from THIS registry (in-process worker):
                    # the sighting is already counted here
                    continue
                kind = str(e["kind"])
                key = str(e["key"])
                hits = max(int(e.get("hits") or 1), 1)
                payload = e["payload"]
                if not isinstance(payload, dict):
                    continue
            except (KeyError, TypeError, ValueError):
                continue
            if self.record(kind, key, lambda p=payload: p, hits=hits):
                _M_RECORDS.inc(outcome="merged")
                n += 1
        return n

    # -- read side -----------------------------------------------------
    def top(self, k: int) -> List[dict]:
        """The k hottest shapes: hit count desc, recency desc as the
        tiebreak — what a joining worker should compile first."""
        with self._lock:
            ranked = sorted(self._entries.values(),
                            key=lambda e: (-e["hits"], -e["seq"]))
            return [dict(e) for e in ranked[:max(int(k), 0)]]

    def hit_counts(self) -> Dict[str, int]:
        """Per-key hit snapshot — the baseline for ``export_delta``."""
        with self._lock:
            return {k: e["hits"] for k, e in self._entries.items()}

    def export_delta(self, before: Dict[str, int]) -> List[dict]:
        """Entries whose hit count GREW since the ``before`` snapshot,
        carrying only the growth as their ``hits`` — the worker-side
        delta a task status ships back. Shipping deltas (not
        cumulative counts) keeps the coordinator's ranking additive:
        N statuses each reporting the same entry contribute exactly
        the sightings that happened, never re-count earlier ones."""
        with self._lock:
            out = []
            for k, e in self._entries.items():
                grown = e["hits"] - before.get(k, 0)
                if grown > 0:
                    ent = dict(e)
                    ent["hits"] = grown
                    ent["origin"] = self.origin
                    out.append(ent)
            return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            _M_SIZE.set(0)


# the process-wide registry (coordinator and worker alike: a worker
# records what it compiles and exports deltas via task status; the
# coordinator records its combine-stage programs directly and merges
# worker deltas)
HOT_SHAPES = HotShapeRegistry()


def _session_allows(session) -> bool:
    try:
        return bool(session.get("prewarm_enabled")) \
            if session is not None else True
    except KeyError:
        return True


def _session_budget(session) -> int:
    try:
        return int(session.get("hot_shape_top_k")) \
            if session is not None else CONFIG.prewarm_top_k
    except KeyError:
        return CONFIG.prewarm_top_k


def record_program(kind: str, cache_key, canon, batch,
                   session, payload_fn=None) -> None:
    """Executor hook: count a structural-program sighting and (first
    time) capture its AOT payload from the canonical input batch.
    ``cache_key`` is the in-process jit-cache key object — the AOT
    compiler re-derives the same key from the decoded fragment, which
    is what lets a pre-warmed program land in the exact slot the
    executor will probe. Gated per query by the ``prewarm_enabled``
    session property, with ``hot_shape_top_k`` as the query's
    new-entry budget. ``payload_fn`` overrides the default chain/
    stream payload builder for kinds with their own transport form
    (the streamed-join probe programs of exec/streamjoin.py)."""
    if not _session_allows(session):
        return
    # the budget is PER QUERY: keyed by the session's current query id
    # (runner/coordinator stamp one per execution), so a long-lived
    # session keeps contributing new shapes query after query instead
    # of going silent once its first queries spent the counter
    used = 0
    qid = None
    if session is not None:
        qid = getattr(session, "query_id", "") or ""
        state = getattr(session, _BUDGET_ATTR, None)
        if isinstance(state, tuple) and state[0] == qid:
            used = state[1]
    budget = _session_budget(session)

    def build() -> Optional[dict]:
        if session is not None and used >= budget:
            return None         # budget spent: hit-count only
        if payload_fn is not None:
            return payload_fn()
        return build_payload(kind, canon, batch)

    outcome = HOT_SHAPES.record(kind, repr(cache_key), build)
    if outcome == "new" and session is not None:
        try:
            setattr(session, _BUDGET_ATTR, (qid, used + 1))
        except AttributeError:      # frozen/foreign session object
            pass


# dictionaries above this entry count are not serialized into the
# registry (the payload would ship a whole string pool per shape);
# such shapes stay un-prewarmable rather than bloating the feed
MAX_DICT_ENTRIES = 64


def build_payload(kind: str, canon, batch) -> Optional[dict]:
    """The AOT transport form of one compiled shape: the canonical
    fragment (plan/serde wire JSON) + the observed input lane spec at
    its capacity bucket. None when the input contains lanes the AOT
    rebuilder cannot fabricate faithfully (nested ARRAY/MAP/ROW
    columns, large dictionaries)."""
    cols = []
    schema = {}
    for name, c in batch.columns.items():
        if c.elements is not None or c.elements2 is not None \
                or c.children is not None:
            return None
        ent: Dict[str, object] = {
            "name": name,
            "dtype": str(np.dtype(c.data.dtype)),
            "valid": c.valid is not None,
            "data2": (None if c.data2 is None
                      else str(np.dtype(c.data2.dtype))),
        }
        if c.dictionary is not None:
            vals = list(c.dictionary.values)
            if len(vals) > MAX_DICT_ENTRIES:
                return None
            ent["dict"] = [None if v is None else str(v)
                           for v in vals]
        cols.append(ent)
        schema[name] = c.type
    num_rows = ("int" if isinstance(batch.num_rows, int)
                else str(np.dtype(batch.num_rows.dtype)))
    out = {"kind": kind,
           "fragment": canon.wire_fragment(schema),
           "cols": cols,
           "capacity": int(batch.capacity),
           "num_rows": num_rows}
    literals = getattr(batch, "literals", None)
    if literals:
        # the program's literal vectors (exec/literals.py): their
        # dtypes and the slots bound, not their values
        out["literals"] = {"dtypes": sorted(literals),
                           "bound": int(batch.bound)}
    return out
