"""Distributed tracing (ISSUE 15): span identity, W3C traceparent
propagation, id-preserving graft, OTLP export, device/CPU attribution,
and the EMA busy-shed signal.

The acceptance spine: a distributed (default MPP) query through real
worker HTTP servers produces ONE trace — every worker span born with
the query's 128-bit trace id and its true parent span id — served as
OTLP/JSON at GET /v1/trace/{query_id}, while EXPLAIN ANALYZE shows
per-stage device_ms and CPU-seconds distinct from wall time.
"""

import json
import re
import threading
import time
import urllib.request

import pytest

from trino_tpu.obs.otlp import (FileSink, HttpSink, spans_from_otlp,
                                trace_to_resource_spans,
                                validate_resource_spans)
from trino_tpu.obs.trace import (QueryTrace, format_traceparent,
                                 new_span_id, new_trace_id,
                                 parse_traceparent)
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.session import Session


# ---------------------------------------------------------------------------
# span identity + W3C context units
# ---------------------------------------------------------------------------

def test_span_and_trace_id_shapes():
    tid, sid = new_trace_id(), new_span_id()
    assert len(tid) == 32 and int(tid, 16) >= 0
    assert len(sid) == 16 and int(sid, 16) >= 0
    assert new_span_id() != sid          # 64-bit mints don't collide
    tp = format_traceparent(tid, sid)
    assert tp == f"00-{tid}-{sid}-01"
    assert parse_traceparent(tp) == (tid, sid)


@pytest.mark.parametrize("bad", [
    None, 42, "", "00-zz-yy-01", "00-" + "a" * 32,
    "00-" + "a" * 31 + "-" + "b" * 16 + "-01",
    "00-" + "g" * 32 + "-" + "b" * 16 + "-01"])
def test_parse_traceparent_rejects_malformed(bad):
    assert parse_traceparent(bad) is None


def test_every_span_carries_identity():
    tr = QueryTrace("q")
    with tr.span("a"):
        with tr.span("b"):
            pass
    a, b = tr.roots[0], tr.roots[0].children[0]
    assert len(a.span_id) == 16 and len(b.span_id) == 16
    assert a.span_id != b.span_id
    d = tr.to_dicts()[0]
    assert d["spanId"] == a.span_id
    assert d["children"][0]["spanId"] == b.span_id
    assert d["startUnixNanos"] > 0 and d["endUnixNanos"] >= \
        d["startUnixNanos"]


# ---------------------------------------------------------------------------
# the span-stack race regression: per-thread open stacks
# ---------------------------------------------------------------------------

def test_two_thread_span_stack_isolation():
    """A span opened on a second thread must NOT nest under whatever
    the first thread has open — the pre-identity implementation shared
    one stack and produced exactly that mis-nesting."""
    tr = QueryTrace("q")
    entered = threading.Event()
    release = threading.Event()
    errors = []

    def dispatcher():
        try:
            with tr.span("dispatch_side") as sp:
                with tr.span("dispatch_child"):
                    pass
                assert tr.current() is sp   # own stack, own top
            entered.set()
            release.wait(5)
        except Exception as e:     # noqa: BLE001
            errors.append(e)
            entered.set()

    with tr.span("executor_side") as main_sp:
        t = threading.Thread(target=dispatcher)
        t.start()
        assert entered.wait(5)
        # the executor thread's stack is untouched by the other thread
        assert tr.current() is main_sp
        release.set()
        t.join()
    assert not errors
    names = {r.name for r in tr.roots}
    # dispatch_side is a ROOT (not a child of executor_side), and its
    # own child nested correctly under it
    assert names == {"executor_side", "dispatch_side"}
    disp = next(r for r in tr.roots if r.name == "dispatch_side")
    assert [c.name for c in disp.children] == ["dispatch_child"]
    assert not next(r for r in tr.roots
                    if r.name == "executor_side").children


def test_explicit_parent_escape_hatch():
    """Cross-thread attachment is explicit: parent= places the span
    under a span owned by another thread."""
    tr = QueryTrace("q")
    with tr.span("root") as root:
        done = threading.Event()

        def worker():
            with tr.span("attached", parent=root, part=1):
                pass
            done.set()

        threading.Thread(target=worker).start()
        assert done.wait(5)
    assert [c.name for c in root.children] == ["attached"]


# ---------------------------------------------------------------------------
# id-preserving graft (the merge that replaced clock rebasing)
# ---------------------------------------------------------------------------

def test_graft_preserves_ids_and_realigns_clock():
    co = QueryTrace("query_9")
    minted = co.new_span_id()
    tp = co.traceparent(minted)
    # the worker side: born with the query's trace id + parent span id
    tid, psid = parse_traceparent(tp)
    wk = QueryTrace("task_9.0", trace_id=tid, parent_span_id=psid)
    assert wk.trace_id == co.trace_id
    with wk.span("task_execute", task="t0"):
        with wk.span("dispatch", cache="chain"):
            time.sleep(0.002)
    wire = wk.to_dicts()                 # what task status ships
    frag = co.record("stage_0_execute", co.origin_s,
                     co.origin_s + 0.05, span_id=minted)
    co.graft(frag, wire)
    merged = frag.children[0]
    # identity survived the wire
    assert merged.span_id == wk.roots[0].span_id
    assert merged.parent_id == minted
    assert merged.children[0].span_id == \
        wk.roots[0].children[0].span_id
    # the clock was REALIGNED via unix-nanos anchors, not rebased to
    # the parent's start: duration is preserved. Tolerance covers
    # time_ns-vs-perf_counter slew over the 2ms span (NTP can drift
    # them a few µs); a rebase bug would be off by the parent's ~50ms.
    assert merged.children[0].wall_s == pytest.approx(
        wk.roots[0].children[0].wall_s, abs=1e-4)


def test_graft_legacy_dicts_without_ids_still_merge():
    co = QueryTrace("q")
    parent = co.record("fragment_0_execute", co.origin_s,
                       co.origin_s + 0.01)
    co.graft(parent, [{"name": "task_execute", "startMillis": 0.0,
                       "wallMillis": 5.0}])
    child = parent.children[0]
    assert len(child.span_id) == 16      # minted on decode
    assert child.parent_id == parent.span_id


# ---------------------------------------------------------------------------
# OTLP: ResourceSpans shape, sinks, round-trip
# ---------------------------------------------------------------------------

def _demo_trace() -> QueryTrace:
    tr = QueryTrace("query_42")
    with tr.span("plan"):
        pass
    with tr.span("execute", rows=10):
        with tr.span("jit_trace", cache="chain", device_ms=1.5):
            pass
    return tr


def test_otlp_document_shape_and_roundtrip():
    tr = _demo_trace()
    doc = trace_to_resource_spans(tr, {"extra": "x"})
    validate_resource_spans(doc)
    # JSON round-trip stays valid (what the file sink persists)
    doc2 = json.loads(json.dumps(doc))
    validate_resource_spans(doc2)
    spans = spans_from_otlp(doc2)
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"plan", "execute", "jit_trace"}
    assert all(s["traceId"] == tr.trace_id for s in spans)
    assert by_name["jit_trace"]["parentSpanId"] == \
        by_name["execute"]["spanId"]
    assert "parentSpanId" not in by_name["plan"]
    res = doc2["resourceSpans"][0]["resource"]["attributes"]
    keys = {a["key"] for a in res}
    assert {"service.name", "trino_tpu.query_id", "extra"} <= keys
    # typed attribute values
    attrs = {a["key"]: a["value"]
             for a in by_name["execute"]["attributes"]}
    assert attrs["rows"] == {"intValue": "10"}


def test_otlp_validation_catches_bad_ids():
    doc = trace_to_resource_spans(_demo_trace())
    doc["resourceSpans"][0]["scopeSpans"][0]["spans"][0]["spanId"] = \
        "short"
    with pytest.raises(ValueError, match="spanId"):
        validate_resource_spans(doc)


def test_otlp_file_sink_appends_jsonl(tmp_path):
    path = str(tmp_path / "otlp.jsonl")
    sink = FileSink(path)
    sink.export(trace_to_resource_spans(_demo_trace()))
    sink.export(trace_to_resource_spans(_demo_trace()))
    lines = open(path).read().splitlines()
    assert len(lines) == 2
    for line in lines:
        validate_resource_spans(json.loads(line))


def test_otlp_http_sink_posts_to_collector():
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
    got = []

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            got.append((self.path, json.loads(self.rfile.read(n))))
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

    srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        sink = HttpSink(f"http://127.0.0.1:{srv.server_address[1]}")
        sink.export(trace_to_resource_spans(_demo_trace()))
        assert got and got[0][0] == "/v1/traces"
        validate_resource_spans(got[0][1])
    finally:
        srv.shutdown()
        srv.server_close()


def test_maybe_export_respects_config_and_session(tmp_path):
    from trino_tpu.config import CONFIG
    from trino_tpu.obs.otlp import maybe_export
    path = str(tmp_path / "sink.jsonl")
    old = CONFIG.otlp_file
    CONFIG.otlp_file = path
    try:
        tr = _demo_trace()
        s = Session(catalog="tpch", schema="tiny")
        s.set("otlp_export", False)
        assert maybe_export(tr, session=s) == 0    # opted out
        s.set("otlp_export", True)
        assert maybe_export(tr, session=s) == 1
        validate_resource_spans(json.loads(open(path).read()))
    finally:
        CONFIG.otlp_file = old


# ---------------------------------------------------------------------------
# device-time attribution
# ---------------------------------------------------------------------------

def test_device_time_attribution_on_jitted_dispatch(monkeypatch):
    """A served query's dispatch spans time the host and wait for
    nothing: no device_ms, no device_s. EXPLAIN ANALYZE waits for each
    program: device_ms rides its device_execute spans and the per-node
    stats, distinct from wall — forced through the fragment jit path
    (the CPU default would run eagerly and dispatch nothing)."""
    monkeypatch.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    r = LocalQueryRunner(
        session=Session(catalog="tpch", schema="tiny"),
        collect_node_stats=True)
    sql = ("SELECT l_orderkey + 1 AS k FROM lineitem "
           "WHERE l_quantity > 30")
    r.execute(sql)                       # cold: trace + compile
    res = r.execute(sql)                 # warm: pure device dispatches
    spans = []

    def walk(ds):
        for d in ds:
            spans.append(d)
            walk(d.get("children") or [])

    walk(res.trace.to_dicts())
    served = [d for d in spans if d["name"] == "dispatch"]
    assert served, "no dispatch span on the warm run"
    assert not any("device_ms" in (d.get("attrs") or {})
                   for d in spans)
    assert not any(s.device_s for s in res.stats)
    assert all(s.cpu_s >= 0 for s in res.stats)
    analyzed = r.execute("EXPLAIN ANALYZE " + sql)
    spans = []
    walk(analyzed.trace.to_dicts())
    dev = [d for d in spans if d["name"] == "device_execute"]
    assert dev, "no device_execute span under EXPLAIN ANALYZE"
    assert all("device_ms" in (d.get("attrs") or {}) for d in dev)
    assert any((d["attrs"]["device_ms"] or 0) > 0 for d in dev)
    text = "\n".join(row[0] for row in analyzed.rows)
    # per-node rollup: some node carries device time
    assert "device " in text


# ---------------------------------------------------------------------------
# worker-side: traceparent in, cpu/device/traceId out
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def workers():
    from trino_tpu.server.task_worker import TaskWorkerServer
    ws = [TaskWorkerServer().start() for _ in range(2)]
    yield ws
    for w in ws:
        w.stop()


def test_worker_status_carries_attribution_and_trace_id(workers):
    from trino_tpu.plan.serde import to_jsonable
    from trino_tpu.server.task_worker import RemoteTaskClient
    r = LocalQueryRunner(session=Session(catalog="tpch",
                                         schema="tiny"))
    plan = r.plan_sql("SELECT o_orderkey FROM orders "
                      "WHERE o_orderkey < 500")
    tid, psid = new_trace_id(), new_span_id()
    client = RemoteTaskClient(workers[0].base_uri)
    client.submit_fragment(
        "trace-task-1", to_jsonable(plan), catalog="tpch",
        schema="tiny", part=0, nparts=1, collect_stats=True,
        traceparent=format_traceparent(tid, psid))
    status = client.wait_done("trace-task-1")
    assert status["state"] == "FINISHED"
    # born with the QUERY's trace id, parented on the pre-minted span
    assert status["traceId"] == tid
    roots = status["spans"]
    assert roots and roots[0]["name"] == "task_execute"
    assert roots[0]["parentSpanId"] == psid
    assert len(roots[0]["spanId"]) == 16
    # scheduler CPU + device attribution in the status beat
    assert status["cpuSeconds"] > 0
    assert status["deviceSeconds"] >= 0


def test_traceparent_header_fallback(workers):
    """A payload without the field still propagates via the HTTP
    header (clients that predate the payload field)."""
    from trino_tpu.plan.serde import to_jsonable
    from trino_tpu.server.task_worker import RemoteTaskClient
    r = LocalQueryRunner(session=Session(catalog="tpch",
                                         schema="tiny"))
    plan = r.plan_sql("SELECT r_name FROM region")
    tid, psid = new_trace_id(), new_span_id()
    body = {"fragment": to_jsonable(plan), "catalog": "tpch",
            "schema": "tiny", "part": 0, "nparts": 1,
            "collect_stats": True, "properties": {}}
    req = urllib.request.Request(
        f"{workers[0].base_uri}/v1/task/trace-task-hdr",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 "traceparent": format_traceparent(tid, psid)},
        method="POST")
    with urllib.request.urlopen(req, timeout=30):
        pass
    status = RemoteTaskClient(workers[0].base_uri).wait_done(
        "trace-task-hdr")
    assert status["traceId"] == tid
    assert status["spans"][0]["parentSpanId"] == psid


# ---------------------------------------------------------------------------
# the distributed e2e: one trace id end to end on the default MPP path
# ---------------------------------------------------------------------------

JOIN_AGG_SQL = (
    "SELECT o_orderpriority, count(*) AS n FROM orders "
    "JOIN lineitem ON o_orderkey = l_orderkey "
    "WHERE l_quantity > 30 GROUP BY o_orderpriority")


def _walk_dicts(ds, out):
    for d in ds:
        out.append(d)
        _walk_dicts(d.get("children") or [], out)


def test_distributed_trace_single_identity_default_mpp(workers):
    from trino_tpu.exec.remote import DistributedHostQueryRunner
    before = {tid for w in workers for tid in w._tasks}
    d = DistributedHostQueryRunner(
        [w.base_uri for w in workers],
        session=Session(catalog="tpch", schema="tiny"),
        collect_node_stats=True)
    res = d.execute(JOIN_AGG_SQL)
    trace = res.trace
    assert len(trace.trace_id) == 32
    flat = []
    _walk_dicts(trace.to_dicts(), flat)
    stage_spans = {d["spanId"]: d for d in flat
                   if re.match(r"stage_\d+_execute", d["name"])}
    assert stage_spans, "no stage spans — did the MPP path run?"
    task_spans = [d for d in flat if d["name"] == "task_execute"]
    assert task_spans, "no worker subtrees grafted"
    # every worker task_execute is parented on the stage span the
    # coordinator pre-minted for its dispatch
    for t in task_spans:
        assert t.get("parentSpanId") in stage_spans
    # the stage spans carry the attribution rollup; device time only
    # under EXPLAIN ANALYZE (a served task waits for no program)
    for sp in stage_spans.values():
        attrs = sp.get("attrs") or {}
        assert "cpu_s" in attrs and "device_ms" not in attrs
    # the workers were BORN with the query's trace id (not merely
    # relabeled at graft time) — only THIS query's tasks, the module
    # fixture's registry still holds earlier tests' tasks
    born = [t.trace_id for w in workers
            for tid, t in w._tasks.items()
            if tid not in before and t.trace_id is not None]
    assert born and all(tid == trace.trace_id for tid in born)


def test_distributed_explain_analyze_shows_cpu_and_device(workers):
    from trino_tpu.exec.remote import DistributedHostQueryRunner
    d = DistributedHostQueryRunner(
        [w.base_uri for w in workers],
        session=Session(catalog="tpch", schema="tiny"),
        collect_node_stats=True)
    res = d.execute("EXPLAIN ANALYZE " + JOIN_AGG_SQL)
    text = "\n".join(r[0] for r in res.rows)
    # per-stage rollup: cpu seconds + device ms, distinct from wall
    tags = re.findall(r"stage \d+ x\d+ tasks \[cpu ([0-9.]+)s, "
                      r"device ([0-9.]+)ms\]", text)
    assert tags, text
    assert any(float(cpu) > 0 for cpu, _ in tags), tags
    # the analysis reached the workers: their stage spans time devices
    flat = []
    _walk_dicts(res.trace.to_dicts(), flat)
    stages = [s for s in flat if re.match(r"stage_\d+_execute", s["name"])]
    assert stages and all("device_ms" in (s.get("attrs") or {})
                          for s in stages)


def test_coordinator_v1_trace_endpoint_e2e(workers):
    """The acceptance e2e: a distributed query through a real
    coordinator + real worker HTTP servers, then GET /v1/trace/{id}
    serves OTLP/JSON where every span shares one trace id and worker
    spans hang off their dispatching stage spans."""
    from trino_tpu.client import StatementClient
    from trino_tpu.server import Coordinator
    co = Coordinator().start()
    try:
        for w in workers:
            co.add_worker(w.base_uri)
        res = StatementClient(co.base_uri, catalog="tpch",
                              schema="tiny").execute(JOIN_AGG_SQL)
        assert res.rows
        with urllib.request.urlopen(
                f"{co.base_uri}/v1/trace/{res.query_id}") as r:
            doc = json.loads(r.read())
        validate_resource_spans(doc)
        spans = spans_from_otlp(doc)
        trace_ids = {s["traceId"] for s in spans}
        assert len(trace_ids) == 1
        by_id = {s["spanId"]: s for s in spans}
        tasks = [s for s in spans if s["name"] == "task_execute"]
        assert tasks, "no worker spans in the exported trace"
        for t in tasks:
            parent = by_id.get(t.get("parentSpanId"))
            assert parent is not None, "worker span parent missing"
            assert re.match(r"(stage|fragment)_\d+_execute",
                            parent["name"])
        # resource attrs name the query
        attrs = {a["key"]: a["value"]
                 for a in doc["resourceSpans"][0]["resource"]
                 ["attributes"]}
        assert attrs["trino_tpu.query_id"]["stringValue"] == \
            res.query_id
        # unknown id → 404
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"{co.base_uri}/v1/trace/nope_404")
        assert exc.value.code == 404
    finally:
        co.stop()


# ---------------------------------------------------------------------------
# scheduler observables: EMA shed, quantum/level/queue-depth families
# ---------------------------------------------------------------------------

def test_busy_shed_ema_smooths_bursts():
    """Deterministic clock: an instant registration burst does NOT
    move the EMA (no shed), sustained load saturates it, and idling
    decays it back."""
    from trino_tpu.exec.taskexec import TaskExecutor
    now = [0.0]
    ex = TaskExecutor(1, clock=lambda: now[0], ema_tau_s=10.0)
    handles = [ex.register("q", f"t{i}") for i in range(8)]
    assert ex.open_tasks() == 8
    assert ex.open_tasks_ema() < 1.0     # the burst rides through
    now[0] = 30.0                        # sustained: ~3 time constants
    assert ex.open_tasks_ema() > 7.0
    for h in handles:
        h.close()
    now[0] = 60.0
    assert ex.open_tasks_ema() < 1.0     # quiet worker recovers
    # tau=0 pins the spot value (the pre-EMA behavior)
    ex0 = TaskExecutor(1, clock=lambda: now[0], ema_tau_s=0)
    ex0.register("q", "t")
    assert ex0.open_tasks_ema() == 1.0


def test_shed_reason_uses_ema_with_factor_floor():
    from trino_tpu.server.task_worker import TaskWorkerServer
    w = TaskWorkerServer(task_runners=1, busy_shed_factor=2,
                         busy_shed_ema_s=120.0).start()
    try:
        # cap = 2: spot past the floor but inside the burst window
        # ([cap, 2*cap)) and the EMA (tau=120s) has seen none of it —
        # no shed
        hs = [w.task_executor.register("q", f"t{i}") for i in range(3)]
        assert w._shed_reason() is None
        # ...but the hard ceiling (2 x cap) sheds REGARDLESS of the
        # EMA: smoothing tolerates a burst, never an unbounded pile-up
        hs.append(w.task_executor.register("q", "t3"))
        reason = w._shed_reason()
        assert reason is not None and "hard ceiling" in reason
        for h in hs:
            h.close()
    finally:
        w.stop()


def test_quantum_level_and_queue_depth_metrics():
    from trino_tpu.exec.taskexec import TaskExecutor
    from trino_tpu.obs.metrics import (TASK_QUANTUM_SECONDS,
                                       TASK_SCHED_LEVEL_SECONDS,
                                       TASK_SCHED_QUEUE_DEPTH)
    q0 = TASK_QUANTUM_SECONDS.count()
    l0 = TASK_SCHED_LEVEL_SECONDS.value(level="0")
    ex = TaskExecutor(1)
    h = ex.register("qm", "t0")
    h.acquire()
    h.checkpoint()                       # one accounted quantum
    # a second task waits → queue depth published
    h2 = ex.register("qm", "t1")
    waiter = threading.Thread(target=h2.acquire)
    waiter.start()
    deadline = time.time() + 5
    while ex.queue_depth() < 1 and time.time() < deadline:
        time.sleep(0.01)
    assert TASK_SCHED_QUEUE_DEPTH.value() >= 1
    h.close()
    waiter.join(5)
    h2.close()
    assert TASK_QUANTUM_SECONDS.count() > q0
    assert TASK_SCHED_LEVEL_SECONDS.value(level="0") >= l0


def test_exchange_wait_histogram_observes_blocked_scope():
    from trino_tpu.exec.taskexec import TaskExecutor
    from trino_tpu.obs.metrics import EXCHANGE_WAIT_SECONDS
    c0 = EXCHANGE_WAIT_SECONDS.count()
    ex = TaskExecutor(1)
    h = ex.register("qw", "t0")
    h.acquire()
    with h.blocked():
        time.sleep(0.005)
    h.close()
    assert EXCHANGE_WAIT_SECONDS.count() == c0 + 1


def test_scheduler_cpu_accounting_per_query():
    from trino_tpu.exec.taskexec import TaskExecutor
    ex = TaskExecutor(2)
    h = ex.register("qcpu", "t0")
    h.acquire()
    x = 0
    for _ in range(200_000):             # real CPU inside the quantum
        x += 1
    h.checkpoint()
    assert ex.query_cpu_seconds("qcpu") > 0
    h.close()
    assert h.cpu_s > 0                   # survives close for status
