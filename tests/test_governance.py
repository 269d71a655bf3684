"""Overload governance: queued admission, cluster memory pool +
low-memory killer, and deadline propagation (PR 10; reference:
InternalResourceGroup + ClusterMemoryManager + LowMemoryKiller +
QueryTracker enforceTimeLimits).

The chaos-style acceptance battery lives here: a burst over
hard_concurrency completes via queueing in fair order (none lost), an
over-memory query is killed naming the pool while a concurrent query
finishes, and a query_max_run_time breach cancels in-flight worker
attempts — with queue depth, pool bytes, and kill counters visible in
/metrics.
"""

import json
import threading
import time
import urllib.request

import pytest

from trino_tpu.client import ClientError, StatementClient
from trino_tpu.errors import error_info, http_status_for
from trino_tpu.obs.metrics import METRICS, parse_exposition
from trino_tpu.runner import QueryResult
from trino_tpu.server.coordinator import Coordinator, QueryTracker
from trino_tpu.server.memory import (ClusterMemoryManager,
                                     ClusterMemoryPool,
                                     MemoryGovernanceError)
from trino_tpu.server.resourcegroups import (ResourceGroup,
                                             ResourceGroupManager)
from trino_tpu.session import Session


def _wait_until(pred, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


class _GatedRunner:
    """Fake runner: execute() optionally reserves pool memory (tagged
    in the SQL), then blocks until its per-query gate opens or the
    query is canceled — admission/governance are runner-agnostic, so
    the tracker-level tests drive them deterministically without
    real query latency."""

    def __init__(self, session, gates, started, reservations):
        self.session = session
        self.gates = gates
        self.started = started
        self.reservations = reservations

    def execute(self, sql):
        self.started.append(sql)
        nbytes = self.reservations.get(sql, 0)
        if nbytes and self.session.memory is not None:
            self.session.memory.reserve(nbytes)
        gate = self.gates.get(sql)
        cancel = self.session.cancel
        while gate is not None and not gate.is_set():
            if cancel is not None and cancel.is_set():
                from trino_tpu.exec.executor import QueryError
                raise QueryError("Query was canceled")
            gate.wait(0.01)
        return QueryResult(["x"], [], [[1]])


# --- admission ------------------------------------------------------------

def test_admission_caps_concurrency_and_drains_fifo():
    """N queries against hard_concurrency=2: two run, the rest queue,
    and completions drain the queue in arrival (FIFO) order — none
    lost. Pure tracker-level (LocalQueryRunner-style in-process
    embedding): admission does not depend on the HTTP layer."""
    mgr = ResourceGroupManager()
    g = mgr.root.add(ResourceGroup("small", hard_concurrency=2,
                                   max_queued=100))
    mgr.add_selector(g)
    gates = {f"q{i}": threading.Event() for i in range(6)}
    started = []
    tracker = QueryTracker(
        lambda s: _GatedRunner(s, gates, started, {}),
        resource_groups=mgr)
    queries = [tracker.submit(f"q{i}", Session(user="alice"))
               for i in range(6)]
    _wait_until(lambda: len(started) == 2, what="2 running")
    time.sleep(0.1)
    # only the admitted pair ran (their two threads race each other,
    # so the first two are order-free)
    assert set(started) == {"q0", "q1"} and len(started) == 2
    assert g.running == 2 and g.queued() == 4
    assert sum(1 for q in queries if q.state == "QUEUED") == 4
    # completions dequeue in arrival order (FIFO within the leaf):
    # each release finishes one query, which admits exactly one
    # queued successor — the next in line
    for i in range(6):
        gates[f"q{i}"].set()
        _wait_until(lambda i=i: queries[i].state == "FINISHED",
                    what=f"q{i} finished")
    assert started[2:] == ["q2", "q3", "q4", "q5"]   # fair order
    assert all(q.state == "FINISHED" for q in queries)     # none lost
    assert g.running == 0 and g.queued() == 0


def test_queue_full_rejected_with_trino_error_identity():
    """Past max_queued the submit FAILS immediately with
    QUERY_QUEUE_FULL — the real StandardErrorCode code and
    INSUFFICIENT_RESOURCES type, counted in the rejection metric."""
    mgr = ResourceGroupManager()
    g = mgr.root.add(ResourceGroup("tiny", hard_concurrency=1,
                                   max_queued=1))
    mgr.add_selector(g)
    gates = {"q0": threading.Event()}
    started = []
    tracker = QueryTracker(
        lambda s: _GatedRunner(s, gates, started, {}),
        resource_groups=mgr)
    rej0 = METRICS.counter("trino_tpu_queue_rejections_total").value()
    q0 = tracker.submit("q0", Session())           # running
    q1 = tracker.submit("q1", Session())           # queued
    q2 = tracker.submit("q2", Session())           # rejected
    _wait_until(lambda: q2.state == "FAILED", what="rejection")
    code, etype = error_info("QUERY_QUEUE_FULL")
    assert q2.error["errorName"] == "QUERY_QUEUE_FULL"
    assert q2.error["errorCode"] == code == 0x0002_0000 + 2
    assert q2.error["errorType"] == etype == "INSUFFICIENT_RESOURCES"
    assert METRICS.counter(
        "trino_tpu_queue_rejections_total").value() == rej0 + 1
    # the rejection did not disturb the admitted pair: q0 completes,
    # then q1 (enqueued BEFORE the rejection) dequeues and completes
    gates["q0"].set()
    _wait_until(lambda: q0.state == "FINISHED", what="q0 finished")
    _wait_until(lambda: q1.state == "FINISHED", what="q1 drained")


def test_http_burst_completes_via_queueing():
    """The protocol-level acceptance leg: a burst of clients over
    hard_concurrency=1 all complete via nextUri polling while QUEUED
    (none lost, no errors), queuedTimeMillis is surfaced in the stats
    payload, and the queued-time histogram moves."""
    mgr = ResourceGroupManager()
    g = mgr.root.add(ResourceGroup("capped", hard_concurrency=1,
                                   max_queued=50))
    mgr.add_selector(g)
    co = Coordinator(resource_groups=mgr).start()
    h = METRICS.histogram("trino_tpu_query_queued_seconds")
    n0 = h.count()
    try:
        results = []
        errors = []

        def run():
            try:
                c = StatementClient(co.base_uri)
                results.append(c.execute(
                    "SELECT count(*) FROM tpch.tiny.region").rows)
            except Exception as e:      # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=run) for _ in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors
        assert results == [[[5]]] * 5           # all completed, none lost
        assert h.count() >= n0 + 1       # some queries really queued
        assert g.running == 0 and g.queued() == 0
        # queuedTimeMillis rides the protocol stats payload
        c = StatementClient(co.base_uri)
        r = c._request("POST", f"{co.base_uri}/v1/statement",
                       b"SELECT 1")
        assert "queuedTimeMillis" in r["stats"]
    finally:
        co.stop()


# --- memory governance ----------------------------------------------------

def test_low_memory_killer_kills_largest_survivor_completes():
    """Two concurrent queries against a small pool: the LARGEST is
    killed with CLUSTER_OUT_OF_MEMORY naming the victim and the pool
    state; the survivor completes. The memory-kill acceptance e2e at
    the tracker level."""
    memory = ClusterMemoryManager(ClusterMemoryPool(1000))
    gates = {"big": threading.Event(), "small": threading.Event()}
    started = []
    reservations = {"big": 700, "small": 400}
    tracker = QueryTracker(
        lambda s: _GatedRunner(s, gates, started, reservations),
        memory=memory)
    kills0 = METRICS.counter("trino_tpu_memory_kills_total").value()
    qbig = tracker.submit("big", Session())
    _wait_until(lambda: "big" in started, what="big running")
    qsmall = tracker.submit("small", Session())   # 700+400 > 1000
    _wait_until(lambda: qbig.state == "FAILED", what="big killed")
    err = qbig.error
    assert err["errorName"] == "CLUSTER_OUT_OF_MEMORY"
    assert err["errorType"] == "INSUFFICIENT_RESOURCES"
    # actionable: names the victim, its reservation, and the pool state
    assert qbig.query_id in err["message"]
    assert "700" in err["message"] and "low-memory killer" \
        in err["message"]
    assert "reserved" in err["message"]
    gates["small"].set()
    _wait_until(lambda: qsmall.state == "FINISHED", what="survivor")
    assert qsmall.state == "FINISHED"
    gates["big"].set()
    qbig.wait_done(5)
    assert METRICS.counter(
        "trino_tpu_memory_kills_total").value() == kills0 + 1
    # unregistration freed both reservations
    assert memory.pool.reserved_bytes() == 0


def test_group_soft_memory_limit_kills_within_group():
    """A resource group's soft memory limit governs ITS aggregate:
    the offending group's largest query dies, a query in another
    group is untouched."""
    mgr = ResourceGroupManager()
    etl = mgr.root.add(ResourceGroup("etl", hard_concurrency=10,
                                     soft_memory_limit_bytes=500))
    adhoc = mgr.root.add(ResourceGroup("adhoc", hard_concurrency=10))
    mgr.add_selector(etl, user_regex="etl")
    mgr.add_selector(adhoc)
    memory = ClusterMemoryManager(ClusterMemoryPool(10_000))
    gates = {k: threading.Event() for k in ("e1", "e2", "a1")}
    started = []
    reservations = {"e1": 300, "e2": 300, "a1": 5000}
    tracker = QueryTracker(
        lambda s: _GatedRunner(s, gates, started, reservations),
        resource_groups=mgr, memory=memory)
    qa = tracker.submit("a1", Session(user="bob"))   # other group, big
    qe1 = tracker.submit("e1", Session(user="etl"))
    _wait_until(lambda: len(started) >= 2, what="first two running")
    qe2 = tracker.submit("e2", Session(user="etl"))  # 600 > 500 in etl
    _wait_until(lambda: qe1.state == "FAILED"
                or qe2.state == "FAILED", what="etl kill")
    victim = qe1 if qe1.state == "FAILED" else qe2
    assert victim.error["errorName"] == "CLUSTER_OUT_OF_MEMORY"
    assert "global.etl" in victim.error["message"]
    assert qa.state == "RUNNING"        # 5000-byte outsider untouched
    for k in gates:
        gates[k].set()
    for q in (qa, qe1, qe2):
        q.wait_done(5)


def test_real_executor_feeds_pool_and_dies_with_trino_error():
    """The executor wiring, end to end through a REAL query: a join's
    capacity reservation flows into the pool via session.memory, and
    a pool breach fails the query with a CLUSTER_OUT_OF_MEMORY
    QueryError in the reserving thread."""
    from trino_tpu.exec.executor import QueryError
    from trino_tpu.runner import LocalQueryRunner
    # the tiny-schema join's largest capacity reservation is ~940 KiB
    # (its two key lanes, which the plan above it reads) — a 512 KiB
    # pool guarantees the breach
    memory = ClusterMemoryManager(ClusterMemoryPool(1 << 19))
    s = Session(catalog="tpch", schema="tiny")
    # pin the MATERIALIZED path: with morsel streaming engaged this
    # query now legitimately completes under the pool by reserving
    # its streamed peak (tests/test_stream_exec.py proves that); this
    # test's subject is the un-streamed wiring + killer identity
    s.set("stream_chunk_rows", -1)
    s.memory = memory.register("qx", kill_fn=lambda m, n: None)
    lr = LocalQueryRunner(session=s)
    with pytest.raises(QueryError) as exc:
        lr.execute("SELECT count(*), sum(l_orderkey + o_orderkey) "
                   "FROM lineitem JOIN orders ON l_orderkey = o_orderkey")
    assert getattr(exc.value, "error_name", None) \
        == "CLUSTER_OUT_OF_MEMORY"
    assert "low-memory killer" in str(exc.value)
    memory.unregister("qx")


def test_query_max_memory_cap_exceeds_global_limit():
    """The per-query cluster cap (query_max_memory) fails ONLY the
    offending query with EXCEEDED_GLOBAL_MEMORY_LIMIT — no other
    query need die for it."""
    memory = ClusterMemoryManager(ClusterMemoryPool(1 << 30))
    ctx = memory.register("qy", kill_fn=lambda m, n: None,
                          query_limit_bytes=100)
    with pytest.raises(MemoryGovernanceError) as exc:
        ctx.reserve(500)
    assert exc.value.error_name == "EXCEEDED_GLOBAL_MEMORY_LIMIT"
    memory.unregister("qy")


def test_memory_kill_error_name_classifies():
    """errors.classify maps governance messages to the Trino names
    (the satellite contract: proper error identity, never a generic
    500 / GENERIC_INTERNAL_ERROR)."""
    from trino_tpu.errors import classify
    from trino_tpu.exec.executor import QueryError
    name, code, etype = classify(QueryError(
        "The cluster is out of memory ..."))
    assert name == "CLUSTER_OUT_OF_MEMORY"
    assert etype == "INSUFFICIENT_RESOURCES"
    name, _, _ = classify(QueryError(
        "Query q exceeded the global memory limit of 5 bytes"))
    assert name == "EXCEEDED_GLOBAL_MEMORY_LIMIT"
    name, _, _ = classify(QueryError(
        "Query exceeded the maximum run time (query_max_run_time)"))
    assert name == "EXCEEDED_TIME_LIMIT"
    # explicit error_name beats message sniffing
    name, _, _ = classify(QueryError("whatever",
                                     error_name="QUERY_QUEUE_FULL"))
    assert name == "QUERY_QUEUE_FULL"
    assert http_status_for("INSUFFICIENT_RESOURCES") == 429
    assert http_status_for("USER_ERROR") == 400
    assert http_status_for("INTERNAL_ERROR") == 500


# --- deadline propagation -------------------------------------------------

def test_deadline_cancels_inflight_worker_attempts():
    """The deadline acceptance chaos: a stage-path distributed query
    blocks in a worker-side scan; the 1s query_max_run_time breach
    fails the query with EXCEEDED_TIME_LIMIT AND aborts the in-flight
    attempts ON the worker (verified via the worker's task registry +
    abort metric) — not merely the next coordinator poll."""
    from trino_tpu.catalog import CatalogManager
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.exec.remote import DistributedHostQueryRunner
    from trino_tpu.server.task_worker import TaskWorkerServer

    gate = threading.Event()

    class BlockingTpch(TpchConnector):
        remote_scan_ok = True

        def read_split(self, split, columns):
            gate.wait(30)
            return super().read_split(split, columns)

    cats = CatalogManager()
    cats.register("tpch", BlockingTpch())
    worker = TaskWorkerServer(catalogs=cats).start()
    aborted = METRICS.counter(
        "trino_tpu_worker_tasks_aborted_total")
    deadline_cancels = METRICS.counter(
        "trino_tpu_deadline_cancels_total")
    a0, d0 = aborted.value(), deadline_cancels.value()
    tracker = QueryTracker(
        lambda s: DistributedHostQueryRunner(
            [worker.base_uri], session=s, catalogs=cats))
    try:
        session = Session(catalog="tpch", schema="tiny")
        session.set("query_max_run_time", 1)
        session.set("multistage_execution", True)
        q = tracker.submit(
            "SELECT count(*) FROM lineitem", session)
        # the worker accepted an attempt (it is blocked in the scan)
        _wait_until(lambda: len(worker._tasks) > 0,
                    what="worker attempt in flight")
        assert q.wait_done(15), "query did not reach a terminal state"
        assert q.state == "FAILED"
        assert q.error["errorName"] == "EXCEEDED_TIME_LIMIT"
        assert "maximum run time" in q.error["message"]
        assert deadline_cancels.value() == d0 + 1
        # the cancel reached the WORKER: its in-flight task was
        # DELETEd (aborted + dropped from the registry) by the
        # scheduler's watch, not left running to completion
        _wait_until(lambda: aborted.value() > a0,
                    what="worker-side abort")
        _wait_until(lambda: len(worker._tasks) == 0,
                    what="worker task registry drained")
    finally:
        gate.set()
        worker.stop()


def test_deadline_fires_while_still_queued():
    """query_max_run_time budgets the WHOLE run including queue time
    (the reference's QUERY_MAX_RUN_TIME): a query that spends its
    budget QUEUED behind a wedged group dies at t=limit with
    EXCEEDED_TIME_LIMIT — it does not wait for admission."""
    mgr = ResourceGroupManager()
    g = mgr.root.add(ResourceGroup("wedged", hard_concurrency=1,
                                   max_queued=10))
    mgr.add_selector(g)
    gates = {"blocker": threading.Event()}
    started = []
    tracker = QueryTracker(
        lambda s: _GatedRunner(s, gates, started, {}),
        resource_groups=mgr)
    blocker = tracker.submit("blocker", Session())    # wedges the slot
    _wait_until(lambda: "blocker" in started, what="blocker running")
    s = Session()
    s.set("query_max_run_time", 1)
    victim = tracker.submit("victim", Session(properties=s.properties))
    assert victim.state == "QUEUED"
    assert victim.wait_done(5), "queued query missed its deadline"
    assert victim.state == "FAILED"
    assert victim.error["errorName"] == "EXCEEDED_TIME_LIMIT"
    assert "victim" not in started        # it never ran
    # the dead entry was withdrawn from the group queue: it no longer
    # holds max_queued capacity and will never burn a concurrency slot
    _wait_until(lambda: g.queued() == 0, what="dead entry withdrawn")
    # a canceled-while-queued query is withdrawn the same way
    q2 = tracker.submit("victim2", Session())
    assert q2.state == "QUEUED" and g.queued() == 1
    tracker.cancel(q2.query_id)
    assert q2.state == "CANCELED" and g.queued() == 0
    gates["blocker"].set()
    blocker.wait_done(5)
    assert g.running == 0


def test_parse_data_size():
    """config.properties query.max-memory accepts the reference's
    DataSize strings, not only raw byte counts."""
    from trino_tpu.server.memory import parse_data_size
    assert parse_data_size("50GB") == 50 << 30
    assert parse_data_size("512MB") == 512 << 20
    assert parse_data_size("1.5GB") == int(1.5 * (1 << 30))
    assert parse_data_size(" 2kB ") == 2048
    assert parse_data_size("12345") == 12345
    assert parse_data_size("100B") == 100


def test_deadline_enforced_by_standalone_runner():
    """A LocalQueryRunner used without a coordinator derives the
    deadline itself: the executor stops between plan nodes with
    EXCEEDED_TIME_LIMIT."""
    from trino_tpu.exec.executor import QueryError
    from trino_tpu.runner import LocalQueryRunner
    s = Session(catalog="tpch", schema="tiny")
    s.set("query_max_run_time", 1)
    s.deadline = time.monotonic() - 0.1      # already spent
    lr = LocalQueryRunner(session=s)
    with pytest.raises(QueryError) as exc:
        lr.execute("SELECT count(*) FROM nation")
    assert getattr(exc.value, "error_name", None) \
        == "EXCEEDED_TIME_LIMIT"


# --- observability of the governance layer --------------------------------

def test_governance_metrics_visible_in_exposition():
    """The acceptance scrape: queue depth, memory-pool bytes, and the
    kill/rejection/deadline counters all render at /metrics on a
    governed coordinator."""
    co = Coordinator(memory_pool_bytes=123456789).start()
    try:
        StatementClient(co.base_uri).execute("SELECT 1")
        raw = urllib.request.urlopen(
            co.base_uri + "/metrics").read().decode()
        fams = parse_exposition(raw)
        assert "trino_tpu_queue_depth" in fams
        assert fams["trino_tpu_memory_pool_bytes"][
            ("kind=total",)] == 123456789
        assert "trino_tpu_memory_kills_total" in fams
        assert "trino_tpu_queue_rejections_total" in fams
        assert "trino_tpu_deadline_cancels_total" in fams
        assert "trino_tpu_query_queued_seconds_count" in raw
        # the cluster overview carries the pool state for the web UI
        cl = json.loads(urllib.request.urlopen(
            co.base_uri + "/v1/cluster").read())
        assert cl["memory"]["maxBytes"] == 123456789
        assert "reservedBytes" in cl["memory"]
        # a default (unconfigured) coordinator still has REAL
        # admission: the root group shows in the group infos
        assert any(i["name"] == "global"
                   for i in co.resource_group_infos())
    finally:
        co.stop()
