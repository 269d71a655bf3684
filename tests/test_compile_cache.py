"""Compile-amortization subsystem: canonical program keys
(exec/progkey.py), the hot-shape registry (exec/hotshapes.py), the AOT
compile path (exec/aot.py), and the coordinator/worker pre-warm
handshake — the kill-the-compile-tax acceptance battery.

Runs under JAX_PLATFORMS=cpu: fragment_jit is forced on where the jit
caches are the subject (TRINO_TPU_FRAGMENT_JIT / explicit arg), and
programs compile in milliseconds on the CPU backend while exercising
the identical cache/lower machinery the device path uses."""

import json
import time
import urllib.request

import pytest

from trino_tpu.exec import aot
from trino_tpu.exec import executor as exmod
from trino_tpu.exec.executor import Executor
from trino_tpu.exec.hotshapes import (HOT_SHAPES, HotShapeRegistry,
                                      record_program)
from trino_tpu.exec.progkey import (PROGRAMS, ProgramCache,
                                    canonicalize_nodes)
from trino_tpu.obs.metrics import METRICS, parse_exposition
from trino_tpu.plan.nodes import FilterNode, LimitNode, ProjectNode
from trino_tpu.planner import LogicalPlanner
from trino_tpu.planner.optimizer import optimize
from trino_tpu.rex import Call, Const, InputRef
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.session import Session
from trino_tpu.sql.parser import parse_statement
from trino_tpu.types import BIGINT, BOOLEAN

_JIT_LOOKUPS = METRICS.counter("trino_tpu_jit_cache_total")


def _plan(runner, sql):
    stmt = parse_statement(sql)
    return optimize(
        LogicalPlanner(runner.catalogs, runner.session).plan(stmt))


def _filter_chain(sym: str, const: int):
    pred = Call("<", (InputRef(sym, BIGINT), Const(const, BIGINT)),
                BOOLEAN)
    return [FilterNode(None, pred)]


# --------------------------------------------------------------------------
# canonical program keys
# --------------------------------------------------------------------------

def test_canonical_key_ignores_symbol_names():
    a = canonicalize_nodes(_filter_chain("l_quantity$3", 10))
    b = canonicalize_nodes(_filter_chain("totally_other$9", 10))
    assert a is not None and b is not None
    assert a.key == b.key
    # ...and the plan-side mappings differ, each onto the same
    # canonical name
    assert a.mapping["l_quantity$3"] == b.mapping["totally_other$9"]


def test_canonical_key_distinguishes_constants():
    """A literal VALUE is a slot of its program (exec/literals.py): two
    filters that differ only in their constant share the program key,
    and what tells the two plans apart is their literal key. A constant
    that fixes a shape, a LIMIT count, is still part of the key."""
    a = canonicalize_nodes(_filter_chain("x", 10))
    b = canonicalize_nodes(_filter_chain("x", 20))
    assert a.key == b.key
    assert a.literal_key != b.literal_key
    la = canonicalize_nodes([LimitNode(None, 10)])
    lb = canonicalize_nodes([LimitNode(None, 20)])
    assert la.key != lb.key


def test_canonical_key_rejects_volatile():
    pred = Call("<", (Call("random", (), BIGINT), Const(1, BIGINT)),
                BOOLEAN)
    assert canonicalize_nodes([FilterNode(None, pred)]) is None


def test_canonical_project_renames_inputs_and_outputs():
    n1 = ProjectNode(None, {"out$1": Call(
        "+", (InputRef("in$1", BIGINT), Const(1, BIGINT)), BIGINT)})
    n2 = ProjectNode(None, {"zz$7": Call(
        "+", (InputRef("aa$2", BIGINT), Const(1, BIGINT)), BIGINT)})
    c1, c2 = canonicalize_nodes([n1]), canonicalize_nodes([n2])
    assert c1.key == c2.key
    (sym, expr), = c1.nodes[0].assignments.items()
    assert sym.startswith("c") and expr.args[0].name.startswith("c")


def test_binding_normalizes_batch_column_order():
    """The Batch treedef (column-name tuple, columnar.py) is part of
    jax's trace-cache key: the binding must emit canonical columns in
    one deterministic order no matter how the source dict was
    ordered."""
    from trino_tpu.columnar import batch_from_pylist
    canon = canonicalize_nodes(_filter_chain("a", 5))
    b1 = batch_from_pylist({"a": [1, 2], "b": [3, 4]},
                           {"a": BIGINT, "b": BIGINT})
    b2 = batch_from_pylist({"b": [3, 4], "a": [1, 2]},
                           {"b": BIGINT, "a": BIGINT})
    r1 = canon.binding(b1).rename_in(b1)
    r2 = canon.binding(b2).rename_in(b2)
    assert list(r1.columns) == list(r2.columns)
    # round trip restores the plan's own names
    back = canon.binding(b1).rename_out(r1)
    assert set(back.columns) == {"a", "b"}


def test_renamed_plans_share_one_program_and_stay_correct():
    """Two alias spellings of the same query land on ONE cached chain
    program (1 miss + 1 hit) and both return correct rows — the
    binding renames the shared program's canonical output back to each
    plan's own symbols."""
    r = LocalQueryRunner()
    sqls = [
        "SELECT l_quantity + 41 AS a, l_discount * 2 AS b "
        "FROM lineitem WHERE l_quantity < 7 ORDER BY a LIMIT 5",
        "SELECT l_quantity + 41 AS zz, l_discount * 2 AS yy "
        "FROM lineitem WHERE l_quantity < 7 ORDER BY zz LIMIT 5"]
    h0 = _JIT_LOOKUPS.value(cache="chain", result="hit")
    outs = []
    for sql in sqls:
        plan = _plan(r, sql)
        eager = Executor(r.catalogs, r.session,
                         fragment_jit=False).execute(plan).to_pylist()
        jitted = Executor(r.catalogs, r.session,
                          fragment_jit=True).execute(plan).to_pylist()
        assert eager == jitted
        outs.append(jitted)
    assert outs[0] == outs[1]
    assert _JIT_LOOKUPS.value(cache="chain", result="hit") >= h0 + 1


# --------------------------------------------------------------------------
# warm-start proof (acceptance): second identical query through a
# FRESH Executor records zero jit_trace spans and renders "cache hit"
# --------------------------------------------------------------------------

def _span_names(trace):
    names = []

    def walk(sp):
        names.append(sp.name)
        for c in sp.children:
            walk(c)

    for root in trace.roots:
        walk(root)
    return names


def test_second_run_through_fresh_executor_is_warm(monkeypatch):
    from trino_tpu.obs.trace import QueryTrace
    monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "1")
    r = LocalQueryRunner()
    # unique constant -> a key no other test has populated
    sql = ("SELECT l_returnflag, sum(l_quantity), avg(l_discount) "
           "FROM lineitem WHERE l_quantity < 43 "
           "GROUP BY l_returnflag ORDER BY l_returnflag")
    outs, traces, stats = [], [], []
    for _ in range(2):
        plan = _plan(r, sql)     # fresh plan = fresh symbols
        session = Session(catalog="tpch", schema="tiny")
        session.trace = QueryTrace("warmtest")
        ex = Executor(r.catalogs, session, collect_stats=True,
                      fragment_jit=True)
        with session.trace.span("execute"):
            outs.append(ex.execute(plan).to_pylist())
        traces.append(session.trace)
        stats.append(ex.stats)
    assert outs[0] == outs[1]
    # run 1 compiled at least one program; run 2 compiled NOTHING
    assert "jit_trace" in _span_names(traces[0])
    assert "jit_trace" not in _span_names(traces[1])
    assert "dispatch" in _span_names(traces[1])
    # ...and the EXPLAIN ANALYZE rendering says so
    rendered = "\n".join(exmod.stats_lines(stats[1]))
    assert "cache hit" in rendered
    assert all(s.cache_hit is not False for s in stats[1])


# --------------------------------------------------------------------------
# hot-shape registry
# --------------------------------------------------------------------------

def test_registry_ranking_and_lru_bound():
    reg = HotShapeRegistry(capacity=3)
    for key, hits in (("a", 1), ("b", 5), ("c", 2)):
        for _ in range(hits):
            assert reg.record("chain", key, lambda: {"k": key})
    assert [e["key"] for e in reg.top(2)] == ["b", "c"]
    # recency breaks hit ties
    reg.record("chain", "a", lambda: {"k": "a"})     # a: 2 hits, newest
    assert [e["key"] for e in reg.top(3)] == ["b", "a", "c"]
    # capacity bound: coldest entry (fewest hits, oldest among ties)
    # evicted — never the hottest, never the just-admitted newcomer
    reg.record("chain", "d", lambda: {"k": "d"})
    assert len(reg) == 3
    keys = {e["key"] for e in reg.top(10)}
    assert "c" not in keys and {"b", "a", "d"} <= keys


def test_registry_unsupported_payload_not_tracked():
    reg = HotShapeRegistry(capacity=4)
    assert reg.record("chain", "nope", lambda: None) is None
    assert len(reg) == 0


def test_registry_merge_dedupes_and_counts():
    reg = HotShapeRegistry(capacity=4)
    reg.record("chain", "k1", lambda: {"x": 1})
    n = reg.merge([
        {"kind": "chain", "key": "k1", "hits": 3, "payload": {"x": 1}},
        {"kind": "stream", "key": "k2", "hits": 1, "payload": {"y": 2}},
        {"bogus": True},                      # skipped, no raise
    ])
    assert n == 2
    top = {e["key"]: e["hits"] for e in reg.top(10)}
    assert top["k1"] == 4 and top["k2"] == 1


def test_registry_export_delta_ships_growth_only():
    """Task statuses ship hit-count DELTAS: re-exporting an entry
    across N statuses must contribute exactly the new sightings, never
    re-count cumulative totals (which would skew the top-K ranking
    toward shapes touched by many short tasks)."""
    reg = HotShapeRegistry(capacity=4)
    reg.record("chain", "k1", lambda: {"x": 1})
    base = reg.hit_counts()
    reg.record("chain", "k1", lambda: {"x": 1})      # +1 hit
    reg.record("stream", "k2", lambda: {"y": 2})     # new: 1 hit
    delta = reg.export_delta(base)
    assert {e["key"]: e["hits"] for e in delta} == {"k1": 1, "k2": 1}
    coord = HotShapeRegistry(capacity=4)
    coord.merge(delta)
    # a second status with NO new sightings contributes nothing
    coord.merge(reg.export_delta(reg.hit_counts()))
    assert {e["key"]: e["hits"]
            for e in coord.top(10)} == {"k1": 1, "k2": 1}


def test_prewarm_enabled_gates_recording():
    r = LocalQueryRunner()
    plan = _plan(r, "SELECT l_quantity + 977 AS v FROM lineitem "
                    "WHERE l_quantity < 977 LIMIT 3")
    session = Session(catalog="tpch", schema="tiny")
    session.set("prewarm_enabled", False)
    n0 = len(HOT_SHAPES)
    Executor(r.catalogs, session, fragment_jit=True).execute(plan)
    assert len(HOT_SHAPES) == n0     # gated off: nothing recorded
    session.set("prewarm_enabled", True)
    Executor(r.catalogs, session, fragment_jit=True).execute(plan)
    assert len(HOT_SHAPES) > n0


# --------------------------------------------------------------------------
# AOT compile path
# --------------------------------------------------------------------------

def test_aot_compile_from_registry_payload(monkeypatch):
    """Record a real run's shapes, wipe the in-process caches (a fresh
    worker process), AOT-compile from the exported payloads alone — no
    data — and prove the next run hits the pre-warmed slots."""
    monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "1")
    r = LocalQueryRunner()
    sql = ("SELECT l_returnflag, sum(l_quantity), avg(l_discount) "
           "FROM lineitem WHERE l_quantity < 29 "
           "GROUP BY l_returnflag ORDER BY l_returnflag")
    plan = _plan(r, sql)
    ref = Executor(r.catalogs, r.session,
                   fragment_jit=True).execute(plan).to_pylist()
    entries = [e for e in HOT_SHAPES.top(50)]
    assert entries
    # round-trip through JSON: the endpoint serves exactly this form
    entries = json.loads(json.dumps(entries))
    PROGRAMS.clear("stream")
    PROGRAMS.clear("chain")
    summary = aot.compile_entries(entries)
    assert summary["compiled"] >= 1 and summary["errors"] == 0
    h0 = _JIT_LOOKUPS.value(cache="stream", result="hit") \
        + _JIT_LOOKUPS.value(cache="chain", result="hit")
    out = Executor(r.catalogs, r.session,
                   fragment_jit=True).execute(_plan(r, sql)).to_pylist()
    assert out == ref
    h1 = _JIT_LOOKUPS.value(cache="stream", result="hit") \
        + _JIT_LOOKUPS.value(cache="chain", result="hit")
    assert h1 > h0


def test_aot_second_compile_is_cached():
    entries = HOT_SHAPES.top(1)
    if not entries:
        pytest.skip("no recorded shapes in this process")
    aot.compile_entries(entries)            # ensure resident
    summary = aot.compile_entries(entries)
    assert summary["cached"] == len(entries)


# --------------------------------------------------------------------------
# coordinator endpoint + worker pre-warm handshake
# --------------------------------------------------------------------------

def test_hotshapes_endpoint_serves_ranked_payloads():
    from trino_tpu.server.coordinator import Coordinator
    r = LocalQueryRunner()
    plan = _plan(r, "SELECT l_quantity * 3 AS t FROM lineitem "
                    "WHERE l_quantity < 31 LIMIT 4")
    Executor(r.catalogs, r.session, fragment_jit=True).execute(plan)
    co = Coordinator().start()
    try:
        with urllib.request.urlopen(
                co.base_uri + "/v1/hotshapes?k=100") as resp:
            d = json.loads(resp.read())
        assert d["tracked"] == len(HOT_SHAPES)
        assert d["shapes"] and all(
            "payload" in e and "kind" in e for e in d["shapes"])
        # k bounds the list
        with urllib.request.urlopen(
                co.base_uri + "/v1/hotshapes?k=1") as resp:
            assert len(json.loads(resp.read())["shapes"]) == 1
    finally:
        co.stop()


def _wait(pred, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def test_prewarm_readiness_flag_rides_announce():
    from trino_tpu.server.coordinator import Coordinator
    from trino_tpu.server.task_worker import TaskWorkerServer
    co = Coordinator().start()
    cold = TaskWorkerServer().start()
    warm = TaskWorkerServer().start()
    try:
        cold.announce(co.base_uri, prewarm=False)
        warm.announce(co.base_uri, prewarm=True)
        assert _wait(lambda: co.worker_prewarmed.get(
            warm.base_uri) is True)
        assert co.worker_prewarmed.get(cold.base_uri) is False
        # warm-first scheduling preference, stable within classes
        assert co.live_workers()[0] == warm.base_uri
    finally:
        cold.stop()
        warm.stop()
        co.stop()


def test_prewarmed_worker_serves_first_fragment_as_cache_hit(
        monkeypatch):
    """The acceptance e2e: a distributed query records its fragment
    shapes into the coordinator registry (worker task status ->
    merge); the in-process jit caches are wiped (a fresh worker
    process); a NEW worker joins with prewarm=True, compiles the hot
    list before taking traffic, and the same query's first fragment on
    it is an in-process cache hit — asserted through /metrics like an
    operator would."""
    from trino_tpu.client import StatementClient
    from trino_tpu.server.coordinator import Coordinator
    from trino_tpu.server.task_worker import TaskWorkerServer
    monkeypatch.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    sql = ("SELECT l_returnflag, sum(l_quantity) AS q FROM lineitem "
           "WHERE l_quantity < 37 GROUP BY l_returnflag "
           "ORDER BY l_returnflag")
    co = Coordinator().start()
    w1 = TaskWorkerServer().start()
    try:
        w1.announce(co.base_uri, prewarm=False)
        assert _wait(lambda: co.live_workers())
        c = StatementClient(co.base_uri, catalog="tpch", schema="tiny")
        ref = c.execute(sql).rows
        assert ref
        # the worker-side fragment shapes reached the coordinator's
        # registry via the task status hotShapes delta
        assert any(e["kind"] in ("stream", "chain")
                   for e in HOT_SHAPES.top(50))
        # fresh-worker simulation: in-process caches wiped; ONLY the
        # pre-warm pull can repopulate them
        PROGRAMS.clear("stream")
        PROGRAMS.clear("chain")
        w2 = TaskWorkerServer().start()
        try:
            w2.announce(co.base_uri, prewarm=True)
            assert _wait(w2._is_prewarmed)
            assert (w2._prewarm_summary or {}).get("compiled", 0) >= 1
            def scrape():
                with urllib.request.urlopen(
                        w2.base_uri + "/metrics") as resp:
                    return parse_exposition(resp.read().decode())
            def hits(m):
                fam = m.get("trino_tpu_jit_cache_total", {})
                return sum(v for k, v in fam.items()
                           if "result=hit" in k)
            h0 = hits(scrape())
            rows = c.execute(sql).rows
            assert rows == ref
            m = scrape()
            assert hits(m) > h0
            aot_fam = m.get("trino_tpu_aot_compiles_total", {})
            assert sum(v for k, v in aot_fam.items()
                       if "result=compiled" in k) >= 1
        finally:
            w2.stop()
    finally:
        w1.stop()
        co.stop()


# --------------------------------------------------------------------------
# the one program cache (exec/progkey.py ProgramCache): every bucket
# behaves alike, so every case runs over all eight
# --------------------------------------------------------------------------

BUCKETS = ProgramCache.BUCKETS
_EVICTED = METRICS.counter("trino_tpu_jit_cache_evictions_total")
_SHED = METRICS.counter("trino_tpu_cache_pressure_evictions_total")


@pytest.fixture
def cache():
    """A cache of its own: the process's ``PROGRAMS`` keeps what other
    tests compiled."""
    return ProgramCache()


def _builder(calls):
    def build():
        calls.append(1)
        return lambda x: x + 1
    return build


def test_the_buckets_are_the_metric_labels():
    assert BUCKETS == ("chain", "stream", "ragged", "join", "window",
                       "streamjoin", "repartition", "spmd", "scan")
    assert isinstance(PROGRAMS, ProgramCache)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_program_misses_then_hits_and_counts_by_bucket(cache, bucket):
    calls = []
    key = ("k", bucket)
    miss0 = _JIT_LOOKUPS.value(cache=bucket, result="miss")
    hit0 = _JIT_LOOKUPS.value(cache=bucket, result="hit")
    first, hit = cache.program(bucket, key, _builder(calls),
                               "kind_" + bucket, key)
    assert hit is False and calls == [1]
    assert _JIT_LOOKUPS.value(cache=bucket, result="miss") == miss0 + 1
    again, hit = cache.program(bucket, key, _builder(calls),
                               "kind_" + bucket, key)
    assert hit is True and again is first and calls == [1]
    assert _JIT_LOOKUPS.value(cache=bucket, result="hit") == hit0 + 1
    # the program is the named jit of what build() gave
    assert first.program.startswith("kind_" + bucket + ":")
    assert int(first(1)) == 2
    # a key of None cannot be named: built for the call alone, kept
    # nowhere and counted nowhere
    local, hit = cache.program(bucket, None, _builder(calls),
                               "kind_" + bucket, None)
    assert hit is False and calls == [1, 1]
    assert local.program == f"kind_{bucket}:local"
    assert not cache.resident(bucket, None)
    assert _JIT_LOOKUPS.value(cache=bucket, result="miss") == miss0 + 1
    assert _JIT_LOOKUPS.value(cache=bucket, result="hit") == hit0 + 1


@pytest.mark.parametrize("bucket", BUCKETS)
def test_cache_put_honors_configured_capacity_and_counts_evictions(
        cache, bucket, monkeypatch):
    from trino_tpu.config import CONFIG
    monkeypatch.setattr(CONFIG, "jit_cache_entries", 2)
    other = BUCKETS[BUCKETS.index(bucket) - 1]
    cache.put(other, ("k", 0), object())
    e0 = _EVICTED.value()
    for i in range(4):
        cache.put(bucket, ("k", i), object())
    # the capacity is each bucket's own, oldest out first
    assert [cache.resident(bucket, ("k", i)) for i in range(4)] \
        == [False, False, True, True]
    assert _EVICTED.value() == e0 + 2
    assert cache.resident(other, ("k", 0))
    # a key that is already kept evicts nothing and keeps its program
    kept = cache.put(bucket, ("k", 3), object())
    assert cache.put(bucket, ("k", 3), object()) is kept
    assert cache.resident(bucket, ("k", 2))
    assert _EVICTED.value() == e0 + 2


@pytest.mark.parametrize("bucket", BUCKETS)
def test_deny_refuses_and_pops(cache, bucket):
    calls = []
    key, sibling = ("k", bucket), (("k", bucket), "full")
    cache.program(bucket, key, _builder(calls), bucket, key)
    cache.program(bucket, sibling, _builder(calls), bucket, key)
    assert not cache.denied(bucket, key)
    lookups0 = (_JIT_LOOKUPS.value(cache=bucket, result="miss")
                + _JIT_LOOKUPS.value(cache=bucket, result="hit"))
    cache.deny(bucket, key)
    assert cache.denied(bucket, key)
    assert not cache.resident(bucket, key)
    # refused: nothing is built and nothing is counted
    assert cache.program(bucket, key, _builder(calls), bucket,
                         key) is None
    assert calls == [1, 1]
    assert (_JIT_LOOKUPS.value(cache=bucket, result="miss")
            + _JIT_LOOKUPS.value(cache=bucket, result="hit")) == lookups0
    # a key is refused alone: the whole-table program of the same
    # canonical key (stream) and a program kept under a key that
    # CONTAINS the refused one (the mesh's fused aggregation) stay
    assert not cache.denied(bucket, sibling)
    assert cache.resident(bucket, sibling)
    # ...and only in its bucket
    other = BUCKETS[BUCKETS.index(bucket) - 1]
    assert not cache.denied(other, key)
    # clear() forgets the refusal with the programs
    cache.clear(bucket)
    assert not cache.denied(bucket, key)
    assert not cache.resident(bucket, sibling)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_put_and_resident_as_the_aot_compiler_uses_them(cache, bucket):
    """exec/aot.py compile_entry: skip what is resident, else compile
    and put; the executor's next lookup of that slot is a hit on the
    very program that was put."""
    key = ("k", bucket)
    assert not cache.resident(bucket, key)
    warmed = object()
    assert cache.put(bucket, key, warmed) is warmed
    assert cache.resident(bucket, key)
    calls = []
    got, hit = cache.program(bucket, key, _builder(calls), bucket, key)
    assert got is warmed and hit is True and calls == []
    assert all(not cache.resident(b, key) for b in BUCKETS
               if b != bucket)


@pytest.mark.parametrize("bucket", BUCKETS)
def test_shed_halves_the_three_plan_buckets_and_no_other(cache, bucket):
    for b in BUCKETS:
        for i in range(5):
            cache.put(b, ("k", i), object())
    s0 = _SHED.value(cache="jit")
    assert cache.shed() == 6
    assert _SHED.value(cache="jit") == s0 + 6
    kept = [cache.resident(bucket, ("k", i)) for i in range(5)]
    if bucket in ("chain", "stream", "ragged"):
        assert kept == [False, False, True, True, True]   # oldest go
    else:
        assert kept == [True] * 5


@pytest.mark.parametrize("bucket", BUCKETS)
def test_two_threads_inserting_at_capacity(cache, bucket, monkeypatch):
    """Insert-with-eviction from several threads at once (query
    threads and the pre-warm thread do): no entry is lost or counted
    twice, and the bucket never outgrows its capacity."""
    import sys
    import threading
    from trino_tpu.config import CONFIG
    monkeypatch.setattr(CONFIG, "jit_cache_entries", 8)
    n_threads, per_thread = 16, 150
    e0 = _EVICTED.value()
    errors, inserted = [], []
    start = threading.Barrier(n_threads)

    def insert(t):
        mine = 0
        try:
            start.wait(timeout=30)
            for i in range(per_thread):
                key = ("k", t, i)
                _, hit = cache.program(bucket, key,
                                       lambda: (lambda x: x), bucket, key)
                mine += not hit
                # a key every thread puts: whoever finds it gone (it
                # was evicted meanwhile) inserts it again
                token = object()
                mine += cache.put(bucket, ("shared", i % 4),
                                  token) is token
        except Exception as e:      # noqa: BLE001 — reported below
            errors.append(e)
        inserted.append(mine)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=insert, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert errors == [] and len(inserted) == n_threads
    keys = [("k", t, i) for t in range(n_threads)
            for i in range(per_thread)] \
        + [("shared", i) for i in range(4)]
    resident = sum(cache.resident(bucket, k) for k in keys)
    assert resident == 8
    # every insertion is still there or was evicted once, and counted
    assert sum(inserted) >= n_threads * per_thread
    assert _EVICTED.value() - e0 + resident == sum(inserted)
