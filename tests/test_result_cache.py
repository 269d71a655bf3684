"""Coordinator result cache (exec/resultcache.py): repeated identical
point queries short-circuit BEFORE dispatch — zero new worker tasks,
asserted via /metrics — a connector data-version bump forces a miss,
and the memory-pressure ladder sheds cached results ahead of compiled
programs.
"""

import urllib.request

from trino_tpu.client import StatementClient
from trino_tpu.exec.resultcache import (RESULT_CACHE, ResultCache,
                                        RESULT_CACHE_EVICTIONS,
                                        RESULT_CACHE_LOOKUPS)
from trino_tpu.server.coordinator import Coordinator
from trino_tpu.server.task_worker import TaskWorkerServer

PROPS = {"result_cache_enabled": "true"}


def _scrape(base_uri: str, name: str, **labels) -> float:
    """Sum a counter family out of a live /metrics exposition."""
    with urllib.request.urlopen(f"{base_uri}/metrics") as r:
        text = r.read().decode()
    want = [f'{k}="{v}"' for k, v in labels.items()]
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and all(w in line for w in want):
            total += float(line.rsplit(None, 1)[-1])
    return total


def test_repeat_query_hits_with_zero_dispatched_tasks():
    """The ISSUE 18 acceptance shape: the second identical dashboard
    query is served from the coordinator cache — the worker's
    dispatched-task counter does not move."""
    worker = TaskWorkerServer().start()
    co = Coordinator(worker_uris=[worker.base_uri]).start()
    try:
        c = StatementClient(co.base_uri, session_properties=PROPS)
        sql = "SELECT n_name FROM tpch.tiny.nation WHERE n_nationkey = 7"
        first = c.execute(sql).rows
        tasks_before = _scrape(worker.base_uri,
                               "trino_tpu_worker_tasks_total")
        hits_before = _scrape(co.base_uri,
                              "trino_tpu_result_cache_lookups_total",
                              result="hit")
        second = c.execute(sql).rows
        assert second == first == [["GERMANY"]]
        assert _scrape(worker.base_uri,
                       "trino_tpu_worker_tasks_total") == tasks_before
        assert _scrape(co.base_uri,
                       "trino_tpu_result_cache_lookups_total",
                       result="hit") == hits_before + 1
    finally:
        co.stop()
        worker.stop()


def test_connector_version_bump_invalidates():
    """An INSERT bumps the memory connector's data version: the cached
    entry is dropped on the next lookup (reason=invalidated) and the
    query re-executes against fresh data."""
    co = Coordinator().start()
    try:
        c = StatementClient(co.base_uri, session_properties=PROPS)
        c.execute("CREATE TABLE memory.default.rc_inv (x bigint)")
        c.execute("INSERT INTO memory.default.rc_inv VALUES (1), (2)")
        sql = "SELECT x FROM memory.default.rc_inv WHERE x = 1"
        assert c.execute(sql).rows == [[1]]     # miss + store
        h0 = RESULT_CACHE_LOOKUPS.value(result="hit")
        assert c.execute(sql).rows == [[1]]     # hit
        assert RESULT_CACHE_LOOKUPS.value(result="hit") == h0 + 1
        i0 = RESULT_CACHE_EVICTIONS.value(reason="invalidated")
        c.execute("INSERT INTO memory.default.rc_inv VALUES (1)")
        assert c.execute(sql).rows == [[1], [1]]    # fresh, not stale
        assert RESULT_CACHE_EVICTIONS.value(
            reason="invalidated") == i0 + 1
    finally:
        co.stop()


def test_cache_off_by_default_no_lookups():
    co = Coordinator().start()
    try:
        c = StatementClient(co.base_uri)    # no session property
        sql = "SELECT r_name FROM tpch.tiny.region WHERE r_regionkey = 1"
        s0 = sum(v for _, v in RESULT_CACHE_LOOKUPS.samples())
        assert c.execute(sql).rows == c.execute(sql).rows
        assert sum(v for _, v in RESULT_CACHE_LOOKUPS.samples()) == s0
    finally:
        co.stop()


def test_pressure_ladder_sheds_result_cache_before_jit(monkeypatch):
    """evict_cache_pressure drops cached result rows (cheap to
    rebuild: saved latency) BEFORE halving the structural jit caches
    (expensive to rebuild: saved compile storms), and counts the shed
    under {cache="result"}."""
    from trino_tpu.exec import executor as ex
    from trino_tpu.exec.progkey import PROGRAMS
    from trino_tpu.obs.metrics import CACHE_PRESSURE_EVICTS

    # drain the scan/replicate tiers other tests populated — they
    # rank ahead of the result cache and would absorb a tiny deficit
    ex.evict_cache_pressure(1 << 40)
    RESULT_CACHE.put(("test-pressure",), ["x"], ["bigint"],
                     [[i] for i in range(64)], (("memory", 1),))
    assert len(RESULT_CACHE) >= 1
    nbytes = RESULT_CACHE.bytes()
    assert ex.cache_memory_bytes() >= nbytes    # governance sees it
    PROGRAMS.clear("chain")     # shedding takes the OLDEST half
    PROGRAMS.put("chain", ("sentinel-a",), object())
    PROGRAMS.put("chain", ("sentinel-b",), object())
    r0 = CACHE_PRESSURE_EVICTS.value(cache="result")
    j0 = CACHE_PRESSURE_EVICTS.value(cache="jit")
    entries_before = len(RESULT_CACHE)
    freed = ex.evict_cache_pressure(1)      # tiny deficit: result-cache
    assert freed >= 1                       # rung alone must cover it
    assert len(RESULT_CACHE) < entries_before
    assert CACHE_PRESSURE_EVICTS.value(cache="result") > r0
    # jit tier untouched
    assert CACHE_PRESSURE_EVICTS.value(cache="jit") == j0
    assert PROGRAMS.resident("chain", ("sentinel-a",))
    assert PROGRAMS.resident("chain", ("sentinel-b",))
    PROGRAMS.clear("chain")


def test_lru_and_capacity_bounds():
    rc = ResultCache(capacity_bytes=4096)
    v = (("memory", 1),)
    # an entry over capacity//4 is refused outright
    assert not rc.put(("big",), ["x"], ["varchar"],
                      [["y" * 8192]], v)
    for i in range(64):
        rc.put((f"k{i}",), ["x"], ["bigint"], [[i] * 8], v)
    assert rc.bytes() <= 4096
    assert rc.get(("k0",), v) is None       # LRU-evicted
    newest = rc.get(("k63",), v)
    assert newest is not None and newest[2] == [[63] * 8]
