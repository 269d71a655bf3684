"""Load-bearing session properties, end-to-end from the
X-Trino-Session header to executor behavior.

Reference: SystemSessionProperties.java:53-123 — the knobs clients and
tests key off. Each test observes the BEHAVIOR change, not just the
stored value.
"""

import time

import pytest

from trino_tpu.client import StatementClient
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.server.coordinator import Coordinator
from trino_tpu.session import SESSION_PROPERTIES, Session


def test_property_registry_breadth():
    for name in ("join_distribution_type", "join_reordering_strategy",
                 "task_concurrency", "spill_enabled",
                 "enable_dynamic_filtering", "distributed_sort",
                 "query_max_memory_per_node", "hash_partition_count",
                 "exchange_compression", "query_max_run_time",
                 "use_table_statistics", "pushdown_into_scan",
                 "multistage_execution", "exchange_partition_count",
                 "prewarm_enabled", "hot_shape_top_k",
                 "stream_chunk_rows", "result_cache_enabled",
                 "ragged_batching", "ragged_batch_max_rows",
                 "query_history_enabled", "learned_stats_enabled",
                 "slow_query_log_ms"):
        assert name in SESSION_PROPERTIES, name


def test_point_lookup_serving_properties_defaults_and_types():
    """ISSUE 18 knobs: both serving paths ship OFF by default (opt-in
    per session — dashboards turn them on), and the batch row cap
    defaults to the TRINO_TPU_RAGGED_BATCH_ROWS config value."""
    from trino_tpu.config import CONFIG
    s = Session()
    assert s.get("result_cache_enabled") is False
    assert s.get("ragged_batching") is False
    assert int(s.get("ragged_batch_max_rows")) == CONFIG.ragged_batch_rows
    s.set("result_cache_enabled", "true")
    assert s.get("result_cache_enabled") is True
    s.set("ragged_batching", "true")
    assert s.get("ragged_batching") is True
    s.set("ragged_batch_max_rows", "4096")
    assert s.get("ragged_batch_max_rows") == 4096


def test_observability_properties_defaults_and_types():
    """ISSUE 19 knobs: history and learned stats default ON (the
    always-on OperatorStats stance — the overhead tests hold them
    under budget), the slow-query log defaults OFF (0 = disarmed,
    any positive value is a millisecond threshold)."""
    s = Session()
    assert s.get("query_history_enabled") is True
    assert s.get("learned_stats_enabled") is True
    assert int(s.get("slow_query_log_ms")) == 0
    s.set("query_history_enabled", "false")
    assert s.get("query_history_enabled") is False
    s.set("learned_stats_enabled", "false")
    assert s.get("learned_stats_enabled") is False
    s.set("slow_query_log_ms", "250")
    assert s.get("slow_query_log_ms") == 250


def test_stream_chunk_rows_defaults_and_types():
    s = Session()
    assert int(s.get("stream_chunk_rows")) == 0   # auto-engage
    s.set("stream_chunk_rows", "4096")
    assert s.get("stream_chunk_rows") == 4096
    s.set("stream_chunk_rows", -1)                # disabled
    assert s.get("stream_chunk_rows") == -1


def test_prewarm_properties_defaults_and_types():
    s = Session()
    assert isinstance(s.get("prewarm_enabled"), bool)
    assert int(s.get("hot_shape_top_k")) > 0
    s.set("prewarm_enabled", "false")
    assert s.get("prewarm_enabled") is False
    s.set("hot_shape_top_k", "3")
    assert s.get("hot_shape_top_k") == 3


def test_multistage_execution_gates_the_stage_fragmenter():
    """The stage-DAG path IS the engine (default ON since PR 13); the
    session property is the explicit fallback knob to the flat
    scatter-gather path (end-to-end behavior in test_stage_mpp.py)."""
    from trino_tpu.exec.remote import RemoteScheduler
    sched = RemoteScheduler.__new__(RemoteScheduler)
    sched.session = Session()
    assert sched._multistage_enabled()
    sched.session.set("multistage_execution", False)
    assert not sched._multistage_enabled()
    assert int(sched.session.get("exchange_partition_count")) == 0
    # the pipelining knob ships default-on next to it
    assert sched.session.get("stage_pipelining") is True


def test_unknown_property_rejected():
    s = Session()
    with pytest.raises(KeyError):
        s.set("no_such_property", "1")


def test_query_max_run_time_fails_with_time_limit_error():
    """Deterministic on any backend speed: the scan blocks in the
    connector, the 1s deadline fires, and the client sees the query
    FAIL with EXCEEDED_TIME_LIMIT (the reference's QUERY_MAX_RUN_TIME
    semantics — a deadline breach is an engine failure with its own
    error identity, not a user cancel) long before the scan would
    finish."""
    from trino_tpu.catalog import CatalogManager
    from trino_tpu.connectors.tpch import TpchConnector

    class SlowTpch(TpchConnector):
        def read_split(self, split, columns):
            time.sleep(8)
            return super().read_split(split, columns)

    cats = CatalogManager()
    cats.register("tpch", SlowTpch())
    coord = Coordinator(catalogs=cats).start()
    try:
        c = StatementClient(
            coord.base_uri, catalog="tpch", schema="tiny",
            session_properties={"query_max_run_time": "1"})
        t0 = time.time()
        with pytest.raises(Exception, match="EXCEEDED_TIME_LIMIT"):
            c.execute("SELECT count(*) FROM nation")
        assert time.time() - t0 < 7   # stopped, not completed
    finally:
        coord.stop()


def test_exchange_compression_off_serves_store_frames():
    import struct
    from trino_tpu.serde import CODEC_LZ4, CODEC_STORE
    from trino_tpu.server.task_worker import (RemoteTaskClient,
                                              TaskWorkerServer)
    import urllib.request
    from trino_tpu.serde import native_available
    srv = TaskWorkerServer().start()
    try:
        c = RemoteTaskClient(srv.base_uri)
        sql = "SELECT o_comment FROM orders LIMIT 2000"
        # without the native library the default codec is already STORE
        default_codec = CODEC_LZ4 if native_available() else CODEC_STORE
        for tid, props, want in (
                ("t-lz4", {}, default_codec),
                ("t-raw", {"exchange_compression": "false"},
                 CODEC_STORE)):
            c.submit(tid, sql, properties=props)
            # raw frame: codec byte sits right after the 4-byte magic
            with urllib.request.urlopen(
                    f"{srv.base_uri}/v1/task/{tid}/results/0") as r:
                while r.status == 202:
                    r.close()
                    r = urllib.request.urlopen(
                        f"{srv.base_uri}/v1/task/{tid}/results/0")
                body = r.read()
            (codec,) = struct.unpack_from("<B", body, 4)
            assert codec == want, (tid, codec)
    finally:
        srv.stop()


def test_use_table_statistics_changes_plans():
    from trino_tpu.planner.logical import LogicalPlanner
    from trino_tpu.planner.optimizer import optimize
    from trino_tpu.sql.parser import parse_statement
    r = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    sql = ("SELECT count(*) FROM lineitem, orders, customer "
           "WHERE l_orderkey = o_orderkey AND o_custkey = c_custkey")
    stmt = parse_statement(sql)

    def plan_for(use_stats):
        s = Session(catalog="tpch", schema="tiny")
        s.set("use_table_statistics", use_stats)
        return optimize(LogicalPlanner(r.catalogs, s).plan(stmt),
                        r.catalogs, s)

    from trino_tpu.plan.nodes import JoinNode

    def joins(p):
        out = []
        stack = [p]
        while stack:
            n = stack.pop()
            if isinstance(n, JoinNode):
                out.append(n)
            stack.extend(n.sources)
        return out

    with_stats = joins(plan_for(True))
    without = joins(plan_for(False))
    assert any(j.distribution is not None for j in with_stats)
    assert all(j.distribution is None for j in without)
    # and the result is identical either way
    r.session.set("use_table_statistics", False)
    no_stats_rows = r.execute(sql).rows
    r.session.reset("use_table_statistics")
    assert no_stats_rows == r.execute(sql).rows


def test_join_distribution_type_forced_partitioned():
    from trino_tpu.planner.logical import LogicalPlanner
    from trino_tpu.planner.optimizer import optimize
    from trino_tpu.plan.nodes import JoinNode
    from trino_tpu.sql.parser import parse_statement
    r = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    sql = ("SELECT count(*) FROM lineitem JOIN orders "
           "ON l_orderkey = o_orderkey")
    s = Session(catalog="tpch", schema="tiny")
    s.set("join_distribution_type", "PARTITIONED")
    plan = optimize(LogicalPlanner(r.catalogs, s).plan(
        parse_statement(sql)), r.catalogs, s)
    stack, dists = [plan], []
    while stack:
        n = stack.pop()
        if isinstance(n, JoinNode):
            dists.append(n.distribution)
        stack.extend(n.sources)
    assert dists and all(d == "partitioned" for d in dists)
