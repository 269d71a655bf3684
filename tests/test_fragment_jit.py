"""Fragment-JIT tests: pipeline chains compiled as one XLA program must
match eager execution (reference analog: compiled PageProcessor vs
interpreted path, sql/gen/PageFunctionCompiler.java:101 vs
ExpressionInterpreter). Floating-point aggregates compare with a 1e-9
relative tolerance: XLA may reassociate reductions when fusing, so the
compiled sum order legitimately differs from the eager one (SURVEY.md
§7 hard part 6)."""

import math

import pytest

from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
from trino_tpu.exec import Executor
from trino_tpu.planner import LogicalPlanner
from trino_tpu.planner.optimizer import optimize
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.sql.parser import parse_statement


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner()


def _both(runner, sql):
    stmt = parse_statement(sql)
    plan = optimize(
        LogicalPlanner(runner.catalogs, runner.session).plan(stmt))
    eager = Executor(runner.catalogs, runner.session,
                     fragment_jit=False).execute(plan).to_pylist()
    jitted = Executor(runner.catalogs, runner.session,
                      fragment_jit=True).execute(plan).to_pylist()
    return eager, jitted


def assert_rows_close(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert len(ra) == len(rb)
        for x, y in zip(ra, rb):
            if isinstance(x, float) and isinstance(y, float):
                assert math.isclose(x, y, rel_tol=1e-9, abs_tol=1e-9), \
                    (x, y)
            else:
                assert x == y, (x, y)


@pytest.mark.parametrize("q", [1, 6, 12])
def test_tpch_jit_matches_eager(runner, q):
    eager, jitted = _both(runner, TPCH_QUERIES[q])
    assert_rows_close(eager, jitted)


def test_jit_with_strings_and_nulls(runner):
    eager, jitted = _both(runner, """
        SELECT l_shipmode, count(*) AS n,
               sum(CASE WHEN l_quantity > 25 THEN 1 ELSE 0 END) AS big
        FROM lineitem WHERE l_returnflag <> 'N'
        GROUP BY l_shipmode ORDER BY l_shipmode
    """)
    assert eager == jitted


def test_jit_host_fallback(runner):
    # cast to varchar materializes rows on host -> the chain must fall
    # back to eager execution and still produce correct results
    eager, jitted = _both(runner, """
        SELECT cast(l_linenumber AS varchar) AS s, count(*)
        FROM lineitem GROUP BY 1 ORDER BY 1
    """)
    assert eager == jitted


def test_whole_table_hbm_path_matches_streaming(monkeypatch):
    """The device-backend whole-table fast path (exec/executor.py
    read_table_cached: splits concatenated once into an HBM-resident
    batch, aggregation fused into ONE program incl. final combine +
    post-processing) must agree with the default split-streaming path.
    Forced on here via TRINO_TPU_WHOLE_TABLE=1 (it is auto-off on the
    CPU test backend)."""
    monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "1")
    r = LocalQueryRunner()
    for q in (1, 6):
        stmt = parse_statement(TPCH_QUERIES[q])
        plan = optimize(
            LogicalPlanner(r.catalogs, r.session).plan(stmt))
        whole = Executor(r.catalogs, r.session,
                         fragment_jit=True).execute(plan).to_pylist()
        monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "0")
        stream = Executor(r.catalogs, r.session,
                          fragment_jit=True).execute(plan).to_pylist()
        monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "1")
        assert_rows_close(stream, whole)


def test_structural_jit_cache_reuses_program(monkeypatch):
    """Two separately planned executions of the same SQL must share one
    cached streaming-aggregation program (plan-fingerprint keyed —
    the ExpressionCompiler generated-class cache analog)."""
    from trino_tpu.obs.metrics import JIT_CACHE_LOOKUPS
    monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "1")
    r = LocalQueryRunner()
    sql = ("SELECT l_returnflag, sum(l_quantity), avg(l_discount) "
           "FROM lineitem WHERE l_shipdate <= DATE '1998-09-02' "
           "GROUP BY l_returnflag ORDER BY l_returnflag")
    outs = []
    hits = []
    for _ in range(2):
        stmt = parse_statement(sql)
        plan = optimize(
            LogicalPlanner(r.catalogs, r.session).plan(stmt))
        outs.append(Executor(r.catalogs, r.session,
                             fragment_jit=True).execute(plan).to_pylist())
        hits.append(JIT_CACHE_LOOKUPS.value(cache="stream",
                                            result="hit"))
    assert_rows_close(outs[0], outs[1])
    # both executions landed on the same fingerprint entry: the
    # second plan's whole-table program was a lookup hit
    assert hits[1] > hits[0]
