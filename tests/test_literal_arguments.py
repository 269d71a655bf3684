"""A query's literals as arguments of its programs (exec/literals.py)
and a fresh pushed constraint derived from resident lanes
(exec/scanderive.py), through the SERVED path at ``tpch.tiny`` on the
CPU: a coordinator started as ``benchmark/harness/engine.py`` starts
one, fragments jitted and tables resident as on the chip.

- a spread of TPC-H's substitution domain (every q6 DISCOUNT, both
  QUANTITYs, every year; every q3 SEGMENT; q1 at DELTA 60, 90, 120)
  equals the benchmark's reference (``benchmark/reference/
  tpch_answers.py``) for each set, and so do 8 seeded draws a class
  (PR 40's served-draws check, here in tier-1);
- once a class has met two sets, as the benchmark's set-up does (the
  validation set, then a drawn one), a third set compiles nothing and
  fills nothing: no new program, no ``jit_trace`` span, no compile
  request, no ``scan_fill`` span; its pushed constraints are derived;
- two texts that differ only in literals have one program key, and a
  LIMIT count stays part of it; ``EXECUTE ... USING`` with two argument
  sets compiles once;
- the host folds a column-free subtree to the value ``exec/expr.py``
  computes for it.
"""

import json
import os
import random
import sys

import numpy as np
import pytest

from trino_tpu.exec.progkey import PROGRAMS, canonicalize_nodes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
TINY = 0.01
CLASSES = ("q1", "q3", "q6")
DRAWS = 8
P = "trino_tpu_query_phase_seconds"


def bench_module(name: str):
    """A module of ``benchmark/`` (it is no package of the program)."""
    import importlib
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module(name)
    finally:
        sys.path.remove(BENCH)


params = bench_module("reference.tpch_params")
SEGMENTS = params.SEGMENTS

# the spread of the domain: q6 covers every DISCOUNT, both QUANTITYs
# and every year; q3 every SEGMENT; q1 the ends and the middle
SPREAD = ([("q1", (d,)) for d in (60, 90, 120)]
          + [("q3", (s, f"1995-03-{d:02d}"))
             for s, d in zip(SEGMENTS, (1, 9, 15, 23, 31))]
          + [("q6", (f"{1993 + i % 5}-01-01", f"0.{2 + i:02d}", 24 + i % 2))
             for i in range(8)])


def drawn(cls):
    rng = random.Random(f"2718281829/params/{cls}")
    return [params.draw(cls, rng) for _ in range(DRAWS)]


DRAWN = [(c, p) for c in CLASSES for p in drawn(c)]
# met after two other sets of their class: the window's case
THIRD = [("q1", (77,)), ("q3", ("AUTOMOBILE", "1995-03-27")),
         ("q6", ("1996-01-01", "0.03", 24))]
WARM = [("q1", params.validation("q1")), ("q1", (101,)),
        ("q3", params.validation("q3")), ("q3", ("HOUSEHOLD", "1995-03-04")),
        ("q6", params.validation("q6")), ("q6", ("1993-01-01", "0.08", 25))]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "tpch_sf1_qgen_1chip.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def templates():
    traffic = bench_module("harness.traffic")
    return {c: traffic.load_sql(c, {"queries_dir": params.TEMPLATES_DIR})
            for c in CLASSES}


@pytest.fixture(scope="module")
def reference():
    answers = bench_module("reference.tpch_answers")
    return answers.Answers(TINY, SPREAD + DRAWN + THIRD + WARM + [
        ("q6", ("1997-01-01", "0.05", 25))])


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    mp.setenv("TRINO_TPU_WHOLE_TABLE", "1")
    mp.setenv("TRINO_TPU_DEVICE_GEN", "1")
    eng = bench_module("harness.engine").Engine(
        "tpch", "tiny", str(tmp_path_factory.mktemp("state")))
    yield eng
    eng.stop()
    mp.undo()


def serve(engine, templates, cls, p):
    res = engine.client("t").execute(
        params.substitute(templates[cls], p))
    assert res.state == "FINISHED", res.error
    return res


def check(res, reference, config, cls, p):
    gaps = bench_module("reference.compare").gaps
    mismatches, rel = gaps(res.rows, reference.answer(cls, p))
    assert mismatches <= config["limits"]["exact_mismatches"], p
    assert rel <= config["limits"]["max_rel_err"], p


@pytest.mark.parametrize("cls,p", THIRD)
def test_a_third_set_compiles_nothing_and_fills_nothing(
        engine, templates, reference, config, cls, p):
    """First in the file: the engine has met no other set of ``cls``
    than the warm-up's two, as a benchmark window after its set-up."""
    assert p not in [w for c, w in WARM if c == cls]
    for c, w in WARM:
        if c == cls:
            serve(engine, templates, c, w)
    jax_counters = bench_module("harness.counters").JaxCounters()
    before_jax = jax_counters.snapshot()
    before = engine.counters()
    programs = {b: dict(PROGRAMS._programs[b]) for b in PROGRAMS.BUCKETS}
    res = serve(engine, templates, cls, p)
    after = engine.counters()
    check(res, reference, config, cls, p)

    def grew(key):
        return after.get(key, 0.0) - before.get(key, 0.0)
    assert {b: set(PROGRAMS._programs[b]) - set(programs[b])
            for b in PROGRAMS.BUCKETS} == {b: set()
                                           for b in PROGRAMS.BUCKETS}
    assert grew(f'{P}_count{{phase="jit_trace"}}') == 0
    assert grew(f'{P}_count{{phase="scan_fill"}}') == 0
    assert jax_counters.snapshot()["compile_requests"] \
        == before_jax["compile_requests"]
    derived = grew(f'{P}_count{{phase="scan_derive"}}')
    assert derived == {"q1": 0, "q3": 2, "q6": 1}[cls]
    assert sum(v - before.get(k, 0.0) for k, v in after.items()
               if k.startswith("trino_tpu_program_literal_args_total")) > 0


@pytest.mark.parametrize("cls,p", SPREAD + DRAWN)
def test_served_answer_of_a_set_equals_the_reference(
        engine, templates, reference, config, cls, p):
    check(serve(engine, templates, cls, p), reference, config, cls, p)


def test_the_spread_covers_the_domain():
    q6 = [p for c, p in SPREAD if c == "q6"]
    assert {p[1] for p in q6} == {f"0.0{c}" for c in range(2, 10)}
    assert {p[2] for p in q6} == {24, 25}
    assert {p[0][:4] for p in q6} == {str(y) for y in range(1993, 1998)}
    assert {p[0] for c, p in SPREAD if c == "q3"} == set(SEGMENTS)
    assert {p for c, p in SPREAD if c == "q1"} == {(60,), (90,), (120,)}
    for cls in CLASSES:
        assert len(set(drawn(cls))) >= 6


def _chain_key(sql: str):
    """The canonical key of the Filter/Project/Limit chain over the
    scan of ``sql``'s plan, and its literal key."""
    from trino_tpu.plan.nodes import (FilterNode, LimitNode, OutputNode,
                                      ProjectNode, TableScanNode)
    from trino_tpu.planner import LogicalPlanner
    from trino_tpu.planner.optimizer import optimize
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.sql.parser import parse_statement
    r = LocalQueryRunner()
    plan = optimize(LogicalPlanner(r.catalogs, r.session).plan(
        parse_statement(sql)))
    assert isinstance(plan, OutputNode)
    chain, cur = [], plan.source
    while not isinstance(cur, TableScanNode):
        assert isinstance(cur, (FilterNode, ProjectNode, LimitNode))
        chain.append(cur)
        cur = cur.source
    canon = canonicalize_nodes(chain)
    return canon.key, canon.literal_key


def test_texts_that_differ_in_literals_share_one_program_key():
    text = ("select l_orderkey, l_extendedprice * (1 - l_discount) "
            "from lineitem where l_quantity < {q} and l_shipdate >= "
            "date '{d}' + interval '1' year and l_returnflag = '{f}' "
            "limit {n}")
    a = _chain_key(text.format(q=5, d="1994-01-01", f="R", n=10))
    b = _chain_key(text.format(q=7, d="1996-03-01", f="AB", n=10))
    c = _chain_key(text.format(q=5, d="1994-01-01", f="R", n=20))
    assert a[0] == b[0] and a[1] != b[1]
    assert a[0] != c[0]


def test_execute_using_two_argument_sets_compiles_once(engine):
    client = engine.client("prepared")
    client.execute("prepare p from select count(*), sum(l_extendedprice) "
                   "from lineitem where l_quantity between ? and ? "
                   "and l_returnflag = ?")
    sets = ((5, 10, "R"), (20, 30, "A"))
    want = {s: client.execute(
        "select count(*), sum(l_extendedprice) from lineitem where "
        f"l_quantity between {s[0]} and {s[1]} and l_returnflag = "
        f"'{s[2]}'").rows for s in sets}
    got = {}
    jax_counters = bench_module("harness.counters").JaxCounters()
    for i, s in enumerate(sets):
        before = jax_counters.snapshot()["compile_requests"]
        n = sum(len(v) for v in PROGRAMS._programs.values())
        res = client.execute(f"execute p using {s[0]}, {s[1]}, '{s[2]}'")
        assert res.state == "FINISHED", res.error
        got[s] = res.rows
        if i:
            assert jax_counters.snapshot()["compile_requests"] == before
            assert sum(len(v) for v in PROGRAMS._programs.values()) == n
    assert got == want and want[sets[0]] != want[sets[1]]


@pytest.mark.parametrize("text", [
    "date '1998-12-01' - interval '90' day",
    "date '1994-01-31' + interval '1' month",
    "date '1996-02-29' + interval '1' year",
    "0.06 - 0.01", "0.06 + 0.01", "cast(0.06 - 0.01 as double)",
    "cast(0.06 + 0.01 as double)", "1.5 - 0.25", "24 + 1", "7 * 6",
    "-(3.25)"])
def test_the_host_folds_a_subtree_as_the_program_evaluates_it(text):
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.exec.expr import eval_expr
    from trino_tpu.exec.literals import host_fold
    from trino_tpu.planner import LogicalPlanner
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.sql.parser import parse_statement
    from trino_tpu.types import BOOLEAN
    r = LocalQueryRunner()
    plan = LogicalPlanner(r.catalogs, r.session).plan(
        parse_statement(f"select {text} as v"))
    node = plan
    while not hasattr(node, "assignments") or "v" not in str(
            node.assignments):
        node = node.source
    (expr,) = [e for s, e in node.assignments.items() if s.startswith("v")]
    want = eval_expr(expr, Batch({"": Column(
        BOOLEAN, np.zeros((1,), bool))}, 1))
    got = host_fold(expr)
    assert got is not None, expr
    assert np.asarray(want.data)[0] == got
    assert np.asarray(want.data).dtype == np.asarray(got).dtype


def test_a_warm_up_that_repeats_the_pushed_literals_compiles_nothing_later(
        tmp_path, templates, reference, config, monkeypatch):
    """q6's warm-up draw repeats the validation set's pushed (year,
    QUANTITY) and hits its filled copy; q3's then meets a second DATE,
    so the scan cache starts deriving, and derives q6's shape too: a
    window's new (year, QUANTITY) compiles nothing and fills nothing.
    A connector of its own, and no program compiled before."""
    import jax
    monkeypatch.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "1")
    monkeypatch.setenv("TRINO_TPU_DEVICE_GEN", "1")
    PROGRAMS.clear()
    jax.clear_caches()
    eng = bench_module("harness.engine").Engine(
        "tpch", "tiny", str(tmp_path / "state"))

    def derives():
        return eng.counters().get(f'{P}_count{{phase="scan_derive"}}', 0.0)
    start = derives()
    try:
        for cls, p in (("q6", params.validation("q6")),
                       ("q3", params.validation("q3")),
                       ("q6", ("1994-01-01", "0.03", 24))):
            serve(eng, templates, cls, p)
        assert derives() == start
        serve(eng, templates, "q3", ("BUILDING", "1995-03-20"))
        # q3's lineitem meets a second DATE: q6's lineitem and q3's
        # orders derive at their first sets, q3's two at the new DATE
        assert derives() == start + 4
        jax_counters = bench_module("harness.counters").JaxCounters()
        before_jax = jax_counters.snapshot()["compile_requests"]
        before = eng.counters()
        p = ("1997-01-01", "0.05", 25)
        res = serve(eng, templates, "q6", p)
        after = eng.counters()
        check(res, reference, config, "q6", p)
        assert jax_counters.snapshot()["compile_requests"] == before_jax
        for phase, n in (("jit_trace", 0), ("scan_fill", 0),
                         ("scan_derive", 1)):
            key = f'{P}_count{{phase="{phase}"}}'
            assert after.get(key, 0.0) - before.get(key, 0.0) == n, phase
    finally:
        eng.stop()
