"""A join puts out only the lanes the plan above it reads.

Planner side: ``prune_columns`` gives every ``JoinNode`` its ``outputs``,
exactly the symbols of its two sides that a node above it reads (one
lane where nothing above reads any: a ``count(*)``), for TPC-DS q3, q7,
q96 and TPC-H q3, q18 at ``tiny``; the sanity checker holds them to the
sides' schemas.

Executor side: for inner, left, right, full, cross and residual-filter
joins the rows are a nested loop's over the same rows and those of the same join
with ``outputs=None`` (every lane), eager and jitted; the expand is
handed the join's outputs alone, and the residual's inputs where there
is one. A served q96 at ``tiny`` dispatches three expands that keep 2
of 5, 1 of 4 and 1 of 4 lanes, on their spans and in
``trino_tpu_join_expand_lanes_total``.
"""

import pytest

from trino_tpu.benchmarks.tpcds_queries import TPCDS_QUERIES
from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
from trino_tpu.columnar import batch_from_pylist
from trino_tpu.obs.metrics import METRICS
from trino_tpu.plan.nodes import (AggregationNode, FilterNode, JoinClause,
                                  JoinNode, OutputNode, ProjectNode,
                                  SemiJoinNode, SortNode, TopNNode)
from trino_tpu.rex import Call, InputRef, input_names
from trino_tpu.types import BIGINT, BOOLEAN, DOUBLE, VARCHAR

QUERIES = [("tpcds", TPCDS_QUERIES, n) for n in (3, 7, 96)] + \
    [("tpch", TPCH_QUERIES, n) for n in (3, 18)]


def plan_of(catalog: str, sql: str):
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.session import Session
    runner = LocalQueryRunner(session=Session(catalog=catalog,
                                              schema="tiny"))
    return runner.plan_sql(sql)


def refs(node) -> set:
    """The symbols ``node`` itself reads (not what it passes through)."""
    out = set()
    if isinstance(node, FilterNode):
        out |= input_names(node.predicate)
    elif isinstance(node, ProjectNode):
        for e in node.assignments.values():
            out |= input_names(e)
    elif isinstance(node, AggregationNode):
        out |= set(node.group_keys)
        for a in node.aggregates.values():
            out |= {s for s in (a.argument, a.argument2, a.mask) if s}
    elif isinstance(node, JoinNode):
        for c in node.criteria:
            out |= {c.left, c.right}
        if node.filter is not None:
            out |= input_names(node.filter)
    elif isinstance(node, SemiJoinNode):
        out |= {node.source_key}
    elif isinstance(node, (SortNode, TopNNode)):
        out |= {k.symbol for k in node.keys}
    elif isinstance(node, OutputNode):
        out |= set(node.symbols)
    return out


def joins_with_reads(node, above=frozenset()):
    """(join, the symbols the nodes above it read), top down. Symbols
    are unique in a plan, so a symbol of the join's sides that a node
    above reads is read from this join."""
    if isinstance(node, JoinNode):
        yield node, above
    below = above | refs(node)
    for s in node.sources:
        yield from joins_with_reads(s, below)


@pytest.mark.parametrize("catalog,texts,number", QUERIES,
                         ids=[f"{c}_q{n}" for c, _t, n in QUERIES])
def test_a_join_puts_out_what_the_plan_above_reads(catalog, texts, number):
    from trino_tpu.analysis.sanity import PlanSanityChecker
    plan = plan_of(catalog, texts[number])
    PlanSanityChecker().validate(plan, "prune_columns")
    joins = list(joins_with_reads(plan))
    assert joins
    unread = 0
    for join, read in joins:
        offered = list(join.left.output_schema()) + \
            list(join.right.output_schema())
        want = [s for s in offered if s in read]
        if not want:                    # a count(*) above: one lane
            unread += 1
            assert list(join.outputs) == offered[:1]
            continue
        assert list(join.outputs) == want
        assert list(join.output_schema()) == want
        # keys and filter inputs leave with the join unless read above
        for c in join.criteria:
            assert (c.left in join.outputs) == (c.left in read)
    assert unread == (1 if (catalog, number) == ("tpcds", 96) else 0)


def test_q96_s_first_join_keeps_the_next_two_joins_keys():
    plan = plan_of("tpcds", TPCDS_QUERIES[96])
    first = [j for j, _r in joins_with_reads(plan)][-1]   # the deepest
    assert [s.split("$")[0] for s in first.outputs] == [
        "ss_sold_time_sk", "ss_hdemo_sk"]
    offered = list(first.left.output_schema()) + \
        list(first.right.output_schema())
    assert len(offered) == 5


# ---- the executor ----------------------------------------------------------
LEFT = {"k": [1, 2, 2, 3, None, 5, 7],
        "a": [0.5, 1.5, 2.5, 3.5, 4.5, 5.5, 6.5],
        "b": ["p", "q", "r", "s", "t", "u", "v"],
        "x": [10, 20, 30, 40, 50, 60, 70]}
RIGHT = {"k2": [2, 3, 3, 4, None, 7],
         "c": [1.0, 3.0, 4.0, 5.0, 6.0, 7.0],
         "d": ["A", "B", "C", "D", "E", "F"]}
LTYPES = {"k": BIGINT, "a": DOUBLE, "b": VARCHAR, "x": BIGINT}
RTYPES = {"k2": BIGINT, "c": DOUBLE, "d": VARCHAR}
A_LT_C = Call("<", (InputRef("a", DOUBLE), InputRef("c", DOUBLE)), BOOLEAN)

# (id, join type, equi-join?, residual, outputs)
CASES = [
    ("inner", "inner", True, None, ("a", "d")),
    ("inner_probe_only", "inner", True, None, ("a",)),
    ("left", "left", True, None, ("a", "c")),
    ("left_build_only", "left", True, None, ("c",)),
    ("right", "right", True, None, ("b", "c")),
    ("full", "full", True, None, ("a", "d")),
    ("cross", "cross", False, None, ("x", "c")),
    ("inner_residual", "inner", True, A_LT_C, ("b", "d")),
    ("left_residual", "left", True, A_LT_C, ("x", "d")),
    ("full_residual", "full", True, A_LT_C, ("b", "c")),
]


def reference(jt, equi, residual, outputs):
    """The join by a nested loop over the Python rows, projected."""
    lrows = [dict(zip(LEFT, r)) for r in zip(*LEFT.values())]
    rrows = [dict(zip(RIGHT, r)) for r in zip(*RIGHT.values())]
    nulls_l = dict.fromkeys(LEFT)
    nulls_r = dict.fromkeys(RIGHT)

    def match(lr, rr):
        if equi and (lr["k"] is None or lr["k"] != rr["k2"]):
            return False
        return residual is None or lr["a"] < rr["c"]

    out, hit_r = [], set()
    for lr in lrows:
        found = False
        for i, rr in enumerate(rrows):
            if match(lr, rr):
                out.append({**lr, **rr})
                found = True
                hit_r.add(i)
        if not found and jt in ("left", "full"):
            out.append({**lr, **nulls_r})
    if jt in ("right", "full"):
        out += [{**nulls_l, **rr} for i, rr in enumerate(rrows)
                if i not in hit_r]
    return sorted_rows([tuple(r[s] for s in outputs) for r in out])


def sorted_rows(rows):
    return sorted(rows, key=lambda r: tuple((v is None, v) for v in r))


@pytest.fixture
def expand_spy(monkeypatch):
    """The lanes of the two inputs each ``expand_join`` is handed."""
    from trino_tpu.exec.progkey import PROGRAMS
    from trino_tpu.ops import join as join_ops
    seen = []
    real = join_ops.expand_join

    def spy(probe, build, *args, **kw):
        seen.append(set(probe.columns) | set(build.columns))
        return real(probe, build, *args, **kw)

    monkeypatch.setattr(join_ops, "expand_join", spy)
    PROGRAMS.clear("join")          # a jitted expand traces anew
    yield seen
    PROGRAMS.clear("join")


def run_join(jt, equi, residual, outputs, jit, order):
    """The join's rows, each a tuple of the lanes ``order`` names."""
    from trino_tpu.catalog import CatalogManager
    from trino_tpu.exec.executor import Executor, _Pre
    from trino_tpu.session import Session
    node = JoinNode(
        _Pre(batch_from_pylist(LEFT, LTYPES)),
        _Pre(batch_from_pylist(RIGHT, RTYPES)), jt,
        (JoinClause("k", "k2"),) if equi else (), residual,
        outputs=outputs)
    out = Executor(CatalogManager(), Session(),
                   fragment_jit=jit).execute(node)
    if outputs is not None:
        assert set(out.columns) == set(outputs)
    idx = [list(out.columns).index(s) for s in order]
    return sorted_rows([tuple(r[i] for i in idx)
                        for r in out.to_pylist()])


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jitted"])
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_the_expand_gathers_the_join_s_outputs_alone(expand_spy, case,
                                                     jit):
    _name, jt, equi, residual, outputs = case
    want = reference(jt, equi, residual, outputs)
    got = run_join(jt, equi, residual, outputs, jit, outputs)
    assert got == want
    assert expand_spy, "no expand ran"
    handed = set(outputs) | (input_names(residual) if residual else set())
    for lanes in expand_spy:
        assert lanes - {"__probe_pos$", "__build_pos$"} == handed
    # the parent's rule: every lane gathered, the same rows
    expand_spy.clear()
    assert run_join(jt, equi, residual, None, jit, outputs) == want
    assert expand_spy[0] - {"__probe_pos$", "__build_pos$"} == \
        set(LEFT) | set(RIGHT)


def test_a_served_q96_keeps_two_of_five_lanes_then_one_of_four(
        monkeypatch):
    from trino_tpu.obs.metrics import observe_span
    from trino_tpu.obs.trace import QueryTrace
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.session import Session
    monkeypatch.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    session = Session(catalog="tpcds", schema="tiny")
    runner = LocalQueryRunner(session=session)
    want = runner.execute(TPCDS_QUERIES[96]).rows

    def lanes_total():
        return {k[1]: v for k, v in METRICS.counter(
            "trino_tpu_join_expand_lanes_total").samples()
            if k[0] == "join_expand"}

    before = lanes_total()
    session.trace = QueryTrace("q96", on_close=observe_span)
    try:
        assert runner.execute(TPCDS_QUERIES[96]).rows == want
        spans = session.trace.all_spans()
    finally:
        session.trace = None
    expands = [s.attrs["lanes"] for s in spans
               if s.name in ("dispatch", "jit_trace")
               and str(s.attrs.get("program")).startswith("join_expand:")]
    assert expands == ["2/5", "1/4", "1/4"]
    grew = {k: v - before.get(k, 0.0) for k, v in lanes_total().items()}
    assert grew == {"yes": 4.0, "no": 9.0}
    assert len(want) == 1
