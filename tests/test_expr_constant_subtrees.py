"""A subtree of an expression that reads no column and calls nothing
volatile is evaluated ONCE, at one row, and broadcast
(exec/expr.py ``_eval_constant``). Every case here runs the constant
expression through SQL beside the same expression over a COLUMN that
holds the constant (``case when n_nationkey >= 0 then <value> end``:
an InputRef in the tree, so the per-row path) and wants the same
answers, and counts the subtrees the evaluator took."""

import re

import pytest

from trino_tpu.exec.expr import _constant_subtree
from trino_tpu.obs.metrics import EXPR_CONSTANT_SUBTREES
from trino_tpu.rex import Call, Const
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.types import BIGINT, DOUBLE, VARCHAR

ROWS = 25       # tpch.tiny.nation


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner()


def _subst(expr: str, const: str) -> str:
    """``expr`` with the identifier ``c`` replaced by the constant."""
    return re.sub(r"\bc\b", f"({const})", expr)


def _taken() -> float:
    return sum(v for _, v in EXPR_CONSTANT_SUBTREES.samples())


# (expression over c, the constant c stands for, subtrees taken)
CASES = [
    # date +- interval: years, months (clamped to the month's end), days
    ("c + interval '1' year", "date '1994-01-01'", 1),
    ("c + interval '1' month", "date '2024-01-31'", 1),
    ("c + interval '1' month", "date '2023-01-31'", 1),
    ("c - interval '1' month", "date '2024-03-31'", 1),
    ("c + interval '1' year", "date '2024-02-29'", 1),
    ("c - interval '4' year", "date '2024-02-29'", 1),
    ("c + interval '-13' month", "date '2024-01-15'", 1),
    ("c - interval '-1' year", "date '1999-12-31'", 1),
    ("c - interval '90' day", "date '1998-12-01'", 1),
    ("c + interval '-366' day", "date '1970-01-01'", 1),
    # timestamp +- interval
    ("c + interval '1' month", "timestamp '2024-01-31 13:14:15.678'", 1),
    ("c - interval '1' month", "timestamp '2024-03-31 23:59:59.999'", 1),
    ("c + interval '36' hour", "timestamp '2024-02-28 12:00:00.000'", 1),
    # date parts of a computed date: one subtree, the inner one is
    # evaluated inside it
    ("year(c + interval '11' month) * 100 + month(c + interval '11' month)",
     "date '2023-02-28'", 1),
    ("date_diff('day', c, c + interval '1' year)", "date '2023-03-01'", 1),
    # integer and decimal arithmetic, division and modulus by sign
    ("(c + 5) * 3 - 100 / 7", "cast(17 as bigint)", 1),
    ("-c / 4 + c % 5 - (-c) % 5", "cast(23 as bigint)", 1),
    ("c - 0.01", "0.06", 1),
    ("c * 1.175 + 2", "cast(12.34 as decimal(10,2))", 1),
    ("c / 3", "cast(10.00 as decimal(12,2))", 1),
    ("c * 2e0 + sqrt(c)", "cast(2.25 as double)", 1),
    # nested casts
    ("cast(cast(c - 0.01 as double) as real)", "0.06", 1),
    ("cast(cast(c + 1 as varchar) as bigint) + 1", "cast(41 as bigint)", 1),
    ("cast(cast(c as varchar) as date) + interval '1' day",
     "date '2024-02-28'", 1),
    ("cast(c + interval '1' month as timestamp)", "date '2024-01-31'", 1),
    ("cast(c * 10 as decimal(10,3))", "cast(7 as integer)", 1),
    # CASE over constants; comparisons and boolean logic
    ("case when c + 1 > 2 then c * 10 when c + 1 > 1 then c * 100 "
     "else -1 end", "cast(1 as bigint)", 1),
    ("case when c > 5 then 1.5e0 end", "cast(3 as bigint)", 1),
    ("c + 1 > 2 and not (c + 1 > 3)", "cast(2 as bigint)", 1),
    ("coalesce(nullif(c + 1, 2), c + 40)", "cast(1 as bigint)", 1),
    ("greatest(c + 1, c * 3, 2) between 2 and 9", "cast(3 as bigint)", 1),
    # a NULL operand
    ("c + interval '1' month", "cast(null as date)", 1),
    ("c + 1", "cast(null as bigint)", 1),
    ("(c + 1) is null", "cast(null as bigint)", 1),
    ("coalesce(c + 1, 7)", "cast(null as bigint)", 1),
    ("case when c + 1 > 0 then 1 else 2 end", "cast(null as bigint)", 1),
    # a VARCHAR inside a plain-typed subtree is evaluated at one row too
    ("length(upper(c)) + 1", "'abc'", 1),
    # a VARCHAR-typed constant call keeps the old path
    ("upper(c)", "'abc'", 0),
    ("concat(lower(c), 'x')", "'ABC'", 0),
    # two subtrees under one root that reads a column
    ("n_nationkey + (c + 1) + n_regionkey * (c + 2)",
     "cast(5 as bigint)", 2),
]


@pytest.mark.parametrize("expr,const,taken", CASES)
def test_constant_subtree_equals_the_per_row_path(runner, expr, const,
                                                  taken):
    before = _taken()
    constant = runner.execute(
        f"select n_nationkey, {_subst(expr, const)} from nation "
        "order by 1").rows
    assert _taken() - before == taken
    per_row = runner.execute(
        f"select n_nationkey, {expr} from (select n_nationkey, "
        f"n_regionkey, case when n_nationkey >= 0 then {const} end as c "
        "from nation) order by 1").rows
    assert len(constant) == ROWS
    assert constant == per_row


@pytest.mark.parametrize("sql,least", [
    # 100 suppliers: a random() taken at one row would be one value
    ("select count(distinct random()) from supplier", 2),
    ("select count(distinct random() + 1) from supplier", 2),
    ("select count(distinct random(1000000) * 2) from supplier", 2),
    ("select count(*) from nation "
     "where now() + interval '1' day > timestamp '2020-01-01 00:00:00'",
     ROWS),
    ("select count(*) from nation "
     "where current_date + interval '1' year > date '2020-01-01'", ROWS),
])
def test_volatile_subtrees_are_not_taken(runner, sql, least):
    before = _taken()
    assert runner.execute(sql).rows[0][0] >= least
    assert _taken() == before


def test_the_rule_itself():
    one = Const(1, BIGINT)
    assert _constant_subtree(Call("+", (one, one), BIGINT))
    assert _constant_subtree(Call("pi", (), DOUBLE))
    # no evaluator of uuid() exists yet; the rule must refuse it before
    # one does
    assert not _constant_subtree(Call("uuid", (), VARCHAR))
    assert not _constant_subtree(
        Call("length", (Call("uuid", (), VARCHAR),), BIGINT))
    assert not _constant_subtree(Call("random", (), DOUBLE))
    assert not _constant_subtree(Call("upper", (Const("a", VARCHAR),),
                                      VARCHAR))
