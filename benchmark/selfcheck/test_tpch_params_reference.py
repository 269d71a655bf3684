"""The generalised reference (``reference/tpch_answers.py`` over any
substitution parameters) against the program's SERVED answers at
``tpch.tiny`` on the CPU, and against its own rows as they were before
it took parameters.

- 8 seeded draws per class, substituted into the templates and sent
  through a coordinator as ``harness/engine.py`` starts one (fragments
  jitted, tables resident as on the chip), equal the reference for
  those parameters under ``tpch_sf1_1chip``'s limits;
- at the validation parameters, asked by name or by parameters, both
  TPC-H references give the rows recorded before, exactly.
"""

import json
import os
import random

import numpy as np
import pytest

from harness import traffic
from reference import compare, tpch_answers, tpch_params
from reference import tpch_q3_q18_answers

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = 0.01
CLASSES = ("q1", "q3", "q6")
DRAWS = 8
with open(os.path.join(HERE, "before_parameters.json")) as _f:
    BEFORE = json.load(_f)["answers_tiny"]


def drawn(cls):
    rng = random.Random(f"2718281829/params/{cls}")
    return [tpch_params.draw(cls, rng) for _ in range(DRAWS)]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch_sf1_1chip.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reference():
    return tpch_answers.Answers(
        TINY, [(c, p) for c in CLASSES for p in drawn(c)] + list(CLASSES))


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    from harness.engine import Engine
    mp = pytest.MonkeyPatch()
    mp.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    mp.setenv("TRINO_TPU_WHOLE_TABLE", "1")
    mp.setenv("TRINO_TPU_DEVICE_GEN", "1")
    eng = Engine("tpch", "tiny", str(tmp_path_factory.mktemp("state")))
    yield eng
    eng.stop()
    mp.undo()


@pytest.mark.parametrize("cls,i", [(c, i) for c in CLASSES
                                   for i in range(DRAWS)])
def test_served_answer_of_a_draw_equals_the_reference(engine, config,
                                                      reference, cls, i):
    params = drawn(cls)[i]
    template = traffic.load_sql(cls, {"queries_dir":
                                      tpch_params.TEMPLATES_DIR})
    res = engine.client("t").execute(tpch_params.substitute(template, params))
    assert res.state == "FINISHED", res.error
    want = reference.answer(cls, params)
    # a draw asks another answer than the validation parameters do
    assert want and (want != reference.answer(cls)
                     or params == tpch_params.validation(cls))
    mismatches, rel = compare.gaps(res.rows, want)
    assert mismatches <= config["limits"]["exact_mismatches"], params
    assert rel <= config["limits"]["max_rel_err"], params


def test_the_draws_are_many_sets():
    for cls in CLASSES:
        assert len(set(drawn(cls))) >= 6


@pytest.mark.parametrize("cls", CLASSES)
def test_validation_parameters_give_the_rows_of_before(cls):
    want = BEFORE["tpch_answers"][cls]
    a = tpch_answers.Answers(TINY, [cls])
    assert a.answer(cls) == want
    b = tpch_answers.Answers(TINY, [(cls, tpch_params.validation(cls))])
    assert b.answer(cls, tpch_params.validation(cls)) == want


@pytest.mark.parametrize("cls", ("q3", "q18"))
def test_the_q3_q18_reference_gives_the_rows_of_before(cls):
    a = tpch_q3_q18_answers.Answers(TINY, ["q3", "q18"], quantity=200)
    assert a.answer(cls) == BEFORE["tpch_q3_q18_answers quantity=200"][cls]


def test_the_float32_reference_differs_for_drawn_parameters():
    """The control's reference, per pair: one precision down misses the
    limit on some drawn pair of every class with a DOUBLE result."""
    pairs = [(c, p) for c in CLASSES for p in drawn(c)]
    f64 = tpch_answers.Answers(TINY, pairs)
    f32 = tpch_answers.Answers(TINY, pairs, dtype=np.float32)
    for cls in CLASSES:
        worst = max(compare.gaps(f32.answer(cls, p), f64.answer(cls, p))[1]
                    for p in drawn(cls))
        assert worst > 1e-8, cls
