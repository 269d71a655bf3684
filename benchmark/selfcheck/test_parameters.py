"""A configuration with substitution parameters (``"parameters":
"tpch_params"``): the templates, the draws, the class order they leave
alone, and the output check per query, shown to pass when sound and to
fail when the program ignores its parameters or the control answers.

The rehearsals skip the harness's look for a chip (``--rehearse``: the
CPU, ``tpch.tiny``) and drive the rest of a run through ``run.main``
with ``tpch_sf1_1chip`` plus the key.
"""

import collections
import json
import os
import random
import re

import pytest

import run as bench_run
from harness import traffic
from reference import tpch_answers, tpch_params

HERE = os.path.dirname(os.path.abspath(__file__))
CLASSES = ("q1", "q3", "q6")
SEED = "2147483659"
with open(os.path.join(HERE, "before_parameters.json")) as _f:
    BEFORE = json.load(_f)

# the clauses' domains, spelled out apart from the module
DOMAIN = {
    "q1": {(d,) for d in range(60, 121)},
    "q3": {(s, f"1995-03-{d:02d}") for s in
           ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
           for d in range(1, 32)},
    "q6": {(f"{y}-01-01", d, q) for y in range(1993, 1998)
           for d in ("0.02", "0.03", "0.04", "0.05", "0.06", "0.07", "0.08",
                     "0.09") for q in (24, 25)},
}


def template(cls):
    return traffic.load_sql(cls, {"queries_dir": tpch_params.TEMPLATES_DIR})


@pytest.mark.parametrize("cls", CLASSES)
def test_template_at_validation_parameters_is_the_flat_text(cls):
    flat = traffic.load_sql(cls, {})
    assert tpch_params.substitute(template(cls),
                                  tpch_params.validation(cls)) == flat
    assert template(cls) != flat


def test_substitute_uses_every_parameter_and_leaves_no_placeholder():
    assert tpch_params.substitute("a :2 :1 :2", ("x", 7)) == "a 7 x 7"
    with pytest.raises(ValueError):
        tpch_params.substitute("a :1", ("x", 7))
    with pytest.raises(ValueError):
        tpch_params.substitute("a :1 :3", ("x", 7))


@pytest.mark.parametrize("cls", CLASSES)
def test_draws_stay_inside_the_clause_and_cover_it(cls):
    rng = random.Random(f"{SEED}/params/0")
    draws = [tpch_params.draw(cls, rng) for _ in range(10_000)]
    assert set(draws) == DOMAIN[cls] == set(tpch_params.domain(cls))
    assert len(tpch_params.domain(cls)) == len(DOMAIN[cls])
    assert tpch_params.validation(cls) in DOMAIN[cls]
    counts = collections.Counter(draws)
    # uniform: no set drawn three times as often as another
    assert max(counts.values()) < 3 * min(counts.values())
    again = random.Random(f"{SEED}/params/0")
    assert [tpch_params.draw(cls, again) for _ in range(10_000)] == draws


class _Stub:
    """Draws a number for any class: it consumes its rng as a real
    module does."""

    @staticmethod
    def draw(cls, rng):
        return (cls, rng.random())


@pytest.mark.parametrize("key", sorted(BEFORE["class_order"]))
def test_class_order_is_as_before_with_and_without_parameters(key):
    cell, seed, tag = key.split("|")
    _b, entry, config = bench_run.load_cell(cell)
    mix = traffic.load_mix(entry["traffic"])
    classes = traffic.classes_of(mix, config)
    for parameters in (None, _Stub):
        got = collections.defaultdict(list)

        def execute(stream, cls, params):
            got[str(stream)].append(cls)
            assert (params is None) == (parameters is None)

            class Res:
                state, query_id, rows = "FINISHED", "", []
            return Res()
        traffic.run_closed(mix, classes, int(seed), 0, execute, cycles=4,
                           stream_tag=tag, parameters=parameters)
        assert dict(got) == BEFORE["class_order"][key], parameters


def test_no_q3_answer_at_tiny_ties_across_its_limit():
    """Row by row is right for q3 only where no two rows ranked up to one
    past the LIMIT share both sort keys: at tiny none does, for any of
    the 155 parameter sets."""
    a = tpch_answers.Answers(0.01, [("q3", p)
                                    for p in tpch_params.domain("q3")])
    ties = {p: a.q3_ties(p) for p in tpch_params.domain("q3")}
    assert len(ties) == 155 and not any(ties.values())
    assert all(len(a.answer("q3", p)) == 10 for p in ties)


# ---- rehearsals of a parameterised configuration --------------------------
@pytest.fixture
def parameterised(monkeypatch):
    load_cell = bench_run.load_cell

    def with_parameters(name):
        bench, cell, config = load_cell(name)
        return bench, cell, dict(config, parameters="tpch_params")
    monkeypatch.setattr(bench_run, "load_cell", with_parameters)


def drive(capsys, *extra):
    rc = bench_run.main(["--workload", "tpch_sf1.power", "--seed", SEED,
                         "--seconds", "1", "--trace", "0", "--rehearse",
                         *extra])
    assert rc == 3
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is False and result["rehearsal"] is True
    assert list(result)[-1] == "checks"
    window = json.loads(next(line for line in out.err.splitlines()
                             if line.startswith("window "))[len("window "):])
    return result, window


def test_parameterised_rehearsal_is_sound(parameterised, capsys):
    r, window = drive(capsys)
    assert r["rehearsal_checks_pass"] is True
    assert r["failed"] == 0 and r["attempted"] >= 3
    assert r["checks"]["max_rel_err"]["value"] < 1e-11
    assert set(window["class_param_sets"]) == set(CLASSES)
    assert all(n >= 1 for n in window["class_param_sets"].values())


def _pattern(cls):
    return re.compile(re.sub(r":\d+", "(.+?)", re.escape(template(cls))))


def test_a_program_that_ignores_its_parameters_is_not_correct(
        parameterised, capsys, monkeypatch):
    """The client sends the validation text in place of the drawn one."""
    from trino_tpu.client import StatementClient
    execute, sent = StatementClient.execute, collections.Counter()
    patterns = {c: _pattern(c) for c in CLASSES}
    flat = {c: traffic.load_sql(c, {}) for c in CLASSES}

    def ignoring(self, sql):
        cls = next((c for c in CLASSES if patterns[c].fullmatch(sql)), None)
        if cls is not None and sql != flat[cls]:
            sent[cls] += 1
            sql = flat[cls]
        return execute(self, sql)
    monkeypatch.setattr(StatementClient, "execute", ignoring)
    r, _window = drive(capsys)
    assert sum(sent.values()) >= 3
    assert r["rehearsal_checks_pass"] is False
    assert r["failed"] > 0
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_parameterised_control_float32_is_not_correct(parameterised, capsys):
    r, window = drive(capsys, "--control", "float32")
    assert r["rehearsal_checks_pass"] is False
    c = r["checks"]["max_rel_err"]
    assert c["value"] > 3 * c["limit"]
    assert sum(window["class_param_sets"].values()) > 3
