"""The comparison that decides ``correct``, shown to fail.

Each case skips the harness's look for a chip (``--rehearse``: the CPU,
``tpch.tiny``) and drives the rest of a run through ``run.main``. A
rehearsal never prints ``"correct": true``; ``rehearsal_checks_pass``
is what ``correct`` would have been. Sound: passes. The control (the
reference in float32, in the program's place) and every fault planted
under the timed path (an answer altered where the client hands it
over; a query that fails) must come out not correct.
"""

import copy
import json

import pytest

import run as bench_run

CELL = "tpch_sf1.power"


def drive(capsys, *extra):
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483659",
                         "--seconds", "1", "--trace", "0", "--rehearse",
                         *extra])
    assert rc == 3
    line = capsys.readouterr().out.strip().splitlines()[-1]
    result = json.loads(line)
    assert result["correct"] is False and result["rehearsal"] is True
    assert list(result)[-1] == "checks"
    return result


def broken(which):
    def alter(res):
        rows = copy.deepcopy(res.rows)
        if which == "sum_off_1e-6" and len(rows) == 1:        # q6
            rows[0][0] *= 1 + 1e-6
        elif which == "key_altered" and len(rows) == 10:      # q3
            rows[0][0] += 1
        elif which == "row_dropped" and len(rows) == 4:       # q1
            rows.pop()
        elif which == "date_altered" and len(rows) == 10:
            rows[3][2] = "1995-01-01"
        res.rows = rows
        return res
    return alter


def test_sound_run_passes(capsys):
    r = drive(capsys)
    assert r["rehearsal_checks_pass"] is True
    assert r["failed"] == 0 and r["attempted"] >= 3
    assert r["checks"]["max_rel_err"]["value"] < 1e-11


def test_control_float32_is_not_correct(capsys):
    r = drive(capsys, "--control", "float32")
    assert r["rehearsal_checks_pass"] is False
    c = r["checks"]["max_rel_err"]
    assert c["value"] > 3 * c["limit"]


@pytest.mark.parametrize("which,check", [
    ("sum_off_1e-6", "max_rel_err"), ("key_altered", "exact_mismatches"),
    ("row_dropped", "exact_mismatches"), ("date_altered", "exact_mismatches")])
def test_altered_answer_is_not_correct(capsys, monkeypatch, which, check):
    from trino_tpu.client import StatementClient
    execute, alter = StatementClient.execute, broken(which)
    monkeypatch.setattr(StatementClient, "execute",
                        lambda self, sql: alter(execute(self, sql)))
    r = drive(capsys)
    assert r["rehearsal_checks_pass"] is False
    assert r["checks"][check]["value"] > r["checks"][check]["limit"]
    assert r["failed"] > 0


def test_failing_query_is_not_correct(capsys, monkeypatch):
    from trino_tpu.client import ClientError, StatementClient
    execute, calls = StatementClient.execute, [0]

    def flaky(self, sql):
        calls[0] += 1
        if calls[0] == 12:
            raise ClientError("planted")
        return execute(self, sql)
    monkeypatch.setattr(StatementClient, "execute", flaky)
    r = drive(capsys)
    assert r["rehearsal_checks_pass"] is False
    assert r["checks"]["failed_queries"]["value"] == 1
