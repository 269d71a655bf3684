"""The reader of the join probe's step counters, on planted
``engine_before`` / ``engine_after`` dicts: steps over probes of the
window alone, ``None`` where the program exports no such family (the
parent) or the window ran no join."""

import pytest

import run as bench_run
from layer_metrics import join_search_steps

PROBES = 'trino_tpu_join_probes_total{site="join_total"}'
STEPS = 'trino_tpu_join_search_steps_total{site="join_total"}'


def planted(before, after):
    run = bench_run.Run()
    run.engine_before = dict(zip((PROBES, STEPS), map(float, before)))
    run.engine_after = dict(zip((PROBES, STEPS), map(float, after)))
    return run


def test_steps_over_probes_of_the_window():
    # set-up ran two q3 (4 probes, one of them a full bisection); the
    # window six more, 3 and 4 steps a pair
    run = planted((4, 31), (16, 31 + 6 * 7))
    assert join_search_steps.read(run) == pytest.approx(3.5)


def test_none_without_the_family_or_without_a_join():
    run = bench_run.Run()       # the parent: no such counters
    run.engine_before = {'trino_tpu_host_reads_total{site="join_total"}': 2.0}
    run.engine_after = {'trino_tpu_host_reads_total{site="join_total"}': 8.0}
    assert join_search_steps.read(run) is None
    assert join_search_steps.read(planted((4, 31), (4, 31))) is None


def test_declared_for_the_cell_that_runs_q3():
    bench, _cell, _config = bench_run.load_cell("tpch_sf1.power")
    spec = next(m for m in bench["per_layer"]
                if m["name"] == "join_search_steps")
    assert spec["workloads"] == ["tpch_sf1.power"]
    assert spec["moves"] == "q3_p50_ms" and spec["better"] == "lower"
    names = [m["name"] for m in bench_run.metrics_of(
        bench, "tpch_sf10.power", "per_layer")]
    assert "join_search_steps" not in names
