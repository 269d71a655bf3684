"""The readers of the engine's phase counters, on planted
``engine_before`` / ``engine_after`` dicts: a value, ``None`` where the
program exports no such family (one older than the spans), and the
division by the queries the window executed."""

import importlib

import pytest

import run as bench_run

P = "trino_tpu_query_phase_seconds"
NEW = ("submit_ms", "result_ms", "finish_ms", "device_wait_ms",
       "execute_host_ms", "host_syncs_per_query", "programs_per_query",
       "setup_scan_fill_s")


def reader(name):
    return importlib.import_module(f"layer_metrics.{name}")


def phase(name, count, seconds):
    return {f'{P}_count{{phase="{name}"}}': float(count),
            f'{P}_sum{{phase="{name}"}}': float(seconds)}


def planted():
    """A window of 10 executed queries over a set-up of 4."""
    run = bench_run.Run()
    before, after = {}, {}
    for name, per_query_s in (
            ("submit", 0.0002), ("queued", 0.0003), ("execute", 0.030),
            ("fetch", 0.0010), ("respond", 0.0005), ("finish", 0.0020),
            ("device_execute", 0.020), ("host_read", 0.004)):
        before.update(phase(name, 4, 4 * per_query_s * 3))  # set-up: slower
        after.update(phase(name, 14, 4 * per_query_s * 3 + 10 * per_query_s))
    # three programs and two reads a query; the set-up filled and traced
    before.update(phase("scan_fill", 2, 7.5))
    after.update(phase("scan_fill", 2, 7.5))
    before.update(phase("jit_trace", 5, 40.0))
    after.update(phase("jit_trace", 5, 40.0))
    for key, b, a in (
            ('trino_tpu_device_programs_total{kind="stream_full"}', 4, 14),
            ('trino_tpu_device_programs_total{kind="join_count"}', 8, 28),
            ('trino_tpu_host_reads_total{site="node_fence"}', 4, 14),
            ('trino_tpu_host_reads_total{site="join_total"}', 4, 14)):
        before[key], after[key] = float(b), float(a)
    for d in (before, after):
        d["trino_tpu_scan_fill_seconds_sum"] = 7.5
        d["trino_tpu_scan_fill_seconds_count"] = 2.0
    run.engine_before, run.engine_after = before, after
    return run


def test_each_reader_reads_its_phases_per_executed_query():
    run = planted()
    want = {"submit_ms": 0.5, "result_ms": 1.5, "finish_ms": 2.0,
            "device_wait_ms": 24.0, "execute_host_ms": 6.0,
            # 2 reads + the device_execute spans (count grew by 10)
            "host_syncs_per_query": 3.0, "programs_per_query": 3.0,
            "setup_scan_fill_s": 7.5}
    assert set(want) == set(NEW)
    for name, value in want.items():
        assert reader(name).read(run) == pytest.approx(value), name


def test_a_phase_that_closed_no_span_counts_zero():
    run = planted()         # no ``persist`` sample anywhere: no spool
    assert reader("result_ms").read(run) == pytest.approx(1.5)
    # a window with a fill: execute_host subtracts it
    run.engine_after[f'{P}_sum{{phase="scan_fill"}}'] += 0.010
    assert reader("execute_host_ms").read(run) == pytest.approx(5.0)


@pytest.mark.parametrize("name", NEW)
def test_none_where_the_program_has_no_such_family(name):
    run = bench_run.Run()   # the parent: /metrics without the families
    run.engine_before = {'trino_tpu_scan_cache_total{cache="table",'
                         'result="hit"}': 3.0}
    run.engine_after = {'trino_tpu_scan_cache_total{cache="table",'
                        'result="hit"}': 9.0}
    assert reader(name).read(run) is None
    run.engine_before = run.engine_after = {}   # the control engine
    assert reader(name).read(run) is None


def test_none_where_the_window_executed_nothing():
    run = planted()
    run.engine_after = dict(run.engine_before)
    for name in NEW:
        if name != "setup_scan_fill_s":
            assert reader(name).read(run) is None, name


def test_the_entries_are_in_benchmark_json_for_every_cell():
    import json
    import os
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert "workloads" not in by_name[name]
    for cell in bench["workloads"]:
        mine = {m["name"] for m in
                bench_run.metrics_of(bench, cell["name"], "per_layer")}
        assert set(NEW) <= mine
