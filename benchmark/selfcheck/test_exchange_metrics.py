"""The five readers of the exchange layer on a small synthetic run: a
trace with two device planes and collective operations, and planted
``/metrics`` samples with and without the ``exchange`` families (a
program older than the mesh cell has none -> ``None``, no raise)."""

import pytest

import run as bench_run
from layer_metrics import (collective_ms, exchange_ici_roofline,
                           exchange_mb_per_query, exchange_ms,
                           shard_skew_pct)

PHASE = "trino_tpu_query_phase_seconds"
BYTES = "trino_tpu_mesh_exchange_bytes_total"
US = 1_000      # trace times are ns
# an event's name is the instruction's text; jax's all_to_all keeps its
# underscores in the instruction's NAME (v5e compiler, PR 28)
A2A = ("%all_to_all.21 = u32[4,1,8]{2,1,0:T(1,128)S(1)} all-to-all("
       "%bitcast.53), channel_id=1, replica_groups={{0,1,2,3}}")
# a fusion that only READS a collective's result is not one
NOT_ONE = ("%bitcast_convert_fusion = (u32[4,8]{1,0}) fusion("
           "%all-gather.2), kind=kLoop, calls=%fused_computation.4")


def counters(executed, exchange_s=None, moved=None):
    out = {f'{PHASE}_count{{phase="execute"}}': float(executed),
           f'{PHASE}_sum{{phase="execute"}}': 1.0 * executed}
    if exchange_s is not None:
        out[f'{PHASE}_count{{phase="exchange"}}'] = 3.0 * executed
        out[f'{PHASE}_sum{{phase="exchange"}}'] = exchange_s
    for kind, b in (moved or {}).items():
        out[f'{BYTES}{{kind="{kind}"}}'] = float(b)
    return out


def planted(with_exchange=True, planes=2):
    """Two queries in a 1,000 us window. Plane 0 is busy 600 us, plane 1
    400 us; each holds two all-to-all of 50 us and an all-gather-start
    that overlaps one of them by half (the union counts it once)."""
    run = bench_run.Run()
    run.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    run.engine_before = counters(10, 1.0 if with_exchange else None,
                                 {"repartition": 1e9, "gather": 1e6}
                                 if with_exchange else None)
    run.engine_after = counters(12, 1.5 if with_exchange else None,
                                {"repartition": 1e9 + 8e6,
                                 "broadcast": 1e6, "gather": 1e6 + 1e6}
                                if with_exchange else None)
    ops0 = [("%fusion.1 = f64[8]", 0, 450 * US),
            (A2A, 450 * US, 50 * US),
            ("%all_to_all.23", 500 * US, 50 * US),
            ("%all-gather-start.1", 525 * US, 50 * US),
            (NOT_ONE, 575 * US, 25 * US)]
    ops1 = [("%fusion.1 = f64[8]", 0, 275 * US),
            (A2A, 450 * US, 50 * US),
            ("%all-to-all.4", 500 * US, 50 * US),
            ("%all-gather-start.1", 525 * US, 50 * US)]
    devices = {"/device:TPU:0": ops0, "/device:TPU:1": ops1}
    run.trace = {"devices": dict(list(devices.items())[:planes]),
                 "host": [("bench_query:q3:0", 0, 500 * US),
                          ("bench_query:q6:0", 500 * US, 500 * US)],
                 "lines": {}}
    run.trace_window = (0, 1000 * US)
    return run


def test_exchange_phase_and_bytes_per_executed_query():
    run = planted()
    assert exchange_ms.read(run) == pytest.approx(250.0)     # 0.5 s / 2
    # 8e6 + 1e6 + 1e6 live bytes over two executed queries
    assert exchange_mb_per_query.read(run) == pytest.approx(5.0)


def test_collectives_are_the_union_on_each_plane_per_traced_query():
    run = planted()
    # 450..575 us busy in collectives on both planes, two queries
    assert collective_ms.read(run) == pytest.approx(0.125 / 2)


def test_skew_is_the_busiest_plane_over_the_mean():
    run = planted()
    assert shard_skew_pct.read(run) == pytest.approx(
        100.0 * (600 / 500 - 1))
    assert shard_skew_pct.read(planted(planes=1)) is None


def test_ici_roofline_sets_repartition_bytes_against_all_to_all_time():
    run = planted()
    # the repartition kind alone (8e6 over two queries), against the
    # two all-to-all of 50 us on each plane: the all-gather that
    # overlaps them, like the broadcast's and the gather's bytes, is
    # on neither side
    least_s = 4e6 * 3 / 4 / 4 / (1600 / 8 * 1e9)
    assert exchange_ici_roofline.read(run) == pytest.approx(
        100.0 * least_s / 50e-6)
    assert 0 < exchange_ici_roofline.read(run) <= 100
    no_a2a = planted()
    for plane, ops in no_a2a.trace["devices"].items():
        no_a2a.trace["devices"][plane] = [
            e for e in ops if "all-to-all" not in e[0]
            and "all_to_all" not in e[0]]
    assert exchange_ici_roofline.read(no_a2a) is None
    run.device["kind"] = "no such chip"
    assert exchange_ici_roofline.read(run) is None


def test_none_without_the_families_or_without_a_trace():
    old = planted(with_exchange=False)       # the parent's counters
    assert exchange_ms.read(old) is None
    assert exchange_mb_per_query.read(old) is None
    assert exchange_ici_roofline.read(old) is None
    untraced = planted()
    untraced.trace = untraced.trace_window = None
    assert collective_ms.read(untraced) is None
    assert shard_skew_pct.read(untraced) is None
    assert exchange_ici_roofline.read(untraced) is None
    assert exchange_ms.read(untraced) == pytest.approx(250.0)


def test_declared_for_the_mesh_cell_alone():
    bench, cell, config = bench_run.load_cell("tpch_sf10_mesh4.power")
    assert cell["chips"] == 4 and config["queries"] == ["q1", "q3", "q6"]
    names = ("exchange_ms", "exchange_mb_per_query", "collective_ms",
             "shard_skew_pct", "exchange_ici_roofline")
    for m in bench["per_layer"]:
        if m["name"] in names:
            assert m["workloads"] == ["tpch_sf10_mesh4.power"]
            assert m["moves"] == "queries_per_s" and m["layer"] == "exchange"
    mine = [m["name"] for m in bench_run.metrics_of(
        bench, "tpch_sf10_mesh4.power", "per_layer")]
    assert set(names) <= set(mine)
    # the one-chip rooflines divide by ONE chip's HBM peak: not here
    assert "q1_hbm_roofline" not in mine and "q6_hbm_roofline" not in mine
    end = [m["name"] for m in bench_run.metrics_of(
        bench, "tpch_sf10_mesh4.power", "end_to_end")]
    assert end == ["queries_per_s", "q6_p50_ms", "setup_s"]
    for other in ("tpch_sf1.power", "tpch_sf10.power"):
        theirs = [m["name"] for m in bench_run.metrics_of(
            bench, other, "per_layer")]
        assert not set(names) & set(theirs)
