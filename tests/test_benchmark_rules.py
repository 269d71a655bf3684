"""Two rules of ``BENCHMARK.json`` and the configurations it names that
need nothing but the JSON (they stood in ``benchmark/selfcheck/``, which
tier-1 does not run): a metric of ONE query class is given only to
cells that have the class, and a roofline's ``scan_rows`` lie within
the table. A cell that breaks the first ends every traced or untraced
run with exit code 4 (``benchmark/run.py``); one that breaks the second
reads a roofline of nothing or of more than the table holds."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
CONFIGS = [c["name"] for c in BENCHMARK["configs"]]


def bench_module(name: str):
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module(name)
    finally:
        sys.path.remove(BENCH)


def config_of(name: str) -> dict:
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", CELLS)
def test_a_class_s_metric_is_given_only_to_cells_with_the_class(cell):
    run = bench_module("run")
    traffic = bench_module("harness.traffic")
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    config = config_of(entry["config"])
    classes = traffic.classes_of(traffic.load_mix(entry["traffic"]), config)
    package = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}
    given = 0
    for kind in package:
        for m in run.metrics_of(BENCHMARK, cell, kind):
            given += 1
            reader = bench_module(f"{package[kind]}.{m['name']}")
            cls = getattr(reader, "CLASS", None)
            if cls is None:
                continue
            assert cls in classes, (cell, m["name"], cls)
            if m["name"].endswith("_roofline"):
                assert config["lanes_read"][cls], (cell, cls)
                assert set(config["scan_rows"][cls]) \
                    == set(config["lanes_read"][cls])
    assert given >= 3       # setup_s, another end-to-end, a per-layer


@pytest.mark.parametrize("name", CONFIGS)
def test_scan_rows_lie_within_the_table(name):
    config = config_of(name)
    assert config["scan_rows"], name
    for cls, tables in config["scan_rows"].items():
        assert cls in config["queries"]
        assert set(tables) == set(config["lanes_read"][cls])
        for table, spec in tables.items():
            rows = config["tables"][table]["rows"]
            assert 0 < spec["rows"] <= rows, (name, cls, table)
            if spec["pushed"] in ("", "nothing"):
                assert spec["rows"] == rows
            for lane in config["lanes_read"][cls][table]:
                assert config["lane_bytes"][lane] in (4, 8)
