"""BENCHMARK.json against the files it names: every configuration, mix,
query class and metric reader is there, and every cell reports
``setup_s``, another end-to-end metric and a per-layer metric."""

import importlib
import json
import os

import run as bench_run
from harness import traffic

ROOT = bench_run.ROOT


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def reader_of(kind, metric):
    package = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}
    return importlib.import_module(f"{package[kind]}.{metric['name']}")


def test_every_named_file_exists():
    b = bench()
    for w in b["workloads"]:
        _b, cell, config = bench_run.load_cell(w["name"])
        mix = traffic.load_mix(cell["traffic"])
        for cls in traffic.classes_of(mix, config):
            assert traffic.load_sql(cls, config).strip()
        reference = importlib.import_module(f"reference.{config['reference']}")
        assert callable(reference.Answers) and callable(reference.pins)
        assert set(config["limits"]) == {"max_rel_err", "exact_mismatches",
                                         "failed_queries", "pin_mismatches"}
    for kind in ("end_to_end", "per_layer"):
        for m in b[kind]:
            assert callable(reader_of(kind, m).read)


def test_a_metric_of_one_class_is_given_only_to_cells_with_the_class():
    """A reader that names its query class (``CLASS``) finds nothing in a
    cell without it, and a run that lacks a metric is refused: so every
    cell such a metric is given to has the class in its configuration
    and mix; a class's roofline also needs the class's ``lanes_read``
    (and only those classes need one)."""
    b = bench()
    for w in b["workloads"]:
        _b, cell, config = bench_run.load_cell(w["name"])
        classes = traffic.classes_of(traffic.load_mix(cell["traffic"]),
                                     config)
        for kind in ("end_to_end", "per_layer"):
            for m in bench_run.metrics_of(b, w["name"], kind):
                cls = getattr(reader_of(kind, m), "CLASS", None)
                if cls is None:
                    continue
                assert cls in classes, (w["name"], m["name"])
                if m["name"].endswith("_roofline"):
                    assert config["lanes_read"][cls], (w["name"], cls)
    listless = [m["name"] for m in b["end_to_end"] if "workloads" not in m]
    assert listless == ["queries_per_s", "setup_s"]


def test_lines_keep_to_the_contract_s_lengths():
    b = bench()
    for entry in b["workloads"] + b["configs"]:
        assert 1 <= len(entry["why"]) <= 200, entry["name"]
    for c in b["configs"]:
        assert 1 <= len(c["source"]) <= 200
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["source"] == c["source"]
    assert all(0.01 <= m["bound"] <= 0.25 for m in b["end_to_end"])


def test_every_cell_reports_enough():
    b = bench()
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        mine = [m["name"] for m in
                bench_run.metrics_of(b, w["name"], "end_to_end")]
        assert "setup_s" in mine and len(mine) >= 2
        layers = bench_run.metrics_of(b, w["name"], "per_layer")
        assert layers
        for m in layers:
            assert m["moves"] in mine and m["moves"] in e2e


def test_no_key_means_every_cell_of_the_moved_metric():
    b = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["y"]}],
         "per_layer": [{"name": "p", "moves": "a"},
                       {"name": "q", "moves": "b"},
                       {"name": "r", "moves": "a", "workloads": ["x"]}]}
    names = lambda cell: [m["name"] for m in       # noqa: E731
                          bench_run.metrics_of(b, cell, "per_layer")]
    assert names("x") == ["p", "r"]
    assert names("y") == ["p", "q"]
