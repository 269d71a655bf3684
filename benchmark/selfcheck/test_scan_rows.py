"""The roofline's row count against the program, once, at the rehearsal
scale: ``scan_rows`` as the reference computes it at ``tiny`` equals the
rows the engine's scans of q1, q3 and q6 DELIVER (``EXPLAIN ANALYZE``:
the scan nodes' own statistics), the scan fills as many lanes as
``lanes_read`` names, and the accepted configurations carry the
reference's numbers for their scale factors' tables."""

import json
import os

import pytest

from harness import engine as eng
from harness import traffic
from reference import pins

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = 0.01


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_scan_rows():
    return pins.scan_rows(TINY)


def test_the_engine_s_scans_deliver_the_reference_s_rows(
        tmp_path, monkeypatch, tiny_scan_rows):
    # split streaming: every scan is a node of its own with statistics
    # (the whole-table path fuses q1's and q6's into their one program)
    monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "0")
    c = config("tpch_sf1_1chip")
    for cls in c["queries"]:
        engine = eng.Engine("tpch", "tiny", str(tmp_path / cls))
        try:
            scans = engine.scans(traffic.load_sql(cls, c))
        finally:
            engine.stop()
        assert [s["table"] for s in scans] == list(c["lanes_read"][cls])
        for s in scans:
            want = tiny_scan_rows[cls][s["table"]]
            assert s["rows"] == want["rows"], (cls, s, want)
            # a fresh engine: every lane the scan delivers is filled
            assert s["lanes"] == len(c["lanes_read"][cls][s["table"]]), s


def test_pushed_conjuncts_are_the_file_s(tiny_scan_rows):
    for name in ("tpch_sf1_1chip", "tpch_sf10_1chip", "tpch_sf10_mesh4"):
        c = config(name)
        assert set(c["scan_rows"]) == set(c["queries"])
        for cls, tables in c["scan_rows"].items():
            assert set(tables) == set(c["lanes_read"][cls])
            for table, spec in tables.items():
                assert spec["pushed"] == tiny_scan_rows[cls][table]["pushed"]
                assert 0 < spec["rows"] <= c["tables"][table]["rows"]
                if spec["pushed"] == "nothing":
                    assert spec["rows"] == c["tables"][table]["rows"]


def test_sf1_file_carries_the_reference_s_count():
    # 6.0M rows through numpy: a few seconds (sf10's takes a minute; the
    # mesh configuration's q3 rows are the 32,369,482 and 7,284,157 that
    # PR 28's chip runs saw repartitioned: PERF.md)
    assert pins.scan_rows(1.0) == config("tpch_sf1_1chip")["scan_rows"]
