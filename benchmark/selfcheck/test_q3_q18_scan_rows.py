"""``tpch_sf10_q3_q18_1chip``'s file against the engine's scans at
``tiny`` and against the benchmark's own generator at SF 10 (by hand,
like its siblings: the SF 10 passes take a few minutes of numpy).

- at ``tiny`` the engine's scans of q3 and q18 deliver the rows the
  reference counts, and fill as many lanes as ``lanes_read`` names;
  q18's plan scans lineitem twice over the same two lanes, which the
  configuration counts once;
- at SF 10 the file's rows and pins are ``pins(10.0)``, q3's
  ``scan_rows`` are ``scan_rows(10.0)``'s, q18's are its tables';
- at SF 10 the reference keeps the orders the ``assumed`` speak of: no
  two tie on both of q18's sort keys, and none has a total within 1e-6
  of a half cent.
"""

import json
import os

import numpy as np
import pytest

from harness import engine as eng
from harness import roofline, traffic
from reference import pins, tpch_q3_q18_answers

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = 0.01


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(HERE, "configs",
                           "tpch_sf10_q3_q18_1chip.json")) as f:
        return json.load(f)


def test_the_engine_s_scans_deliver_the_file_s_lanes(tmp_path, monkeypatch,
                                                     config):
    # split streaming: every scan is a node of its own with statistics
    monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "0")
    tiny_rows, tiny_pins = pins.scan_rows(TINY), pins.pins(TINY)
    for cls in config["queries"]:
        engine = eng.Engine("tpch", "tiny", str(tmp_path / cls))
        try:
            scans = engine.scans(traffic.load_sql(cls, config))
        finally:
            engine.stop()
        lanes = config["lanes_read"][cls]
        assert list(dict.fromkeys(s["table"] for s in scans)) == list(lanes)
        assert len(scans) == {"q3": 3, "q18": 4}[cls]
        for s in scans:
            want = (tiny_rows["q3"][s["table"]]["rows"] if cls == "q3"
                    else tiny_pins[s["table"]]["rows"])
            assert s["rows"] == want, (cls, s)
            assert s["lanes"] == len(lanes[s["table"]]), (cls, s)


def test_rows_pins_and_scan_rows_are_the_generator_s_at_sf10(config):
    want = pins.pins(10.0)
    assert next(iter(config["tables"].values()))["pins"] == "deployment"
    for table, spec in config["tables"].items():
        if spec.get("pins") == "deployment":
            continue
        assert spec["pin_sql"] == pins.PIN_SQL[table]
        assert (spec["rows"], spec["pin_sum"]) == (
            want[table]["rows"], want[table]["pin_sum"])
    assert config["scan_rows"]["q3"] == pins.scan_rows(10.0)["q3"]
    for table, spec in config["scan_rows"]["q18"].items():
        assert spec == {"rows": config["tables"][table]["rows"],
                        "pushed": "nothing"}
    assert roofline.query_bytes(config, "q3") == 969_687_336
    assert roofline.query_bytes(config, "q18") == 1_398_119_904


def test_sf10_s_kept_orders_need_no_tie_rule_and_no_half_cent(config):
    a = tpch_q3_q18_answers.Answers(10.0, ["q18"])
    kept = {k: np.concatenate([c[k] for c in a._q18]) for k in a._q18[0]}
    assert 100 < len(kept["o_orderkey"]) < 5000
    keys = set(zip(kept["o_totalprice"].tolist(),
                   kept["o_orderdate"].tolist()))
    assert len(keys) == len(kept["o_orderkey"])
    assert len(a.answer("q18")) == 100
    # the cents of a total: how far the unrounded sum is from a half
    # cent is not kept; the rounded totals at least are whole cents
    cents = kept["o_totalprice"] * 100
    assert np.abs(cents - np.rint(cents)).max() < 1e-6
