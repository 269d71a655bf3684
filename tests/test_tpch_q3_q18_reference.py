"""The q3 + q18 deployment of the benchmark (ISSUE 36,
``benchmark/configs/tpch_sf10_q3_q18_1chip.json``) at ``tpch.tiny`` on
the CPU, through the SERVED path: a coordinator started as
``benchmark/harness/engine.py`` starts one and the program's
``StatementClient``, fragments jitted and tables resident as on the
chip.

- q3 and q18 equal the benchmark's plain reference
  (``benchmark/reference/tpch_q3_q18_answers.py``) under the cell's
  limits, q18 also at a lower quantity (no order of ``tiny`` passes
  300), and the reference's float32 control does not;
- the three data pins of the configuration equal ``pins(0.01)``;
- the SQL files are the repo's own text;
- the scans deliver the configuration's lanes and rows;
- q18 runs the way the issue asks: the grouping dense, the HAVING and
  the semi join's mark counted (capacity follows the live rows), the
  semi join a cached program, and the counters say so;
- where the kept orders are compacted, lineitem's join against them
  reads the bitmap directory (0 steps, one word a probe row) and
  ``trino_tpu_join_bitmap_probes_total`` grows.
"""

import json
import os
import sys

import numpy as np
import pytest

from trino_tpu.obs.metrics import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CLASSES = ("q3", "q18")
TINY = 0.01
# tiny's fullest order holds 276 of quantity: 300 keeps none of them
LOW_QUANTITY = 200


def bench_module(name: str):
    """A module of ``benchmark/`` (it is no package of the program)."""
    import importlib
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module(name)
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "tpch_sf10_q3_q18_1chip.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sql(config):
    traffic = bench_module("harness.traffic")
    return {cls: traffic.load_sql(cls, config) for cls in config["queries"]}


@pytest.fixture(scope="module")
def reference():
    return bench_module("reference.tpch_q3_q18_answers")


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    mp.setenv("TRINO_TPU_WHOLE_TABLE", "1")
    mp.setenv("TRINO_TPU_DEVICE_GEN", "1")
    eng = bench_module("harness.engine").Engine(
        "tpch", "tiny", str(tmp_path_factory.mktemp("state")))
    yield eng
    eng.stop()
    mp.undo()


def low(text: str) -> str:
    assert "> 300" in text
    return text.replace("> 300", f"> {LOW_QUANTITY}")


def test_the_sql_is_the_repo_s_own_text(config, sql):
    from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
    assert tuple(config["queries"]) == CLASSES
    for cls in CLASSES:
        assert sql[cls].strip() == TPCH_QUERIES[int(cls[1:])].strip()
    assert sql["q18"] == TPCH_QUERIES[18].lstrip("\n")


@pytest.mark.parametrize("cls,quantity", [("q3", None), ("q18", None),
                                          ("q18", LOW_QUANTITY)])
def test_served_answers_equal_the_plain_reference(engine, config, sql,
                                                  reference, cls, quantity):
    gaps = bench_module("reference.compare").gaps
    kw = {} if quantity is None else {"quantity": quantity}
    want = reference.Answers(TINY, [cls], **kw).answer(cls)
    res = engine.client("t").execute(sql[cls] if quantity is None
                                     else low(sql[cls]))
    assert res.state == "FINISHED", res.error
    mismatches, rel = gaps(res.rows, want)
    assert mismatches <= config["limits"]["exact_mismatches"]
    assert rel <= config["limits"]["max_rel_err"]
    assert len(want) == {("q3", None): 10, ("q18", None): 0,
                         ("q18", LOW_QUANTITY): 100}[cls, quantity]


def test_no_two_kept_orders_tie_on_both_sort_keys(reference):
    """q18's ORDER BY is total only as far as its two keys (the
    configuration's ``assumed``): at this scale no tie exists, so the
    row-by-row comparison needs no rule for one."""
    a = reference.Answers(TINY, ["q18"], quantity=LOW_QUANTITY)
    keys = [(r[4], r[3]) for r in a.answer("q18")]
    assert len(set(keys)) == len(keys) == 100


def test_the_float32_control_is_not_correct(config, reference):
    """One precision below the configuration's DOUBLE: o_totalprice and
    q3's revenue miss the limit; sum(l_quantity) sums whole numbers and
    cannot differ, nor can a key, a date or a name."""
    gaps = bench_module("reference.compare").gaps
    f64 = reference.Answers(TINY, CLASSES, quantity=LOW_QUANTITY)
    f32 = reference.Answers(TINY, CLASSES, dtype=np.float32,
                            quantity=LOW_QUANTITY)
    limit = config["limits"]["max_rel_err"]
    # q3's order is by revenue: float32 may order rows otherwise, which
    # is a mismatch too; either way the control is not correct
    m3, r3 = gaps(f32.answer("q3"), f64.answer("q3"))
    assert m3 > 0 or r3 > limit
    m18, r18 = gaps(f32.answer("q18"), f64.answer("q18"))
    assert m18 > 0 or r18 > limit
    by_key = {r[2]: r for r in f64.answer("q18")}
    shared = [r for r in f32.answer("q18") if r[2] in by_key]
    assert len(shared) > 50
    assert all(r[5] == by_key[r[2]][5] and r[:4] == by_key[r[2]][:4]
               for r in shared)
    assert any(r[4] != by_key[r[2]][4] for r in shared)


@pytest.fixture(scope="module")
def tiny_pins(reference):
    return reference.pins(TINY)


def test_the_first_pin_is_the_deployment_s(engine, config, monkeypatch):
    """One node whose executor spans one chip of 15.75 GiB: what
    ``system.runtime.nodes.device_memory_bytes`` says on a backend that
    reports its memory limit; the CPU reports none (NULL), and a program
    without the column fails the statement (the parent: its run of the
    cell ends there, exit code 1)."""
    import jax
    name, spec = next(iter(config["tables"].items()))
    assert (name, spec["pins"]) == ("nodes", "deployment")
    assert (spec["rows"], spec["pin_sum"]) == (1, 15)
    res = engine.client("pins").execute(spec["pin_sql"])
    assert [tuple(r) for r in res.rows] == [(1, None)]

    class Chip:
        def memory_stats(self):
            return {"bytes_limit": 16_911_433_728, "bytes_in_use": 5}
    monkeypatch.setattr(jax, "local_devices", lambda: [Chip(), Chip()])
    res = engine.client("pins").execute(spec["pin_sql"])
    assert [tuple(r) for r in res.rows] == [(spec["rows"], spec["pin_sum"])]
    res = engine.client("pins").execute(
        "select devices, device_memory_bytes from system.runtime.nodes")
    assert [tuple(r) for r in res.rows] == [(1, 16_911_433_728)]


@pytest.mark.parametrize("table", ["lineitem", "orders", "customer"])
def test_data_pins(engine, config, tiny_pins, table):
    pins = bench_module("reference.pins")
    spec = config["tables"][table]
    assert spec["pin_sql"] == pins.PIN_SQL[table]
    res = engine.client("pins").execute(spec["pin_sql"])
    want = tiny_pins[table]
    assert tuple(res.rows[0]) == (want["rows"], want["pin_sum"])
    mesh = json.load(open(os.path.join(BENCH, "configs",
                                       "tpch_sf10_mesh4.json")))
    assert (spec["rows"], spec["pin_sum"]) == (
        mesh["tables"][table]["rows"], mesh["tables"][table]["pin_sum"])


# ---- what the rooflines count, held to the engine's scans ------------------
@pytest.mark.parametrize("cls", CLASSES)
def test_scans_deliver_the_configuration_s_lanes(tmp_path, monkeypatch,
                                                 config, sql, cls):
    # split streaming: every scan is a node of its own with statistics
    monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "0")
    eng = bench_module("harness.engine").Engine("tpch", "tiny",
                                                str(tmp_path))
    try:
        scans = eng.scans(sql[cls])
    finally:
        eng.stop()
    tiny_rows = bench_module("reference.pins").scan_rows(TINY)
    lanes = config["lanes_read"][cls]
    assert set(config["scan_rows"][cls]) == set(lanes)
    # q18 scans lineitem twice (the join, then the IN-subquery's
    # grouping) over the same two lanes: the configuration counts it once
    assert [s["table"] for s in scans] == {
        "q3": ["lineitem", "orders", "customer"],
        "q18": ["lineitem", "orders", "lineitem", "customer"]}[cls]
    assert set(s["table"] for s in scans) == set(lanes)
    pins = bench_module("reference.pins").pins(TINY)
    for s in scans:
        spec = config["scan_rows"][cls][s["table"]]
        want = (tiny_rows["q3"][s["table"]]["rows"] if cls == "q3"
                else pins[s["table"]]["rows"])
        assert s["rows"] == want, s
        if cls == "q3":
            assert spec["pushed"] == tiny_rows["q3"][s["table"]]["pushed"]
        else:
            assert spec["pushed"] == "nothing"
            assert spec["rows"] == config["tables"][s["table"]]["rows"]
        assert all(lane in config["lane_bytes"]
                   for lane in lanes[s["table"]])
        # a fresh engine: every lane the scan delivers is filled (both
        # scans of q18's lineitem ask for the same two)
        assert s["lanes"] == len(lanes[s["table"]]), s


def test_the_bytes_are_the_issue_s(config):
    roofline = bench_module("harness.roofline")
    assert roofline.query_bytes(config, "q3") == 969_687_336
    assert roofline.query_bytes(config, "q18") == 1_398_119_904


# ---- how q18 runs -----------------------------------------------------------
def samples(name: str) -> dict:
    return {k: v for k, v in METRICS.counter(name).samples()}


def grown(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}


NAMES = ("trino_tpu_groupby_total", "trino_tpu_groupby_lanes_total",
         "trino_tpu_host_reads_total", "trino_tpu_device_programs_total")


def test_q18_groups_dense_and_counts_its_filters(engine, sql):
    engine.client("t").execute(low(sql["q18"]))         # warm: traces
    before = {n: samples(n) for n in NAMES}
    res = engine.client("t").execute(low(sql["q18"]))
    assert res.state == "FINISHED" and len(res.rows) == 100
    grew = {n: grown(samples(n), before[n]) for n in NAMES}
    # the IN-subquery's grouping over lineitem (59,969 rows, capacity
    # 2^16): dense, in the whole-table program, counted per dispatch
    lanes = grew["trino_tpu_groupby_lanes_total"]
    assert lanes[("stream_dense", "dense")] == 1 << 16
    assert grew["trino_tpu_groupby_total"][("stream_dense", "dense")] == 1
    # the final grouping by five keys (a DOUBLE among them) keeps the
    # sort: it runs eagerly over the second join's 2^13 output lanes
    assert lanes == {("stream_dense", "dense"): 1 << 16,
                     ("eager", "sort"): 1 << 13}
    reads = grew["trino_tpu_host_reads_total"]
    assert reads[("groupby_key_range",)] == 1
    # the HAVING over the group slots; tiny's orders (2^14 lanes) are
    # under COUNTED_FILTER_MIN_LANES, so the mark's filter is not counted
    assert reads[("filter_rows",)] == 1
    programs = grew["trino_tpu_device_programs_total"]
    assert programs[("semi_join",)] == 1
    assert programs[("stream_dense",)] == 1 and programs[("compact",)] == 1
    spans = engine.co.tracker.get(res.query_id).trace.all_spans()
    dense = [s for s in spans if str(s.attrs.get("program", ""))
             .startswith("stream_dense:")]
    assert [s.attrs["form"] for s in dense] == ["dense"]
    kept = [s.attrs for s in spans if s.name == "host_read"
            and s.attrs.get("site") == "filter_rows"]
    # lineitem's keys ascend in runs of at most seven: the groups are
    # made in row space (2^16 lanes), no slot, no scatter; what the
    # HAVING left of them is counted and compacted
    ranged = [s.attrs for s in spans if s.name == "host_read"
              and s.attrs.get("site") == "groupby_key_range"]
    assert ranged == [dict(ranged[0], fits=1, ascending=1, run=8)]
    assert kept[0]["lanes"] == 1 << 16 and kept[0]["rows"] > 100


def test_a_big_filter_is_counted_and_its_capacity_follows(engine,
                                                          monkeypatch):
    """The rule of the counted filter at a bound tiny reaches: the semi
    join's mark on orders (15,000 rows, 2^14 lanes) keeps the few orders
    over the quantity, and the join above builds at their capacity."""
    from trino_tpu.exec import executor as ex
    from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
    text = low(TPCH_QUERIES[18])
    want = engine.client("t").execute(text).rows
    monkeypatch.setattr(ex, "COUNTED_FILTER_MIN_LANES", 1 << 14)
    before = samples("trino_tpu_host_reads_total")
    res = engine.client("t").execute(text)
    assert res.rows == want
    reads = grown(samples("trino_tpu_host_reads_total"), before)
    assert reads[("filter_rows",)] == 2
    spans = engine.co.tracker.get(res.query_id).trace.all_spans()
    kept = [s.attrs for s in spans if s.name == "host_read"
            and s.attrs.get("site") == "filter_rows"]
    assert [k["lanes"] for k in kept] == [1 << 16, 1 << 14]
    assert kept[0]["rows"] == kept[1]["rows"] > 100


def test_q18_s_lineitem_join_reads_the_bitmap(engine, reference, config,
                                              monkeypatch):
    """The orders the HAVING keeps, compacted (the counted filter's
    bound moved to tiny's 2^14 lanes): a build side of a few hundred
    unique order keys over tiny's whole range of 60,000, wider than the
    32 values a build row its exact directory holds. Lineitem's probe
    reads the bitmap directory; the answer is the reference's."""
    from trino_tpu.exec import executor as ex
    from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
    gaps = bench_module("reference.compare").gaps
    monkeypatch.setattr(ex, "COUNTED_FILTER_MIN_LANES", 1 << 14)
    name = "trino_tpu_join_bitmap_probes_total"
    before = samples(name)
    res = engine.client("t").execute(low(TPCH_QUERIES[18]))
    assert res.state == "FINISHED", res.error
    want = reference.Answers(TINY, ["q18"],
                             quantity=LOW_QUANTITY).answer("q18")
    mismatches, rel = gaps(res.rows, want)
    assert mismatches <= config["limits"]["exact_mismatches"]
    assert rel <= config["limits"]["max_rel_err"]
    spans = engine.co.tracker.get(res.query_id).trace.all_spans()
    reads = [s.attrs for s in spans if s.name == "host_read"
             and s.attrs.get("site") == "join_total"]
    lineitem = [r for r in reads if r["probe_rows"] == 59_969]
    assert len(lineitem) == 1
    assert (lineitem[0]["bitmap"], lineitem[0]["steps"],
            lineitem[0]["exact"]) == (1, 0, 0)
    assert grown(samples(name), before)[("join_total",)] >= 1
