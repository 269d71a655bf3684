"""The TPC-DS star-join deployment of the benchmark (ISSUE 34,
``benchmark/configs/tpcds_sf10_1chip.json``) at ``tpcds.tiny`` on the
CPU, through the SERVED path: a coordinator started as
``benchmark/harness/engine.py`` starts one and the program's
``StatementClient``, fragments jitted as on the chip.

- q3, q7 and q96 equal the benchmark's plain reference
  (``benchmark/reference/tpcds_answers.py``) under the cell's limits,
  and the reference's float32 control does not;
- the eight data pins of the configuration equal ``pins(0.01)``;
- ``host_read[join_total]`` carries the join's shape (``probe_rows``,
  ``total``) and the two counters grow by them;
- the configuration's ``scan_rows`` and ``lanes_read`` are what the
  engine's scans deliver;
- a fact table whose lanes fit the scan-cache budget stays resident
  over cycles that also read its dimensions, and a resident table of
  one split is no table-level miss.
"""

import json
import os
import sys

import numpy as np
import pytest

from trino_tpu.config import CONFIG
from trino_tpu.obs.metrics import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CLASSES = ("q3", "q7", "q96")
TINY = 0.01


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
    with open(os.path.join(BENCH, "configs", "tpcds_sf10_1chip.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sql(config):
    traffic = bench_module("harness.traffic")
    return {cls: traffic.load_sql(cls, config) for cls in config["queries"]}


@pytest.fixture(scope="module")
def reference():
    return bench_module("reference.tpcds_answers")


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    eng = bench_module("harness.engine").Engine(
        "tpcds", "tiny", str(tmp_path_factory.mktemp("state")))
    yield eng
    eng.stop()
    mp.undo()


def test_the_sql_is_the_repo_s_own_text(config, sql):
    from trino_tpu.benchmarks.tpcds_queries import TPCDS_QUERIES
    assert list(config["queries"]) == list(CLASSES)
    for cls in CLASSES:
        assert sql[cls].strip() == TPCDS_QUERIES[int(cls[1:])].strip()


@pytest.mark.parametrize("cls", CLASSES)
def test_served_answers_equal_the_plain_reference(engine, config, sql,
                                                  reference, cls):
    gaps = bench_module("reference.compare").gaps
    want = reference.Answers(TINY, [cls]).answer(cls)
    res = engine.client("t").execute(sql[cls])
    assert res.state == "FINISHED", res.error
    mismatches, rel = gaps(res.rows, want)
    assert mismatches <= config["limits"]["exact_mismatches"]
    assert rel <= config["limits"]["max_rel_err"]
    assert len(want) == {"q3": 7, "q7": 100, "q96": 1}[cls]


def test_the_float32_control_is_not_correct(config, reference):
    """One precision below the configuration's DOUBLE: q3 (sums of
    prices) and q7 (averages) miss the limit; q96 is a count and cannot."""
    gaps = bench_module("reference.compare").gaps
    f64 = reference.Answers(TINY, CLASSES)
    f32 = reference.Answers(TINY, CLASSES, dtype=np.float32)
    rel = {cls: gaps(f32.answer(cls), f64.answer(cls)) for cls in CLASSES}
    assert all(m == 0 for m, _r in rel.values())
    assert rel["q3"][1] > config["limits"]["max_rel_err"]
    assert rel["q7"][1] > config["limits"]["max_rel_err"]
    assert rel["q96"][1] == 0.0


@pytest.fixture(scope="module")
def tiny_pins(reference):
    return reference.pins(TINY)


@pytest.mark.parametrize("table", [
    "store_sales", "customer_demographics", "item", "time_dim", "date_dim",
    "household_demographics", "promotion", "store"])
def test_data_pins(engine, config, reference, tiny_pins, table):
    spec = config["tables"][table]
    assert spec["pin_sql"] == reference.PIN_SQL[table]
    res = engine.client("pins").execute(spec["pin_sql"])
    want = tiny_pins[table]
    assert tuple(res.rows[0]) == (want["rows"], want["pin_sum"])
    assert spec["rows"] == bench_module("reference.tpcds_rows").table_rows(
        table, config["scale_factor"])


def test_the_eight_tables_are_the_configuration_s(config, tiny_pins):
    assert set(config["tables"]) == set(tiny_pins)
    for cls, tables in config["scan_rows"].items():
        assert set(tables) == set(config["lanes_read"][cls])
        for table, spec in tables.items():
            # nothing is pushed into a tpcds scan: it delivers the table
            assert spec == {"rows": config["tables"][table]["rows"],
                            "pushed": ""}


# ---- the join's shape on its one read --------------------------------------
SHAPE = ("trino_tpu_join_probes_total", "trino_tpu_join_probe_rows_total",
         "trino_tpu_join_output_rows_total")


def counted() -> tuple:
    return tuple(sum(v for _k, v in METRICS.counter(name).samples())
                 for name in SHAPE)


def join_reads(engine, text):
    """(result, the attrs of each join's one ``host_read[join_total]``,
    in execution order)."""
    res = engine.client("t").execute(text)
    assert res.state == "FINISHED", res.error
    spans = engine.co.tracker.get(res.query_id).trace.all_spans()
    return res, [s.attrs for s in spans if s.name == "host_read"
                 and s.attrs.get("site") == "join_total"]


def test_join_total_carries_the_join_s_shape(engine, sql, tiny_pins):
    before = counted()
    res, reads = join_reads(engine, sql["q96"])
    # three joins, left-deep, the fact table the first probe side; each
    # join's output is the next one's probe side
    assert len(reads) == 3
    assert reads[0]["probe_rows"] == tiny_pins["store_sales"]["rows"]
    for this, following in zip(reads, reads[1:]):
        assert following["probe_rows"] == this["total"]
    assert reads[-1]["total"] == res.rows[0][0]
    assert all("steps" in r and "exact" in r for r in reads)
    grew = tuple(a - b for a, b in zip(counted(), before))
    assert grew == (3, sum(r["probe_rows"] for r in reads),
                    sum(r["total"] for r in reads))


def test_a_join_that_finds_rows_counts_them(engine, sql, reference):
    """q3's two joins: the rows the second puts out are the rows the
    reference selected."""
    _res, reads = join_reads(engine, sql["q3"])
    answers = reference.Answers(TINY, ["q3"])
    assert len(reads) == 2 and reads[1]["probe_rows"] == reads[0]["total"]
    assert reads[1]["total"] == sum(len(x[0]) for x in answers._q3) > 0


# ---- what the rooflines count, held to the engine's scans ------------------
@pytest.mark.parametrize("cls", ["q7", "q96"])
def test_scans_deliver_the_configuration_s_lanes(tmp_path, monkeypatch,
                                                 config, sql, tiny_pins,
                                                 cls):
    # split streaming: every scan is a node of its own with statistics
    monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "0")
    eng = bench_module("harness.engine").Engine("tpcds", "tiny",
                                                str(tmp_path))
    try:
        scans = eng.scans(sql[cls])
    finally:
        eng.stop()
    lanes = config["lanes_read"][cls]
    assert [s["table"] for s in scans] == list(lanes)
    for s in scans:
        assert s["rows"] == tiny_pins[s["table"]]["rows"], s
        assert all(lane in config["lane_bytes"] for lane in lanes[s["table"]])
        # a fresh engine: every lane the scan delivers is filled
        assert s["lanes"] == len(lanes[s["table"]]), s


# ---- residency --------------------------------------------------------------
def scan_counts() -> dict:
    return {k: v for k, v in
            METRICS.counter("trino_tpu_scan_cache_total").samples()}


def test_a_fact_table_that_fits_the_budget_stays_resident(monkeypatch, sql):
    """The deployment's proportions at tiny: store_sales in several
    splits (so it is concatenated under a whole-table entry), a budget
    a fifth over what the classes' lanes come to. After two cycles every
    lane of every table is still there, the third fills nothing, and
    every lookup of it is a hit."""
    from trino_tpu.catalog import CatalogManager
    from trino_tpu.connectors.tpcds import TpcdsConnector
    from trino_tpu.exec import executor as ex
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.session import Session
    monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "1")
    conn = TpcdsConnector(rows_per_split=1 << 14)
    catalogs = CatalogManager()
    catalogs.register("tpcds", conn)
    runner = LocalQueryRunner(
        catalogs=catalogs, session=Session(catalog="tpcds", schema="tiny"))

    def cycle():
        return [runner.execute(sql[cls]).rows for cls in CLASSES]

    def state():
        with ex._SCAN_CACHE_LOCK:
            s = ex._SCAN_CACHES.get(conn)
            return s["bytes"], {k[1]: set(e["cols"])
                                for k, e in s["entries"].items()}

    want = cycle()
    full, lanes = state()
    assert len(lanes["store_sales"]) == 12 and len(lanes) == 8
    with ex._SCAN_CACHE_LOCK:
        ex._SCAN_CACHES.clear()
    monkeypatch.setattr(CONFIG, "scan_cache_bytes", int(full * 1.2))
    assert cycle() == want and cycle() == want
    assert state() == (full, lanes)
    before = scan_counts()
    fills = METRICS.histogram("trino_tpu_scan_fill_seconds").count()
    assert cycle() == want
    grew = {k: v - before.get(k, 0) for k, v in scan_counts().items()}
    assert METRICS.histogram("trino_tpu_scan_fill_seconds").count() == fills
    assert sum(v for k, v in grew.items() if "miss" in str(k)) == 0
    assert sum(v for k, v in grew.items() if "hit" in str(k)) == 12
    assert state() == (full, lanes)
