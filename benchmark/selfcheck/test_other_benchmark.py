"""A cell of ANOTHER benchmark is new files and new entries: a throwaway
tree in ``tmp_path`` (a BENCHMARK.json with one more configuration and
cell, the configuration's file with catalog ``tpcds``, its SQL in a
sub-directory of its own with a class called ``q3`` that is TPC-DS's,
no ``q6``, and a three-line reference) rehearses to a well-formed line
with the metrics every cell can report and without ``q6_p50_ms``. And a
cell that a metric lists but cannot serve ends the run, exit code 4,
no result line, with the metric and its reader named on standard error:
what the driver's check would refuse (PR 32), found in the rehearsal.
"""

import json
import os
import shutil
import sys
import types

import pytest

import run as bench_run
from harness import traffic

DS_Q3 = """\
select d_year, i_brand_id, i_brand, sum(ss_ext_sales_price) sum_agg
from date_dim, store_sales, item
where d_date_sk = ss_sold_date_sk
  and ss_item_sk = i_item_sk
  and i_manufact_id = 128
  and d_moy = 11
group by d_year, i_brand_id, i_brand
order by d_year, sum_agg desc, i_brand_id
limit 100
"""
CELL = "ds_tiny.power"
CONFIG = {
    "name": "ds_tiny", "source": "a throwaway of the selfcheck",
    "catalog": "tpcds", "schema": "tiny", "scale_factor": 0.01,
    "rehearsal_schema": "tiny", "rehearsal_scale_factor": 0.01,
    "queries": ["q3"], "queries_dir": "ds_tmp", "reference": "ds_tmp",
    "tables": {"date_dim": {
        "rows": 73049, "pin_sum": 179082983754,
        "pin_sql": "select count(*), sum(d_date_sk) from date_dim"}},
    "limits": {"max_rel_err": 1e-9, "exact_mismatches": 0,
               "failed_queries": 0, "pin_mismatches": 0},
}


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """The throwaway tree; returns the BENCHMARK.json as a dict and a
    function that writes it."""
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "ds_tiny", "source": CONFIG["source"],
                             "file": "configs/ds_tiny.json", "reduced": [],
                             "why": "selfcheck"})
    bench["workloads"].append({"name": CELL, "config": "ds_tiny",
                               "traffic": "power", "chips": 1,
                               "why": "selfcheck"})
    os.makedirs(tmp_path / "configs")
    with open(tmp_path / "configs" / "ds_tiny.json", "w") as f:
        json.dump(CONFIG, f)
    os.makedirs(tmp_path / "traffic" / "queries" / "ds_tmp")
    shutil.copy(os.path.join(traffic.HERE, "traffic", "power.json"),
                tmp_path / "traffic" / "power.json")
    with open(tmp_path / "traffic" / "queries" / "ds_tmp" / "q3.sql",
              "w") as f:
        f.write(DS_Q3)

    # the three-line reference: it answers nothing rightly (the line is
    # what is looked at here), but its pins are TPC-DS's, not TPC-H's
    ref = types.ModuleType("reference.ds_tmp")
    ref.Answers = lambda sf, classes, dtype=None: types.SimpleNamespace(
        answer=lambda cls: [])
    ref.pins = lambda sf: {"date_dim": {"rows": 73049,
                                        "pin_sum": 179082983754}}
    monkeypatch.setitem(sys.modules, "reference.ds_tmp", ref)
    monkeypatch.setattr(bench_run, "ROOT", str(tmp_path))
    monkeypatch.setattr(bench_run, "CACHE_DIR", str(tmp_path / ".cache"))
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))

    def write():
        with open(tmp_path / "BENCHMARK.json", "w") as f:
            json.dump(bench, f)
    return bench, write


def rehearse(capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "2147483693",
                         "--seconds", "1", "--trace", "0", "--rehearse"])
    io = capsys.readouterr()
    return rc, io.out.strip(), io.err


def test_a_cell_with_no_tpch_class_rehearses_to_a_whole_line(tree, capsys):
    _bench, write = tree
    write()
    rc, out, _err = rehearse(capsys)
    assert rc == 3
    result = json.loads(out.splitlines()[-1])
    assert set(result["metrics"]) == {"queries_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["attempted"] >= 1 and result["rehearsal"] is True
    assert list(result)[-1] == "checks"
    # pins came from the throwaway reference, answers did not match it
    assert result["checks"]["pin_mismatches"]["value"] == 0
    assert result["checks"]["failed_queries"]["value"] == 0
    assert result["checks"]["exact_mismatches"]["value"] > 0


def test_a_listed_metric_with_nothing_to_read_ends_the_run(tree, capsys):
    bench, write = tree
    q6 = next(m for m in bench["end_to_end"] if m["name"] == "q6_p50_ms")
    q6["workloads"].append(CELL)
    write()
    rc, out, err = rehearse(capsys)
    assert rc == bench_run.EXIT_NOTHING_TO_READ == 4
    assert out == ""
    last = [line for line in err.splitlines() if line.strip()][-1]
    assert CELL in last and "q6_p50_ms" in last
    assert os.path.join("benchmark", "end_to_end", "q6_p50_ms.py") in last
