"""The exact directory of the join probe (ISSUE 29) as the two
executors run it: ``tpch.tiny`` through a ``Coordinator`` over HTTP, on
one device (fragments jitted, as on the chip) and on a 4-device mesh of
the suite's virtual CPU devices.

- q3 equals the benchmark's plain reference, and BOTH of its joins
  (lineitem x orders on the order key, their result x customer on the
  customer key: dense integer keys) read ``exact`` in their one
  ``host_read[join_total]``, with 0 steps;
- a join on two columns and a join on a DOUBLE key count none, and
  answer what numpy counts from the key columns;
- every one of them reads ``packed`` beside it (ISSUE 37: a probe row
  read its bucket's bounds as ONE directory word), and so do the four
  joins of a TPC-DS star chain (q7); a build side where one key fills
  more of a bucket than the word's size bits hold reads 0, and answers
  the same;
- none of them reads ``bitmap``; a build side of a few unique keys
  spread wider than its exact directory holds does (the bitmap
  directory: 0 steps, one word a probe row), and answers what numpy
  counts.
"""

import collections
import json
import os
import sys

import pytest

from trino_tpu.obs.metrics import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

# name -> (the join, the two scans of its key columns)
HASHED = {
    "two_columns": (
        "select count(*) from lineitem l join partsupp ps "
        "on l.l_partkey = ps.ps_partkey and l.l_suppkey = ps.ps_suppkey",
        "select l_partkey, l_suppkey from lineitem",
        "select ps_partkey, ps_suppkey from partsupp"),
    "double": (
        "select count(*) from customer c join supplier s "
        "on c.c_acctbal = s.s_acctbal",
        "select c_acctbal from customer",
        "select s_acctbal from supplier"),
}


@pytest.fixture(scope="module")
def q3():
    """(sql, the reference's answer, the comparison, the cell's limits)."""
    sys.path.insert(0, BENCH)
    try:
        from reference.compare import gaps
        from reference.tpch_answers import Answers
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", "tpch_sf1_1chip.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "queries", "q3.sql")) as f:
        sql = f.read()
    answer = Answers(config["rehearsal_scale_factor"], ["q3"]).answer("q3")
    return sql, answer, gaps, config["limits"]


@pytest.fixture(scope="module", params=["one_device", "mesh"])
def coordinator(request, tmp_path_factory):
    from trino_tpu.parallel import get_mesh
    from trino_tpu.server import Coordinator
    mp = pytest.MonkeyPatch()
    mp.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    mesh = request.param == "mesh"
    co = Coordinator(distributed=mesh, history_dir=str(
        tmp_path_factory.mktemp("history")))
    if mesh:
        co._proto.mesh = get_mesh(4)    # four of the suite's eight devices
    co.start()
    yield co
    co.stop()
    mp.undo()


def counted() -> tuple:
    return tuple(
        sum(v for _k, v in METRICS.counter(name).samples())
        for name in ("trino_tpu_join_probes_total",
                     "trino_tpu_join_exact_probes_total",
                     "trino_tpu_join_search_steps_total",
                     "trino_tpu_join_packed_probes_total",
                     "trino_tpu_join_bitmap_probes_total"))


def execute(co, sql, catalog="tpch"):
    """(rows, the (steps, exact, packed, bitmap) of each join's one
    read, the growth of the five counters)."""
    from trino_tpu.client import StatementClient
    before = counted()
    res = StatementClient(co.base_uri, catalog=catalog,
                          schema="tiny").execute(sql)
    assert res.state == "FINISHED", res.error
    spans = co.tracker.get(res.query_id).trace.all_spans()
    reads = [(s.attrs.get("steps"), s.attrs.get("exact"),
              s.attrs.get("packed"), s.attrs.get("bitmap")) for s in spans
             if s.name == "host_read"
             and s.attrs.get("site") == "join_total"]
    return res.rows, reads, tuple(
        a - b for a, b in zip(counted(), before))


def test_q3_s_joins_are_both_exact(coordinator, q3):
    sql, answer, gaps, limits = q3
    rows, reads, grew = execute(coordinator, sql)
    mismatches, rel = gaps(rows, answer)
    assert mismatches <= limits["exact_mismatches"]
    assert rel <= limits["max_rel_err"]
    assert reads == [(0, 1, 1, 0), (0, 1, 1, 0)]
    assert grew == (2, 2, 0, 2, 0)


@pytest.mark.parametrize("key", sorted(HASHED))
def test_a_hashed_key_counts_no_exact_probe(coordinator, key):
    join, probe_keys, build_keys = HASHED[key]
    rows, reads, grew = execute(coordinator, join)
    have = collections.Counter(
        map(tuple, execute(coordinator, build_keys)[0]))
    want = sum(have[tuple(r)]
               for r in execute(coordinator, probe_keys)[0])
    assert rows == [[want]] and want > 0
    assert len(reads) == 1 and reads[0][1:] == (0, 1, 0) and reads[0][0] > 0
    assert grew[:2] == (1, 0) and grew[2:] == (reads[0][0], 1, 0)


def test_a_star_chain_s_probes_all_read_one_word(coordinator, request):
    """TPC-DS q7 at ``tiny``: the fact table against four filtered
    dimensions, every join on a dimension's surrogate key (its answer:
    tests/test_tpcds_reference.py). The mesh executor shards no tpcds
    table yet (PERF.md §7): there, TPC-H's chain from the fact table to
    ``nation``."""
    if request.node.callspec.params["coordinator"] == "mesh":
        rows, reads, grew = execute(
            coordinator,
            "select n.n_name, count(*) from lineitem l "
            "join orders o on l.l_orderkey = o.o_orderkey "
            "join customer c on o.o_custkey = c.c_custkey "
            "join nation n on c.c_nationkey = n.n_nationkey "
            "join region r on n.n_regionkey = r.r_regionkey "
            "group by n.n_name")
        assert len(rows) == 25
    else:
        with open(os.path.join(BENCH, "traffic", "queries", "tpcds",
                               "q7.sql")) as f:
            rows, reads, grew = execute(coordinator, f.read(), "tpcds")
        assert len(rows) > 0
    assert reads == [(0, 1, 1, 0)] * 4
    assert grew == (4, 4, 0, 4, 0)


def test_a_build_side_with_one_heavy_key_reads_two_sums(coordinator):
    """Every lineitem row under ONE key value (60,175 of them, where a
    word at this capacity holds sizes under 2^15): the exact directory
    still, 0 steps, but no packed probe, and the same count."""
    rows, reads, grew = execute(
        coordinator,
        "select count(*), sum(l.l_quantity) from region r left join "
        "lineitem l on r.r_regionkey = l.l_linenumber - l.l_linenumber")
    n, quantity = execute(
        coordinator, "select count(*), sum(l_quantity) from lineitem")[0][0]
    assert rows == [[n + 4, quantity]]     # four regions find no row
    assert reads == [(0, 1, 0, 0)]
    assert grew == (1, 1, 0, 0, 0)


def test_a_wide_unique_build_side_reads_the_bitmap(coordinator):
    """Twenty-one order keys from 1 to 59,981 (a build capacity of 32: an
    exact directory of 1,024 values) against lineitem: the bitmap
    directory, 0 steps, one word a probe row, where every shard had it
    on the mesh; the count is numpy's."""
    keys = list(range(1, 60_000, 2_999))
    rows, reads, grew = execute(
        coordinator,
        "select count(*) from lineitem l join (values %s) o(k) "
        "on l.l_orderkey = o.k" % ",".join("(%d)" % k for k in keys))
    have = [r[0] for r in execute(
        coordinator, "select l_orderkey from lineitem")[0]]
    want = sum(k in set(keys) for k in have)
    assert rows == [[want]] and want > 0
    assert reads == [(0, 0, 1, 1)]
    assert grew == (1, 0, 0, 1, 1)


def test_the_one_read_carries_the_join_s_shape(coordinator):
    """``probe_rows`` and ``total`` ride the same int64 vector as
    ``steps`` and ``exact`` (ISSUE 34), and ``packed`` is its fifth
    entry (ISSUE 37); on the mesh the rows are summed over the shards
    and ``packed`` holds where every shard's was, so both executors
    report the join's whole shape, on the span and at ``/metrics``."""
    import urllib.request
    from trino_tpu.client import StatementClient
    names = ("trino_tpu_join_probe_rows_total",
             "trino_tpu_join_output_rows_total",
             "trino_tpu_join_packed_probes_total")

    def grown():
        return [sum(v for _k, v in METRICS.counter(n).samples())
                for n in names]
    before = grown()
    res = StatementClient(coordinator.base_uri, catalog="tpch",
                          schema="tiny").execute(
        "select count(*) from orders o join customer c "
        "on o.o_custkey = c.c_custkey")
    assert res.state == "FINISHED", res.error
    spans = coordinator.tracker.get(res.query_id).trace.all_spans()
    reads = [s.attrs for s in spans if s.name == "host_read"
             and s.attrs.get("site") == "join_total"]
    assert res.rows == [[15000]] and len(reads) == 1
    assert reads[0]["probe_rows"] == 15000 and reads[0]["total"] == 15000
    assert reads[0]["packed"] == 1 and reads[0]["exact"] == 1
    assert [a - b for a, b in zip(grown(), before)] == [15000, 15000, 1]
    with urllib.request.urlopen(coordinator.base_uri + "/metrics") as r:
        text = r.read().decode()
    assert 'trino_tpu_join_packed_probes_total{site="join_total"}' in text
