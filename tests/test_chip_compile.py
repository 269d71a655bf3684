"""Compiles for a DESCRIBED TPU v5e, kept as tests: the chip's own
compiler (installed here without the chip) sees the grouped-sum kernel
at its real shapes, the fused q1 stage program with the kernel in it,
q6's filter, the two programs of the materialized hash join (the
expand in both forms of its ``run_positions``), the streamed probe,
and the mesh executor's exchange, per-shard count and per-shard
expand — what it refuses fails here at no chip time. Nothing runs, so
these say nothing about results or speed; ``chip_smoke.py`` is the
run on the chip.

This is the ONLY file that describes the chip. The topology is
described inside a module-scoped fixture (never at import: every xdist
worker imports every test file, and only one process may load the
TPU's library), the persistent compile cache is off around the
compiles (an entry written for a described chip cannot be read back
without one), and there are no child processes.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

KERNEL_SHAPES = [(8, 1024), (24, 1 << 20), (24, 1 << 22)]
Q1_CAPACITY = 1 << 22          # EngineConfig.max_batch_rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _as_structs(tree, capacity, sharding):
    """A pytree of real (tiny) arrays -> the same tree of shapes at
    ``capacity`` rows placed on the described chip."""
    return jax.tree.map(
        lambda a: _struct((capacity,) if np.ndim(a) else (),
                          jnp.asarray(a).dtype, sharding), tree)


@pytest.mark.parametrize("k,cap", KERNEL_SHAPES)
def test_grouped_sum_kernel_compiles(one_chip, no_persistent_cache,
                                     k, cap):
    from trino_tpu.ops import pallas_groupby as pg
    compiled = pg._grouped_sums_impl.lower(
        _struct((cap,), jnp.int32, one_chip),
        _struct((k, cap), jnp.float32, one_chip), False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_q1_stage_program_compiles_with_kernel(one_chip,
                                               no_persistent_cache,
                                               monkeypatch):
    """The fused q1 stage program (__graft_entry__._q1_step) at
    max_batch_rows, with the kernel in it. Code that asks
    jax.default_backend() sees the CPU during such a compile, so the
    kernel selection is steered here."""
    import __graft_entry__ as ge
    from trino_tpu.ops import pallas_groupby as pg
    monkeypatch.setattr(pg, "mode", lambda: "tpu")
    ge.entry()                      # eager imports, outside the trace
    args = _as_structs(ge._q1_inputs(8), Q1_CAPACITY, one_chip)
    compiled = jax.jit(ge._q1_step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the program must fit the chip beside its own arguments
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < 16 << 30


def test_q6_filter_constants_leave_the_per_row_program(
        one_chip, no_persistent_cache):
    """q6's WHERE as the planner leaves it in the Filter (``l_shipdate <
    date '1994-01-01' + interval '1' year`` and the two decimal bounds
    of the discount), at max_batch_rows. ``date + interval`` is civil
    calendar arithmetic in int64 with floor divisions: evaluated per
    row it was 2.96 MB of HLO and 4,331 flops a row (88% of
    tpch_sf10.power's device time, PERF.md PR 31); evaluated at one
    row (exec/expr.py ``_eval_constant``) the compiler folds it, and
    what is left per row is compares."""
    from trino_tpu import batch_from_pylist
    from trino_tpu.exec.expr import eval_predicate
    from trino_tpu.plan.nodes import FilterNode
    from trino_tpu.runner import LocalQueryRunner
    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "benchmark", "traffic", "queries",
                           "q6.sql")) as f:
        node = LocalQueryRunner().plan_sql(f.read())
    while not isinstance(node, FilterNode):
        node, = node.sources
    assert "date_add_interval" in str(node.predicate)
    schema = node.source.output_schema()
    rows = batch_from_pylist({c: [1, 2] for c in schema}, schema)
    compiled = jax.jit(
        lambda b: eval_predicate(node.predicate, b)).lower(
            _as_structs(rows, Q1_CAPACITY, one_chip)).compile()
    assert len(compiled.as_text()) < 100_000
    assert compiled.cost_analysis()["flops"] < 64 * Q1_CAPACITY


def _q3_join_sides():
    from trino_tpu import BIGINT, DOUBLE, batch_from_pylist
    probe = batch_from_pylist({"l_orderkey": [1, 2], "rev": [1.0, 2.0]},
                              {"l_orderkey": BIGINT, "rev": DOUBLE})
    build = batch_from_pylist({"o_orderkey": [1, 2], "o_date": [3, 4]},
                              {"o_orderkey": BIGINT, "o_date": BIGINT})
    return probe, build


@pytest.mark.parametrize("out_cap,loops", [(1 << 20, 0), (1 << 10, 1)],
                         ids=["histogram", "search"])
def test_hash_join_expand_program_compiles(one_chip,
                                           no_persistent_cache,
                                           out_cap, loops):
    """Phase 2 of the materialized hash join at the shapes q3 runs at
    sf1, 2^22 lineitem rows probing 2^20 order rows: the 64-bit cumsum
    of the counts, each output row's probe row (ops/join.py
    run_positions), the gathers. Into 2^20 output rows the probe rows
    are found by a histogram and an int32 cumsum: NO loop in the
    program (the 23-step ``searchsorted`` over the cumsum was the
    largest device operation of every join cell, PERF.md PR 35); into
    2^10, past the constant at which the histogram's 2^22 updates cost
    more than the search, exactly the search's one loop."""
    from trino_tpu.exec.executor import make_mjoin_expand_program
    from trino_tpu.ops.join import expand_form
    probe, build = _q3_join_sides()
    pcap, bcap = 1 << 22, 1 << 20
    assert expand_form(pcap, out_cap) == ("search" if loops
                                          else "histogram")
    fn = make_mjoin_expand_program("inner", None, out_cap)
    lane = _struct((pcap,), jnp.int64, one_chip)
    compiled = jax.jit(fn).lower(
        _as_structs(probe, pcap, one_chip),
        _as_structs(build, bcap, one_chip), lane, lane,
        _struct((bcap,), jnp.int64, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30
    assert len(re.findall(r" while\(", compiled.as_text())) == loops


@pytest.mark.parametrize("outputs,gathers", [
    (None, 16), (("ss_sold_time_sk", "ss_hdemo_sk"), 5)],
    ids=["every_lane", "outputs"])
def test_a_join_s_expand_gathers_the_lanes_it_is_handed(
        one_chip, no_persistent_cache, outputs, gathers):
    """TPC-DS q96's first join at SF 10 (store_sales' 2^25 lanes against
    store, 2^22 output rows), handed every lane of both inputs or the
    two its plan reads above it (executor.py ``expand_columns``): each
    lane the program is handed costs a gather of 2^22 indices per 32-bit
    word and per validity lane. Handed no build lane, the program
    gathers neither the run starts nor the offsets either: 16 -> 5."""
    from trino_tpu import BIGINT, VARCHAR, batch_from_pylist
    from trino_tpu.columnar import Batch
    from trino_tpu.exec.executor import (expand_columns, expand_lanes,
                                         make_mjoin_expand_program)
    probe = batch_from_pylist(
        {"ss_sold_time_sk": [1, 2], "ss_hdemo_sk": [1, None],
         "ss_store_sk": [1, None]},
        dict.fromkeys(("ss_sold_time_sk", "ss_hdemo_sk", "ss_store_sk"),
                      BIGINT))
    build = batch_from_pylist({"s_store_sk": [1, 2],
                               "s_store_name": ["ese", "ation"]},
                              {"s_store_sk": BIGINT,
                               "s_store_name": VARCHAR})
    pcols, bcols, _ = expand_columns(probe.columns, build.columns,
                                     expand_lanes(outputs))
    pcap, bcap, out_cap = 1 << 25, 1 << 7, 1 << 22
    lane = _struct((pcap,), jnp.int64, one_chip)
    compiled = jax.jit(make_mjoin_expand_program(
        "inner", None, out_cap)).lower(
        _as_structs(Batch(pcols, probe.num_rows), pcap, one_chip),
        _as_structs(Batch(bcols, build.num_rows), bcap, one_chip),
        lane, lane, _struct((bcap,), jnp.int64, one_chip)).compile()
    assert len(re.findall(rf"\[{out_cap}\][^=]* gather\(",
                          compiled.as_text())) == gathers


def _gathers_by_arm(text, lanes):
    """For every ``conditional`` of a compiled program's HLO text, how
    many gathers of ``lanes`` elements each of its arms holds (through
    the fusions, loops and conditionals it calls), arm 0 the false
    one: a gather costs the chip per element, so this is what an arm
    costs."""
    bodies = dict(re.findall(
        r"^(?:ENTRY )?(%[\w.\-]+) [^\n]*\{\n(.*?)^\}", text, re.M | re.S))
    wide = re.compile(r"= \w+\[%d\]\S* gather\(" % lanes)

    def count(name):
        # a computation's name inside another's body is a call of it
        body = bodies[name]
        return len(wide.findall(body)) + sum(
            count(callee) for callee in set(re.findall(r"%[\w.\-]+", body))
            if callee in bodies and callee != name)

    return [tuple(count(arm) for arm in arms.split(", "))
            for arms in re.findall(
                r" conditional\(.*?branch_computations=\{([^}]*)\}", text)]


def test_hash_join_count_program_compiles(one_chip,
                                          no_persistent_cache):
    """Phase 1: the build side sorted on its 64-bit key lane and
    indexed (a scatter-add and two scans: the bucket directory or the
    bitmap directory, and the run lengths), then the probe (ops/join.py
    probe_runs: ONE gather of a bitmap word in that form's arm;
    otherwise ONE directory gather where the build side packed its
    words — two in the arm that reads the adjacent sums —, ONE
    bisection loop whose trip count is a device value, one run-length
    gather). The probe
    side is q3's at sf1; the build side is CUT to 2^12 rows: the
    sorting network's compile time grows with its size (40 s at 2^20,
    PERF.md) and this file has to stay fast. What the compiler accepts
    does not depend on the size."""
    from trino_tpu.exec.executor import make_mjoin_count_program
    probe, build = _q3_join_sides()
    fn = make_mjoin_count_program(["l_orderkey"], ["o_orderkey"], False)
    text = jax.jit(fn).lower(
        _as_structs(probe, 1 << 22, one_chip),
        _as_structs(build, 1 << 12, one_chip)).compile().as_text()
    # one loop, not the two full-depth searches it replaced
    assert len(re.findall(r" while\(", text)) == 1
    # the bounds: two probe-sized gathers in the plain arm, ONE in the
    # packed one; the exact arm of the other conditional has none, its
    # search arm the loop's two (a 64-bit lane) and the run length; the
    # bitmap's arm ONE word against the six of the directory's; the
    # lane's branch (key - base or its hash) and the build side's index
    # (bitmap or directory, on the build rows) gather none
    assert sorted(_gathers_by_arm(text, 1 << 22)) == [
        (0, 0), (0, 0), (2, 1), (3, 0), (6, 1)]


def test_streamed_join_probe_program_compiles(one_chip,
                                              no_persistent_cache):
    """The streamed join's per-chunk program (exec/streamjoin.py): the
    same probe against a build side sorted and indexed ONCE outside it,
    plus the expansion at a static capacity (chunk 2^16 into 2^16: the
    histogram form, so the probe's bisection is the program's ONE
    loop). Build 2^20 (nothing is sorted in here, so it is not cut): a
    directory of 2^25 entries, past its head, so the bounds are read in
    one of FOUR arms (whole or head, word or sums), one gather in
    either packed arm, or as ONE bitmap word."""
    from trino_tpu.exec.streamjoin import make_probe_program
    from trino_tpu.ops.join import build_side
    probe, build = _q3_join_sides()
    cap = 1 << 16
    bstructs = _as_structs(build, 1 << 20, one_chip)
    side = jax.tree.map(
        lambda a: _struct(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda b: build_side(b, ["o_orderkey"]),
                       bstructs))
    fn = make_probe_program("inner", ["l_orderkey"], ["o_orderkey"],
                            None, cap)
    compiled = jax.jit(fn).lower(_as_structs(probe, cap, one_chip),
                                 bstructs, side).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == 1
    # the whole-or-head conditional holds the other two: its arms read
    # one of theirs; the bitmap's arm reads one word where the other
    # holds all nine; the lane's branch gathers nothing
    assert sorted(_gathers_by_arm(text, cap)) == [
        (0, 0), (2, 1), (2, 1), (3, 0), (3, 3), (9, 1)]


@pytest.mark.parametrize("kind", ["counts", "move"])
def test_mesh_exchange_compiles_for_a_2x2_mesh(topo, no_persistent_cache,
                                               kind):
    """The mesh executor's hash repartition (parallel/spmd.py) over the
    described 2x2 mesh, a shard of 2^20 rows of q3's lineitem lanes (an
    int64 key and two float64: 64-bit lanes cross the chips through
    ``all_to_all``): phase 1 counts rows per (source, destination);
    phase 2 bins them with running counts and one int32 scatter, sends
    sized buffers and lays the received runs end to end — no sort and
    no ``nonzero`` at this size (PR 23: a sort costs this compiler
    minutes)."""
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding
    from trino_tpu import BIGINT, DOUBLE, batch_from_pylist
    from trino_tpu.parallel import spmd
    P, AXIS = spmd.P, spmd.AXIS
    n, per = 4, 1 << 20
    mesh = Mesh(np.asarray(topo.devices[:n]), (AXIS,))
    cols = batch_from_pylist(
        {"k": [1, 2], "a": [1.0, 2.0], "b": [1.0, 2.0]},
        {"k": BIGINT, "a": DOUBLE, "b": DOUBLE}).columns
    rows = NamedSharding(mesh, P(AXIS))
    args = (jax.tree.map(lambda a: _struct((n * per,),
                                           jnp.asarray(a).dtype, rows),
                         cols),
            _struct((n,), jnp.int64, NamedSharding(mesh, P())))

    def counts(c, nvec):
        my_n = nvec[jax.lax.axis_index(AXIS)]
        live = jnp.arange(per, dtype=jnp.int64) < my_n
        return jax.lax.all_gather(spmd._dest_counts(
            spmd._hash_pid(c, ["k"], n), live, n), AXIS)

    def move(c, nvec):
        my_n = nvec[jax.lax.axis_index(AXIS)]
        out, new_n = spmd._shard_exchange(
            c, my_n, spmd._hash_pid(c, ["k"], n), n, per // 2, per)
        return out, jax.lax.all_gather(new_n, AXIS)

    in_specs = (spmd._col_specs(cols, P(AXIS)), P())
    f, out_specs = ((counts, P()) if kind == "counts" else
                    (move, (spmd._col_specs(cols, P(AXIS)), P())))
    text = jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
                   ).lower(*args).compile().as_text()
    assert " sort(" not in text
    if kind == "move":
        assert "all-to-all" in text


def _q3_sides_on_a_2x2_mesh(topo):
    """(mesh, q3's join sides, ``lanes(arrays, rows a shard)``: their
    row-sharded shapes, the replicated live-row counts) for the
    per-shard join programs of the mesh executor."""
    from jax.sharding import Mesh, NamedSharding
    from trino_tpu.parallel import spmd
    n = 4
    mesh = Mesh(np.asarray(topo.devices[:n]), (spmd.AXIS,))
    rows = NamedSharding(mesh, spmd.P(spmd.AXIS))
    probe, build = _q3_join_sides()

    def lanes(arrays, per):
        return jax.tree.map(
            lambda a: _struct((n * per,), jnp.asarray(a).dtype, rows),
            arrays)

    return mesh, probe, build, lanes, _struct(
        (n,), jnp.int64, NamedSharding(mesh, spmd.P()))


def test_mesh_join_expand_has_no_loop_on_a_2x2_mesh(topo,
                                                    no_persistent_cache):
    """The mesh executor's per-shard expand (exec/distributed.py
    ``_shard_join`` inside ``shard_map``) at the shapes q3 runs at on
    the four-chip cell: a shard of 2^23 lineitem rows against 2^21
    order rows into 2^19 output rows. The sorted scatter-add of
    ``run_positions`` lowers per shard as on one chip: one scatter and
    no loop, where the search over the running sums was the cell's
    largest operation (PERF.md PR 35)."""
    from jax import shard_map
    from trino_tpu.columnar import Batch
    from trino_tpu.exec.distributed import _shard_join
    from trino_tpu.parallel import spmd
    P, AXIS = spmd.P, spmd.AXIS
    per_p, per_b, out_cap = 1 << 23, 1 << 21, 1 << 19
    mesh, probe, build, lanes, live = _q3_sides_on_a_2x2_mesh(topo)

    def f(pcols, pn, bcols, bn, start, count, order):
        d = jax.lax.axis_index(AXIS)
        out = _shard_join(Batch(pcols, pn[d]), Batch(bcols, bn[d]),
                          start, count, order, "inner", None, out_cap, 0)
        return out.columns, jax.lax.all_gather(out.num_rows_device(),
                                               AXIS)

    lane = lanes(jnp.int64(0), per_p)
    in_specs = (spmd._col_specs(probe.columns, P(AXIS)), P(),
                spmd._col_specs(build.columns, P(AXIS)), P(),
                P(AXIS), P(AXIS), P(AXIS))
    text = jax.jit(shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=(P(AXIS), P()),
        check_vma=False)).lower(
            lanes(probe.columns, per_p), live,
            lanes(build.columns, per_b), live, lane, lane,
            lanes(jnp.int64(0), per_b)
    ).compile().as_text()
    assert " while(" not in text
    assert len(re.findall(r" scatter\(", text)) == 1


def test_mesh_join_count_reads_one_word_on_a_2x2_mesh(topo,
                                                      no_persistent_cache):
    """The mesh executor's per-shard count program (exec/distributed.py
    ``build_count``: ``match_runs`` inside ``shard_map``, the mode
    gathered beside the total) for the described 2x2 mesh, a shard of
    2^23 lineitem rows as q3 has on the four-chip cell (the build shard
    CUT to 2^12 rows, as above: the sort's compile time): the probe
    lowers per shard as on one chip, ONE probe-sized directory gather
    in the packed arm, ONE bitmap word in the bitmap's."""
    from jax import shard_map
    from trino_tpu.columnar import Batch
    from trino_tpu.ops import join as join_ops
    from trino_tpu.parallel import spmd
    P, AXIS = spmd.P, spmd.AXIS
    per_p, per_b = 1 << 23, 1 << 12
    mesh, probe, build, lanes, live = _q3_sides_on_a_2x2_mesh(topo)

    def f(pcols, pn, bcols, bn):
        d = jax.lax.axis_index(AXIS)
        pb, bb = Batch(pcols, pn[d]), Batch(bcols, bn[d])
        start, count, side = join_ops.match_runs(
            pb, bb, ["l_orderkey"], ["o_orderkey"])
        return (start, count, side.order, jax.lax.all_gather(
            join_ops.total_and_mode(count, side, pb), AXIS))

    text = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(spmd._col_specs(probe.columns, P(AXIS)), P(),
                  spmd._col_specs(build.columns, P(AXIS)), P()),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P()), check_vma=False)
    ).lower(lanes(probe.columns, per_p), live,
            lanes(build.columns, per_b), live).compile().as_text()
    assert "s64[4,6]" in text      # the one read: six entries a shard
    assert sorted(_gathers_by_arm(text, per_p)) == [
        (0, 0), (0, 0), (2, 1), (3, 0), (6, 1)]


# ---- ISSUE 36: q18's programs -------------------------------------------
@pytest.mark.parametrize("run,scatters", [(8, 0), (0, 2)])
def test_dense_aggregation_and_its_compaction_compile(
        one_chip, no_persistent_cache, run, scatters):
    """q18's IN-subquery, ``group by l_orderkey having sum(l_quantity) >
    300``, as the dense whole-table program makes it (ops/groupby.py
    dense_group_slots, the HAVING a mask over what it returns) and the
    counted compaction above it (ops/compact.py compact_batch into
    2^10: a bisection). Keys that ascend in runs of at most eight
    (lineitem's) are added up row by row: NO scatter and no loop, 2^20
    rows in, 2^20 out. Longer runs scatter into 2^25 slots, with sorted
    indices: two scatters (the s32 row count, the f64 sum: on the chip
    0.59 s and 9.3 s for 2^26 updates, PERF.md) and still NO sort —
    without the promise this compiler sorts the indices first."""
    from trino_tpu import BIGINT, DOUBLE, batch_from_pylist
    from trino_tpu.ops.compact import compact_batch
    from trino_tpu.ops.groupby import (AggInput, DenseKeys,
                                       dense_group_slots, dense_slots)
    cap = 1 << 20
    b = batch_from_pylist({"k": [1, 2], "q": [1.0, 2.0]},
                          {"k": BIGINT, "q": DOUBLE})

    def slots_and_having(b, base):
        slots, exists = dense_group_slots(
            b, ["k"], [AggInput("sum", "q", output="s")],
            DenseKeys(base, True, run))
        passed = exists & (jnp.asarray(slots.column("s").data) > 300.0)
        return slots, passed, jnp.sum(passed.astype(jnp.int64))

    args = (_as_structs(b, cap, one_chip), _struct((), jnp.int64, one_chip))
    text = jax.jit(slots_and_having).lower(*args).compile().as_text()
    assert " sort(" not in text and " while(" not in text
    assert len(re.findall(r" scatter\(", text)) == scatters
    slots = jax.tree.map(lambda a: _struct(a.shape, a.dtype, one_chip),
                         jax.eval_shape(slots_and_having, *args)[0])
    rows = slots.capacity
    assert rows == (cap if run else dense_slots(cap))
    text = jax.jit(lambda s, m: compact_batch(s, m, 1 << 10)).lower(
        slots, _struct((rows,), jnp.bool_, one_chip)
    ).compile().as_text()
    assert " sort(" not in text
    assert len(re.findall(r" while\(", text)) == 1


def test_a_float64_group_key_compiles(one_chip, no_persistent_cache):
    """q18 groups by o_totalprice, a DOUBLE: its equality lanes come
    from the float32 pair the chip's float64 is (ops/hashing.py
    ``_lanes_by_float32_pair``, chosen by the lowering's platform); the
    ``jnp.frexp`` every other platform takes is refused here (PR 23)."""
    from trino_tpu import BIGINT, DOUBLE, batch_from_pylist
    from trino_tpu.ops.groupby import AggInput, group_aggregate
    b = batch_from_pylist({"k": [1, 2], "p": [1.5, 2.5], "q": [1.0, 2.0]},
                          {"k": BIGINT, "p": DOUBLE, "q": DOUBLE})
    text = jax.jit(lambda b: group_aggregate(
        b, ["k", "p"], [AggInput("sum", "q", output="s")])).lower(
            _as_structs(b, 1 << 13, one_chip)).compile().as_text()
    assert "bitcast-convert" in text


def test_semi_join_program_compiles(one_chip, no_persistent_cache):
    """The mark of ``o_orderkey IN (...)``: 2^20 probe keys against a
    build side of 2^10 (what the counted HAVING leaves)."""
    from trino_tpu import BIGINT, batch_from_pylist
    from trino_tpu.exec.executor import semi_join_mark
    k = batch_from_pylist({"k": [1, 2]}, {"k": BIGINT})
    compiled = jax.jit(semi_join_mark).lower(
        _as_structs(k, 1 << 20, one_chip),
        _as_structs(k, 1 << 10, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def _q6_plan():
    from trino_tpu.plan.nodes import FilterNode
    from trino_tpu.runner import LocalQueryRunner
    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "benchmark", "traffic", "queries",
                           "q6.sql")) as f:
        node = LocalQueryRunner().plan_sql(f.read())
    while not isinstance(node, FilterNode):
        node, = node.sources
    return node


def test_q6_filter_with_literal_slots_compiles(one_chip,
                                               no_persistent_cache):
    """q6's Filter as its canonical program runs it (exec/literals.py):
    the year's end and the two discount bounds are slots of the
    program's literal vectors, folded on the host; what is left per
    row is compares against one element of a vector."""
    from trino_tpu import batch_from_pylist
    from trino_tpu.exec.expr import eval_predicate
    from trino_tpu.exec.literals import LITERAL_SLOTS, BoundBatch
    from trino_tpu.exec.progkey import canonicalize_nodes, named_jit
    node = _q6_plan()
    canon = canonicalize_nodes([node])
    assert {s.dtype for s in canon.slots} == {"int32", "float64"}
    schema = node.source.output_schema()
    rows = canon.binding(batch_from_pylist(
        {c: [1, 2] for c in schema}, schema)).rename_in(
            batch_from_pylist({c: [1, 2] for c in schema}, schema))
    lanes = _as_structs(rows.columns, Q1_CAPACITY, one_chip)
    args = BoundBatch(lanes, Q1_CAPACITY, {
        dt: _struct((LITERAL_SLOTS,), np.dtype(dt), one_chip)
        for dt in rows.literals}, rows.bound)
    pred = canon.nodes[0].predicate
    compiled = named_jit(lambda b: eval_predicate(pred, b), "chain",
                         canon.key).lower(args).compile()
    assert len(compiled.as_text()) < 100_000
    assert compiled.cost_analysis()["flops"] < 64 * Q1_CAPACITY


def test_scan_derive_program_compiles(one_chip, no_persistent_cache):
    """The derive of q6's pushed constraint (exec/scanderive.py) over
    sf1 lineitem's base lanes at 2^23: the mask of the bounds, the
    stable compaction at the same capacity by shifts: no sort and no
    gather (a gather of 2^23 indices a lane word held the chip for
    about 130 ms)."""
    from trino_tpu import DATE, DOUBLE, batch_from_pylist
    from trino_tpu.exec.scanderive import (bound_vectors, constraint_shape,
                                           make_derive_program)
    scan = _q6_plan().source
    shape, values = constraint_shape(scan.handle.constraint)
    keep = ("l_discount", "l_extendedprice", "l_shipdate")
    base = batch_from_pylist(
        {"l_discount": [0.1], "l_extendedprice": [1.0],
         "l_quantity": [1.0], "l_shipdate": [1]},
        {"l_discount": DOUBLE, "l_extendedprice": DOUBLE,
         "l_quantity": DOUBLE, "l_shipdate": DATE})
    bounds = {dt: _struct(np.shape(v), np.asarray(v).dtype, one_chip)
              for dt, v in bound_vectors(values).items()}
    compiled = jax.jit(make_derive_program(shape, keep)).lower(
        _as_structs(base, 1 << 23, one_chip), bounds).compile()
    text = compiled.as_text()
    assert " sort(" not in text and " gather(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
