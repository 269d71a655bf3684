"""Pallas grouped-aggregation kernel (ops/pallas_groupby.py).

Runs in interpreter mode on the CPU suite (TRINO_TPU_PALLAS=interpret);
on a real TPU the same kernel compiles via Mosaic. Validates the
exact-sum digit decomposition and the engine integration end-to-end
against the XLA masked-reduction path.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from trino_tpu.ops.pallas_groupby import G_PAD, grouped_sums


def test_grouped_sums_exact():
    rng = np.random.default_rng(1)
    cap, n = 8192, 7000
    gid = rng.integers(0, 11, cap).astype(np.int32)
    gid[n:] = G_PAD
    money = np.round(rng.uniform(900, 105000, cap), 2)
    small = rng.integers(0, 50, cap).astype(np.float64)
    signed = rng.normal(scale=1e9, size=cap)
    live = np.arange(cap) < n
    lanes = [np.where(live, x, 0.0) for x in (money, small, signed)]
    lanes.append(live.astype(np.float64))
    out = grouped_sums(jnp.asarray(gid),
                       [jnp.asarray(x) for x in lanes], 11,
                       interpret=True)
    for g in range(11):
        m = (gid[:n] == g)
        assert abs(float(out[0][g]) - money[:n][m].sum()) \
            <= 1e-8 * abs(money[:n][m].sum())
        assert float(out[1][g]) == small[:n][m].sum()
        assert abs(float(out[2][g]) - signed[:n][m].sum()) \
            <= 1e-8 * abs(signed[:n][m].sum())
        assert float(out[3][g]) == m.sum()


def test_grouped_sums_empty_and_zero_groups():
    cap = 512
    gid = np.full(cap, G_PAD, np.int32)   # everything dead
    out = grouped_sums(jnp.asarray(gid),
                       [jnp.zeros(cap)], 4, interpret=True)
    assert np.allclose(np.asarray(out[0]), 0.0)


def test_sql_q1_shape_matches_xla_path(monkeypatch):
    """The q1 aggregation (filter + multi-key GROUP BY + sums/avg/
    count) through the engine with the pallas path forced on must
    match the XLA masked-reduction path exactly enough for SQL."""
    from trino_tpu.runner import LocalQueryRunner
    sql = ("SELECT l_returnflag, l_linestatus, sum(l_quantity), "
           "sum(l_extendedprice), "
           "sum(l_extendedprice * (1 - l_discount)), "
           "avg(l_quantity), count(*) "
           "FROM lineitem WHERE l_shipdate <= DATE '1998-09-02' "
           "GROUP BY l_returnflag, l_linestatus "
           "ORDER BY l_returnflag, l_linestatus")
    monkeypatch.setenv("TRINO_TPU_PALLAS", "0")
    want = LocalQueryRunner().execute(sql).rows
    monkeypatch.setenv("TRINO_TPU_PALLAS", "interpret")
    got = LocalQueryRunner().execute(sql).rows
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g[:2] == w[:2]
        for a, b in zip(g[2:], w[2:]):
            assert a == pytest.approx(b, rel=1e-9)


def test_sql_filtered_count_matches(monkeypatch):
    from trino_tpu.runner import LocalQueryRunner
    sql = ("SELECT l_linestatus, "
           "count(*) FILTER (WHERE l_quantity > 25), "
           "sum(l_extendedprice) FILTER (WHERE l_discount > 0.05), "
           "min(l_shipdate), max(l_quantity) "
           "FROM lineitem GROUP BY l_linestatus ORDER BY 1")
    monkeypatch.setenv("TRINO_TPU_PALLAS", "0")
    want = LocalQueryRunner().execute(sql).rows
    monkeypatch.setenv("TRINO_TPU_PALLAS", "interpret")
    got = LocalQueryRunner().execute(sql).rows
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g[0] == w[0] and g[1] == w[1]
        assert g[2] == pytest.approx(w[2], rel=1e-9)
        assert g[3] == w[3] and g[4] == w[4]


def test_tpu_backend_never_falls_back_silently(monkeypatch):
    """On the tpu backend the kernel IS the path: mode() says "tpu"
    without probing, and a kernel the chip's compiler refuses fails the
    aggregation — it must never quietly select the XLA reductions (the
    old probe returned "" here and every test still passed)."""
    import jax
    from trino_tpu import DOUBLE, VARCHAR, batch_from_pylist
    from trino_tpu.ops import pallas_groupby as pg
    from trino_tpu.ops.groupby import AggInput, group_aggregate

    class MosaicRefused(Exception):
        pass

    def refuse(*_a, **_kw):
        raise MosaicRefused("Mosaic failed to compile TPU kernel")

    monkeypatch.delenv("TRINO_TPU_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pg, "_grouped_sums_impl", refuse)
    assert pg.mode() == "tpu"
    # the explicit switches still win over the backend
    monkeypatch.setenv("TRINO_TPU_PALLAS", "0")
    assert pg.mode() == ""
    monkeypatch.setenv("TRINO_TPU_PALLAS", "interpret")
    assert pg.mode() == "interpret"
    monkeypatch.delenv("TRINO_TPU_PALLAS")

    b = batch_from_pylist({"k": ["a", "b", "a"], "v": [1.0, 2.0, 3.0]},
                          {"k": VARCHAR, "v": DOUBLE})
    with pytest.raises(MosaicRefused):
        group_aggregate(b, ["k"], [AggInput("sum", "v", output="s")])
