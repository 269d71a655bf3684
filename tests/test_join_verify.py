"""Join hash-collision re-verification (VERDICT weak #9).

The equality lane of ops/join.py is exact only for a single integer-like
key; multi-column and float keys are hash-combined. The executor appends
real key-equality conjuncts for those (executor.join_verify_filter —
reference: JoinProbe verifies positions by actual equality, never by
hash). These tests inject collisions by weakening the hash combiner to
2 bits and assert results stay correct.
"""

import jax.numpy as jnp
import pytest

from trino_tpu.runner import LocalQueryRunner


@pytest.fixture()
def weak_hash(monkeypatch):
    """Collapse combined hashes to 4 distinct values — multi-key joins
    then see constant collisions unless re-verification kicks in."""
    from trino_tpu.ops import hashing, join as join_ops

    def weak(hashes):
        acc = jnp.zeros_like(hashes[0])
        for h in hashes:
            acc = acc + h
        return acc % jnp.uint64(4)

    # join key lanes use ops.join.fold_hashes (captured at import)
    monkeypatch.setattr(join_ops, "fold_hashes", weak)
    return weak


def _runner():
    return LocalQueryRunner()


def test_multikey_inner_join_collisions(weak_hash):
    r = _runner()
    res = r.execute(
        "SELECT a.x, a.y, b.v FROM "
        "(VALUES (1, 10, 'l1'), (2, 20, 'l2'), (3, 30, 'l3')) a(x, y, s) "
        "JOIN (VALUES (1, 10, 'r1'), (2, 99, 'r2'), (3, 30, 'r3')) "
        "b(x2, y2, v) ON a.x = b.x2 AND a.y = b.y2 ORDER BY a.x")
    assert res.rows == [[1, 10, "r1"], [3, 30, "r3"]]


def test_multikey_left_join_collisions(weak_hash):
    r = _runner()
    res = r.execute(
        "SELECT a.x, b.v FROM "
        "(VALUES (1, 10), (2, 20)) a(x, y) "
        "LEFT JOIN (VALUES (1, 10, 'r1'), (2, 99, 'r2')) b(x2, y2, v) "
        "ON a.x = b.x2 AND a.y = b.y2 ORDER BY a.x")
    assert res.rows == [[1, "r1"], [2, None]]


def test_multikey_full_join_collisions(weak_hash):
    r = _runner()
    res = r.execute(
        "SELECT a.x, b.x2 FROM "
        "(VALUES (1, 10), (2, 20)) a(x, y) "
        "FULL JOIN (VALUES (1, 10), (2, 99)) b(x2, y2) "
        "ON a.x = b.x2 AND a.y = b.y2 ORDER BY a.x, b.x2")
    key = lambda row: tuple((v is None, v or 0) for v in row)
    assert sorted(res.rows, key=key) == [[1, 1], [2, None], [None, 2]]


def test_multikey_semi_join_collisions(weak_hash):
    r = _runner()
    res = r.execute(
        "SELECT x FROM (VALUES (1, 10), (2, 20), (3, 30)) t(x, y) "
        "WHERE EXISTS (SELECT 1 FROM (VALUES (1, 10), (3, 99)) u(a, b) "
        "WHERE u.a = t.x AND u.b = t.y) ORDER BY x")
    assert res.rows == [[1]]


def test_float_single_key_join(weak_hash):
    r = _runner()
    res = r.execute(
        "SELECT a.x, b.v FROM (VALUES (1.5), (2.5)) a(x) "
        "JOIN (VALUES (CAST(1.5 AS double), 'm'), "
        "(CAST(9.5 AS double), 'n')) b(x2, v) "
        "ON a.x = CAST(b.x2 AS decimal(2,1)) ORDER BY a.x")
    assert len(res.rows) == 1 and res.rows[0][1] == "m"


@pytest.mark.slow
def test_distributed_partitioned_multikey(weak_hash):
    dist = LocalQueryRunner(distributed=True, n_devices=8)
    dist.execute("SET SESSION join_distribution_type = 'PARTITIONED'")
    loc = _runner()
    q = ("SELECT count(*) FROM lineitem l JOIN lineitem r "
         "ON l.l_orderkey = r.l_orderkey "
         "AND l.l_linenumber = r.l_linenumber "
         "WHERE l.l_quantity > 49")
    assert dist.execute(q).rows == loc.execute(q).rows
