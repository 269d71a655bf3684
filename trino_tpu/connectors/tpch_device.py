"""Device-side TPC-H generation: lineitem/orders lanes born in HBM.

Reference parity: plugin/trino-tpch/.../TpchRecordSet.java:43-51 —
the generator is split-addressable and scales by design. On a 1-core
host the numpy leg tops out around ~1M rows/s; at sf100 (600M lineitem
rows) host generation alone would dwarf the query. The counter-based
RNG (value = mix(seed, row_index)) is branch-free integer arithmetic —
exactly what the TPU's VPU eats — so the lanes are generated directly
on device, bit-identical to the numpy leg (tests/test_tpch_device.py
asserts exact equality).

Strings: dictionary-coded columns (returnflag, linestatus, shipmode,
shipinstruct, orderstatus, orderpriority) are device-generatable — the
code lane is integer math, the dictionary is static. Free-text comment
columns and per-row formatted keys (o_clerk) stay on the host path.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Batch, Column, StringDictionary
from ..config import capacity_for
from ..types import BIGINT, DATE, DOUBLE, INTEGER, VarcharType

from .tpch import (CURRENTDATE, INSTRUCTIONS, MODES, ORDER_DATE_SPAN,
                   PRIORITIES, STARTDATE, _SEED, table_rows,
                   _strings as _dict_col)
# _strings shares its StringDictionary cache across host and device
# generation — dictionary identity is static trace metadata, so sharing
# keeps one compiled pipeline per query instead of one per split
# (codes.astype(np.int32) on a jax array stays on device)

_C1 = jnp.uint64(0xBF58476D1CE4E5B9)
_C2 = jnp.uint64(0x94D049BB133111EB)
_GOLD = jnp.uint64(0x9E3779B97F4A7C15)


def _mix(x: jax.Array) -> jax.Array:
    x = x ^ (x >> jnp.uint64(30))
    x = x * _C1
    x = x ^ (x >> jnp.uint64(27))
    x = x * _C2
    x = x ^ (x >> jnp.uint64(31))
    return x


def _u64(seed: int, idx: jax.Array) -> jax.Array:
    return _mix(jnp.uint64(seed) * _GOLD + idx.astype(jnp.uint64))


def _randint(seed: int, idx: jax.Array, lo: int, hi: int) -> jax.Array:
    span = jnp.uint64(hi - lo + 1)
    return (lo + (_u64(seed, idx) % span).astype(jnp.int64))


def _order_key(i: jax.Array) -> jax.Array:
    return ((i >> 3) << 5) | (i & 7)


def _order_date(order_idx: jax.Array) -> jax.Array:
    return STARTDATE + _randint(_SEED["orders"] + 4, order_idx, 0,
                                ORDER_DATE_SPAN)


def _cust_key(order_idx: jax.Array, c_count: int) -> jax.Array:
    j = _randint(_SEED["orders"] + 3, order_idx, 1,
                 max(2 * c_count // 3, 1))
    return 3 * ((j - 1) // 2) + 1 + ((j - 1) % 2)


def _line_counts(order_idx: jax.Array) -> jax.Array:
    return _randint(_SEED["lineitem"] + 1, order_idx, 1, 7)


# n / 100.0 for n in 0..10, divided on the HOST. The chip has no
# 64-bit floats: XLA carries a float64 as a pair of float32 and divides
# approximately, so a discount DIVIDED on the device need not be the
# value the literal 0.05 becomes there, and q6's
# ``l_discount between 0.05 and 0.07`` would drop a whole discount
# class. Looked up from host doubles, a generated 0.05 and the literal
# 0.05 are the same double going through the same conversion. On the
# CPU both forms give the host generator's IEEE quotient.
_HUNDREDTHS = np.arange(0, 11) / 100.0


def _hundredths(n: jax.Array) -> jax.Array:
    return jnp.take(jnp.asarray(_HUNDREDTHS), n)


# Divisions by 100 take the divisor as an ARGUMENT where they run inside
# one compiled program (ShardGenerator): a compiler that sees the
# literal may multiply by its reciprocal, which lands on the other
# float neighbour for about a seventh of the values; a divisor it
# cannot see is divided by, as the eager per-split path (one primitive
# a dispatch) and the host generator do.

def _retailprice(partkey: jax.Array, hundred=100.0) -> jax.Array:
    pk = partkey.astype(jnp.int64)
    return (90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) / hundred


def _ps_suppkey(partkey: jax.Array, i: jax.Array,
                s_count: int) -> jax.Array:
    pk = partkey.astype(jnp.int64)
    s = jnp.int64(s_count)
    return (pk + i * (s // 4 + (pk - 1) // s)) % s + 1


# --------------------------------------------------------------------------
# per-table device column sets
# --------------------------------------------------------------------------

LINEITEM_DEVICE_COLS = {
    "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
    "l_quantity", "l_extendedprice", "l_discount", "l_tax",
    "l_shipdate", "l_commitdate", "l_receiptdate", "l_returnflag",
    "l_linestatus", "l_shipinstruct", "l_shipmode"}

ORDERS_DEVICE_COLS = {
    "o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
    "o_orderdate", "o_orderpriority", "o_shippriority"}


def device_columns(table: str) -> Optional[set]:
    if table == "lineitem":
        return LINEITEM_DEVICE_COLS
    if table == "orders":
        return ORDERS_DEVICE_COLS
    return None


# --------------------------------------------------------------------------
# lineitem
# --------------------------------------------------------------------------

def _line_grid(lo: int, hi: int):
    """(order_rep, line_no, live-compact index, total) for order
    indices (lo, hi] — static 7-wide grid compacted by a host count
    (two-phase capacity pattern; values depend only on
    (order_idx, linenumber) so compaction order matches numpy repeat)."""
    oi = jnp.arange(lo + 1, hi + 1, dtype=jnp.int64)
    counts = _line_counts(oi)
    total = int(jnp.sum(counts))
    o_grid = jnp.repeat(oi, 7)                     # static repeat
    ln_grid = jnp.tile(jnp.arange(1, 8, dtype=jnp.int64), hi - lo)
    live = ln_grid <= jnp.repeat(counts, 7)
    cap = capacity_for(max(total, 1), minimum=8)
    idx = jnp.nonzero(live, size=cap, fill_value=0)[0]
    return jnp.take(o_grid, idx), jnp.take(ln_grid, idx), total, cap


def lineitem_batch(lo: int, hi: int, sf: float,
                   columns: List[str]) -> Batch:
    """Device-generated lineitem rows for order indices (lo, hi]."""
    order_rep, line_no, total, _cap = _line_grid(lo, hi)
    out = _lineitem_columns(order_rep, line_no, sf, columns)
    return Batch({c: out[c] for c in columns}, total)


def _lineitem_columns(order_rep: jax.Array, line_no: jax.Array, sf: float,
                      columns, hundred=100.0) -> Dict[str, Column]:
    """The lanes of the lineitem rows (order index, line number): pure
    functions of the two, row by row."""
    S = _SEED["lineitem"]
    rid = order_rep * 8 + line_no
    p_count = table_rows("part", sf)
    s_count = table_rows("supplier", sf)
    need = set(columns)
    out: Dict[str, Column] = {}

    partkey = None
    if need & {"l_partkey", "l_suppkey", "l_extendedprice"}:
        partkey = _randint(S + 2, rid, 1, p_count)
    odate = None
    if need & {"l_shipdate", "l_commitdate", "l_receiptdate",
               "l_returnflag", "l_linestatus"}:
        odate = _order_date(order_rep)
    shipdate = None
    if need & {"l_shipdate", "l_receiptdate", "l_returnflag",
               "l_linestatus"}:
        shipdate = odate + _randint(S + 7, rid, 1, 121)

    if "l_orderkey" in need:
        out["l_orderkey"] = Column(BIGINT, _order_key(order_rep), None)
    if "l_partkey" in need:
        out["l_partkey"] = Column(BIGINT, partkey, None)
    if "l_suppkey" in need:
        out["l_suppkey"] = Column(
            BIGINT, _ps_suppkey(partkey, _randint(S + 3, rid, 0, 3),
                                s_count), None)
    if "l_linenumber" in need:
        out["l_linenumber"] = Column(INTEGER,
                                     line_no.astype(jnp.int32), None)
    if need & {"l_quantity", "l_extendedprice"}:
        qty = _randint(S + 4, rid, 1, 50).astype(jnp.float64)
        if "l_quantity" in need:
            out["l_quantity"] = Column(DOUBLE, qty, None)
        if "l_extendedprice" in need:
            out["l_extendedprice"] = Column(
                DOUBLE, qty * _retailprice(partkey, hundred), None)
    if "l_discount" in need:
        out["l_discount"] = Column(
            DOUBLE, _hundredths(_randint(S + 5, rid, 0, 10)), None)
    if "l_tax" in need:
        out["l_tax"] = Column(
            DOUBLE, _hundredths(_randint(S + 6, rid, 0, 8)), None)
    if "l_shipdate" in need:
        out["l_shipdate"] = Column(DATE, shipdate.astype(jnp.int32),
                                   None)
    if "l_commitdate" in need:
        out["l_commitdate"] = Column(
            DATE, (odate + _randint(S + 8, rid, 30, 90))
            .astype(jnp.int32), None)
    if "l_receiptdate" in need or "l_returnflag" in need:
        # shipdate is always materialized here: both triggering columns
        # are in the set that forces it above
        receipt = shipdate + _randint(S + 9, rid, 1, 30)
        if "l_receiptdate" in need:
            out["l_receiptdate"] = Column(DATE,
                                          receipt.astype(jnp.int32),
                                          None)
        if "l_returnflag" in need:
            returned = receipt <= CURRENTDATE
            ra = (_u64(S + 20, rid) % jnp.uint64(2)).astype(jnp.int64)
            flag = jnp.where(returned, ra, 2).astype(jnp.int32)
            out["l_returnflag"] = _dict_col(["R", "A", "N"], flag,
                                            VarcharType(1))
    if "l_linestatus" in need:
        st = (shipdate > CURRENTDATE).astype(jnp.int32)
        out["l_linestatus"] = _dict_col(["F", "O"], st,
                                        VarcharType(1))
    if "l_shipinstruct" in need:
        si = _randint(S + 21, rid, 0, 3).astype(jnp.int32)
        out["l_shipinstruct"] = _dict_col(INSTRUCTIONS, si,
                                          VarcharType(25))
    if "l_shipmode" in need:
        sm = _randint(S + 22, rid, 0, 6).astype(jnp.int32)
        out["l_shipmode"] = _dict_col(MODES, sm, VarcharType(10))
    return out


# --------------------------------------------------------------------------
# orders
# --------------------------------------------------------------------------

def orders_batch(lo: int, hi: int, sf: float,
                 columns: List[str]) -> Batch:
    """Device-generated orders rows for order indices (lo, hi]."""
    n = hi - lo
    cap = capacity_for(max(n, 1), minimum=8)
    out = _orders_columns(jnp.arange(lo + 1, hi + 1, dtype=jnp.int64), sf,
                          columns, pad=cap - n)
    return Batch({c: out[c] for c in columns}, n)


def _orders_columns(idx: jax.Array, sf: float, columns,
                    pad: int = 0, hundred=100.0) -> Dict[str, Column]:
    """The lanes of the orders rows with order indices ``idx``, each
    padded by ``pad`` rows: pure functions of the index, row by row."""
    S = _SEED["orders"]
    n = int(idx.shape[0])

    def _padded(a):
        return jnp.pad(a, (0, pad)) if pad else a

    need = set(columns)
    out: Dict[str, Column] = {}
    if "o_orderkey" in need:
        out["o_orderkey"] = Column(BIGINT, _padded(_order_key(idx)),
                                   None)
    if "o_custkey" in need:
        out["o_custkey"] = Column(
            BIGINT, _padded(_cust_key(idx, table_rows("customer", sf))),
            None)
    if need & {"o_orderstatus", "o_totalprice"}:
        # aggregates of this order's generated lineitems, on the static
        # 7-wide grid (no compaction needed: dead cells are masked)
        SL = _SEED["lineitem"]
        counts = _line_counts(idx)
        o_grid = jnp.repeat(idx, 7)
        ln_grid = jnp.tile(jnp.arange(1, 8, dtype=jnp.int64), n)
        live = ln_grid <= jnp.repeat(counts, 7)
        rid = o_grid * 8 + ln_grid
        pk = _randint(SL + 2, rid, 1, table_rows("part", sf))
        qty = _randint(SL + 4, rid, 1, 50).astype(jnp.float64)
        disc = _randint(SL + 5, rid, 0, 10) / hundred
        tax = _randint(SL + 6, rid, 0, 8) / hundred
        price = qty * _retailprice(pk, hundred) * (1.0 + tax) * (1.0 - disc)
        price = jnp.where(live, price, 0.0).reshape(n, 7)
        # sequential left-to-right adds: bit-identical to the host
        # leg's np.add.at accumulation (XLA's tree reduction rounds
        # differently in the last ULP)
        total = price[:, 0]
        for k in range(1, 7):
            total = total + price[:, k]
        # rint(x*100)/100 — numpy's around algorithm with a TRUE
        # division (jnp.round multiplies by the 0.01 reciprocal, which
        # lands on the other float neighbor for ~14% of values)
        total = jnp.divide(jnp.rint(total * 100.0), hundred)
        if "o_totalprice" in need:
            out["o_totalprice"] = Column(DOUBLE, _padded(total), None)
        if "o_orderstatus" in need:
            odate_grid = _order_date(o_grid)
            ship = odate_grid + _randint(SL + 7, rid, 1, 121)
            shipped = jnp.where(live, (ship <= CURRENTDATE)
                                .astype(jnp.int64), 0).reshape(n, 7)
            n_shipped = jnp.sum(shipped, axis=1)
            status = jnp.where(
                n_shipped == 0, 0,
                jnp.where(n_shipped == counts, 1, 2)).astype(jnp.int32)
            out["o_orderstatus"] = _dict_col(
                ["O", "F", "P"], _padded(status), VarcharType(1))
    if "o_orderdate" in need:
        out["o_orderdate"] = Column(
            DATE, _padded(_order_date(idx).astype(jnp.int32)), None)
    if "o_orderpriority" in need:
        p = _randint(S + 5, idx, 0, 4).astype(jnp.int32)
        out["o_orderpriority"] = _dict_col(PRIORITIES, _padded(p),
                                           VarcharType(15))
    if "o_shippriority" in need:
        out["o_shippriority"] = Column(
            INTEGER, jnp.zeros((n + pad,), jnp.int32), None)
    return out


# --------------------------------------------------------------------------
# device-side pushdown enforcement (the filter_batch_host analog)
# --------------------------------------------------------------------------

def constraint_mask(batch: Batch, constraint) -> jax.Array:
    """Rows of ``batch`` (live or not) that an accepted TupleDomain
    keeps. Dictionary columns evaluate the domain once per dictionary
    VALUE host-side (a tiny table), then gather the per-code verdicts;
    numeric columns translate ranges to jnp comparisons. Generator
    columns carry no NULLs."""
    mask = jnp.ones((batch.capacity,), bool)
    for col, dom in constraint.domains:
        if col not in batch.columns or dom.is_all:
            continue
        c = batch.columns[col]
        if c.dictionary is not None:
            vals = c.dictionary.values.astype(str)
            tbl = dom.mask_for(
                np.arange(len(vals)), None,
                lambda cds, v=vals: v[np.clip(
                    cds.astype(np.int64), 0, len(v) - 1)])
            m = jnp.take(jnp.asarray(tbl),
                         jnp.asarray(c.data).astype(jnp.int32),
                         mode="clip")
        else:
            data = jnp.asarray(c.data)
            m = jnp.zeros(data.shape, bool)
            for r in dom.ranges:
                rm = jnp.ones(data.shape, bool)
                if r.low is not None:
                    rm = rm & ((data >= r.low) if r.low_inclusive
                               else (data > r.low))
                if r.high is not None:
                    rm = rm & ((data <= r.high) if r.high_inclusive
                               else (data < r.high))
                m = m | rm
        mask = mask & m
    return mask


def device_filter(batch: Batch, constraint, limit: Optional[int]) -> Batch:
    """Apply an accepted TupleDomain + limit to a device-resident batch
    without a host round-trip."""
    from ..ops import compact
    if constraint is not None and constraint.is_none:
        return Batch(batch.columns, 0)
    if constraint is not None and not constraint.is_all():
        batch = compact.filter_batch(
            batch, batch.row_valid() & constraint_mask(batch, constraint))
    if limit is not None:
        from ..ops.compact import limit_batch
        batch = limit_batch(batch, limit)
    return batch


# --------------------------------------------------------------------------
# a whole shard in one program (the mesh executor's scan fill)
# --------------------------------------------------------------------------

class ShardGenerator:
    """A table's rows for ANY set of order indices as two traceable
    functions, so that the mesh executor fills a shard with two
    programs (exec/executor.py ``_fill_sharded``) where the per-split
    generators above are one dispatch, and on a cold cache one compile,
    per primitive per split shape per chip:

    - ``rows(oi, n, hundred)``: how many rows the first ``n`` order
      indices of ``oi`` give under the pushed-down constraint (the
      capacity is chosen from it on the host); ``hundred`` is 100.0 as
      an operand (see ``_retailprice``);
    - ``batch(oi, n, hundred, cap)``: those rows' lanes at capacity ``cap``,
      compacted ONCE (generated cells that are no row, and rows the
      constraint drops, never reach a lane).

    Row order is the per-split generators': ascending order index, then
    line number. ``key`` names everything the two close over."""

    def __init__(self, table: str, sf: float, columns, constraint):
        self.table, self.sf = table, sf
        self.columns = list(columns)
        self.constraint = (None if constraint is None or constraint.is_all()
                           else constraint)
        self.wanted = ([] if self.constraint is None else
                       [c for c, _ in self.constraint.domains])
        self.key = ("tpch", table, sf, tuple(self.columns), self.constraint)

    def order_indices(self, splits) -> np.ndarray:
        """The 1-based order indices (row indices, for orders) the
        splits cover, in split order."""
        units = table_rows("orders", self.sf)
        return np.concatenate(
            [np.arange(s.part * units // s.part_count + 1,
                       (s.part + 1) * units // s.part_count + 1,
                       dtype=np.int64) for s in splits]
            or [np.zeros(0, np.int64)])

    def _live(self, oi: jax.Array, n: jax.Array, hundred):
        """(keep mask, order index lane, line number lane) over the
        generated cells."""
        real = jnp.arange(oi.shape[0], dtype=jnp.int64) < n
        if self.table == "orders":
            keep, order, line = real, oi, None
            cols = (_orders_columns(oi, self.sf, self.wanted,
                                    hundred=hundred)
                    if self.wanted else {})
        else:
            counts = jnp.where(real, _line_counts(oi), 0)
            order = jnp.repeat(oi, 7)                  # static repeat
            line = jnp.tile(jnp.arange(1, 8, dtype=jnp.int64),
                            oi.shape[0])
            keep = line <= jnp.repeat(counts, 7)
            cols = (_lineitem_columns(order, line, self.sf, self.wanted,
                                      hundred)
                    if self.wanted else {})
        if self.constraint is not None:
            if self.constraint.is_none:
                keep = jnp.zeros(keep.shape, bool)
            else:
                keep = keep & constraint_mask(
                    Batch(cols, keep.shape[0]), self.constraint)
        return keep, order, line

    def rows(self, oi: jax.Array, n: jax.Array, hundred) -> jax.Array:
        return jnp.sum(self._live(oi, n, hundred)[0].astype(jnp.int64))

    def batch(self, oi: jax.Array, n: jax.Array, hundred,
              cap: int) -> Batch:
        keep, order, line = self._live(oi, n, hundred)
        at = jnp.nonzero(keep, size=cap, fill_value=0)[0]
        order = jnp.take(order, at)
        if self.table == "orders":
            out = _orders_columns(order, self.sf, self.columns,
                                  hundred=hundred)
        else:
            out = _lineitem_columns(order, jnp.take(line, at), self.sf,
                                    self.columns, hundred)
        return Batch({c: out[c] for c in self.columns},
                     jnp.sum(keep.astype(jnp.int64)))


def shard_generator(table: str, sf: float, columns, constraint,
                    limit) -> Optional[ShardGenerator]:
    """The shard generator of a scan, or None where the per-split path
    has to serve it: a table or a column without a device generator, a
    pushed-down limit."""
    allowed = device_columns(table)
    if allowed is None or limit is not None:
        return None
    gen = ShardGenerator(table, sf, columns, constraint)
    if not set(gen.columns) | set(gen.wanted) <= allowed:
        return None
    return gen
