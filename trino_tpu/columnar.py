"""Columnar data model: Column / Batch — the TPU-native Page/Block.

Reference parity: core/trino-spi/src/main/java/io/trino/spi/Page.java:33-358
and spi/block/* (70 files). Redesigned for XLA rather than translated:

- A ``Column`` is a struct-of-arrays: a dense device value lane (``data``),
  an optional validity lane (``valid``; None means all-valid — the analog of
  Block.mayHaveNull()==false), and for string types a host-side deduplicated
  ``dictionary`` (DictionaryBlock made primary, SURVEY.md §7.1).
- A ``Batch`` is a named tuple of Columns plus a row count. Physical array
  length ("capacity") is a power-of-two bucket >= the logical ``num_rows``;
  rows past num_rows are garbage and every kernel masks them with
  ``iota < num_rows``. This is how data-dependent cardinalities (filters,
  joins) keep static shapes for XLA without a recompile per row-count.
- LazyBlock's deferred-load role (spi/block/LazyBlock.java) is played by
  host-resident numpy until a kernel first touches a column, at which point
  jnp.asarray uploads it to HBM.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import config  # noqa: F401  (enables x64 before any jnp use)
from .config import capacity_for
from .types import (BOOLEAN, DOUBLE, BIGINT, DecimalType, Type, VarcharType,
                    CharType, is_string)

ArrayLike = Union[jax.Array, np.ndarray]


class StringDictionary:
    """Host-side deduplicated string pool backing a dictionary column.

    Codes are int32 indices into ``values``. The dictionary is immutable;
    merges produce a new dictionary plus a remap array usable as a device
    gather (reference analog: DictionaryBlock id remapping,
    spi/block/DictionaryBlock.java).

    Equality/hash are CONTENT-based (order-sensitive, via a cached
    fingerprint): the dictionary rides in the Column pytree aux, so
    jax's trace-cache treedef comparison uses ``__eq__`` — and any
    trace constant derived from a dictionary (merge remaps, per-entry
    predicate masks) is a pure function of the ordered value list.
    Content equality therefore means "same compiled program", which is
    what lets an AOT-fabricated dictionary (exec/aot.py, rebuilt from
    a hot-shape payload) land the live query on a compiled-program HIT
    instead of an identity-mismatch retrace.
    """

    __slots__ = ("values", "_index", "_fp")

    def __init__(self, values: np.ndarray, _index: Optional[dict] = None):
        self.values = np.asarray(values, dtype=object)
        self._index = _index
        self._fp: Optional[tuple] = None

    @staticmethod
    def from_strings(strings: Sequence[Optional[str]]):
        """Build (dictionary, codes) from raw strings; None -> code 0."""
        uniq: Dict[str, int] = {}
        codes = np.empty(len(strings), dtype=np.int32)
        for i, s in enumerate(strings):
            if s is None:
                codes[i] = 0
                continue
            c = uniq.get(s)
            if c is None:
                c = uniq.setdefault(s, len(uniq))
            codes[i] = c
        if not uniq:
            uniq[""] = 0
        vals = np.empty(len(uniq), dtype=object)
        for s, c in uniq.items():
            vals[c] = s
        return StringDictionary(vals, uniq), codes

    def __len__(self) -> int:
        return len(self.values)

    @property
    def index(self) -> dict:
        if self._index is None:
            self._index = {s: i for i, s in enumerate(self.values)}
        return self._index

    def code_of(self, s: str) -> int:
        """Code for s, or -1 if absent (no row can equal it)."""
        return self.index.get(s, -1)

    def rank_codes(self) -> np.ndarray:
        """rank[code] = collation rank of values[code]; for ORDER BY."""
        order = np.argsort(self.values.astype(str), kind="stable")
        ranks = np.empty(len(self.values), dtype=np.int32)
        ranks[order] = np.arange(len(self.values), dtype=np.int32)
        return ranks

    @property
    def fingerprint(self) -> tuple:
        """(length, blake2b-128 of the ordered value list) — computed
        once and cached. Order-sensitive on purpose: codes index
        ``values``, so two pools with the same set but different order
        are NOT interchangeable."""
        if self._fp is None:
            import hashlib
            h = hashlib.blake2b(digest_size=16)
            for v in self.values:
                if v is None:
                    h.update(b"\xff\x00\x00\x00\x00")
                else:
                    b = str(v).encode("utf-8", "surrogatepass")
                    h.update(len(b).to_bytes(4, "little"))
                    h.update(b)
            self._fp = (len(self.values), h.digest())
        return self._fp

    def __eq__(self, other):
        if other is self:
            return True
        if not isinstance(other, StringDictionary):
            return NotImplemented
        return self.fingerprint == other.fingerprint

    def __ne__(self, other):
        eq = self.__eq__(other)
        return eq if eq is NotImplemented else not eq

    def __hash__(self) -> int:
        return hash(self.fingerprint)

    def merge(self, other: "StringDictionary"):
        """Unify with other; returns (merged, remap_self, remap_other)."""
        if other is self:
            n = len(self.values)
            ident = np.arange(n, dtype=np.int32)
            return self, ident, ident
        idx = dict(self.index)
        vals: List[str] = list(self.values)
        remap_other = np.empty(len(other.values), dtype=np.int32)
        for i, s in enumerate(other.values):
            c = idx.get(s)
            if c is None:
                c = len(vals)
                idx[s] = c
                vals.append(s)
        for i, s in enumerate(other.values):
            remap_other[i] = idx[s]
        merged = StringDictionary(np.asarray(vals, dtype=object), idx)
        remap_self = np.arange(len(self.values), dtype=np.int32)
        return merged, remap_self, remap_other


@dataclass(frozen=True)
class Column:
    """One SQL column: value lane + validity lane (+ dictionary, + hi lane).

    ``data`` rows beyond the owning Batch's num_rows are garbage.
    ``valid`` is None when every (live) row is non-null.
    ``data2`` is the high int64 lane for DECIMAL(p>18) Int128 emulation.

    ARRAY columns (spi/block/ArrayBlock.java redesigned as
    struct-of-arrays): ``data`` is the per-row START offset into the
    flat ``elements`` column, ``data2`` the per-row LENGTH, and
    ``elements`` holds every element value (its own Column, possibly
    longer than the row capacity). Row gathers move only the
    offset/length lanes; ``elements`` is shared untouched.

    MAP columns (spi/block/MapBlock.java): same offsets/length lanes;
    ``elements`` is the flat KEY column and ``elements2`` the flat VALUE
    column, entry-aligned (key i pairs with value i).

    ROW columns (spi/block/RowBlock.java): ``children`` is one
    row-aligned Column per field; ``data`` is a dummy int8 lane that
    carries the capacity.
    """

    type: Type
    data: ArrayLike
    valid: Optional[ArrayLike] = None
    dictionary: Optional[StringDictionary] = None
    data2: Optional[ArrayLike] = None
    elements: Optional["Column"] = None
    elements2: Optional["Column"] = None
    children: Optional[Tuple["Column", ...]] = None

    def __post_init__(self):
        if is_string(self.type) and self.dictionary is None:
            raise ValueError(f"string column of type {self.type} needs a "
                             "dictionary")

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def on_device(self) -> "Column":
        d = jnp.asarray(self.data)
        v = None if self.valid is None else jnp.asarray(self.valid)
        d2 = None if self.data2 is None else jnp.asarray(self.data2)
        return replace(self, data=d, valid=v, data2=d2)

    def gather(self, indices: ArrayLike, fill_invalid: Optional[ArrayLike]
               = None) -> "Column":
        """Row gather; optionally mark gathered rows invalid where
        ``fill_invalid`` is True (used for outer-join null padding)."""
        data = jnp.take(jnp.asarray(self.data), indices, axis=0,
                        mode="clip")
        valid = (None if self.valid is None
                 else jnp.take(jnp.asarray(self.valid), indices, axis=0,
                               mode="clip"))
        if fill_invalid is not None:
            base = jnp.ones_like(indices, dtype=bool) if valid is None \
                else valid
            valid = base & ~fill_invalid
        data2 = (None if self.data2 is None
                 else jnp.take(jnp.asarray(self.data2), indices, axis=0,
                               mode="clip"))
        children = (None if self.children is None
                    else tuple(c.gather(indices, fill_invalid)
                               for c in self.children))
        # elements are row-independent (offsets were gathered) — shared
        return replace(self, data=data, valid=valid, data2=data2,
                       children=children)

    def valid_mask(self, n: Optional[int] = None) -> jax.Array:
        cap = self.capacity if n is None else n
        if self.valid is None:
            return jnp.ones((cap,), dtype=bool)
        return jnp.asarray(self.valid)[:cap]

    def with_dictionary(self, dictionary: StringDictionary,
                        remap: np.ndarray) -> "Column":
        """Rewrite codes through remap into a merged dictionary."""
        codes = jnp.take(jnp.asarray(remap), jnp.asarray(self.data),
                         axis=0, mode="clip")
        return replace(self, data=codes, dictionary=dictionary)


def hi_lane_or_fill(col: "Column"):
    """``col.data2`` as a jnp lane, synthesized when absent: Int128
    decimal columns sign-extend (a negative lo zero-filled would be off
    by 2^64); every other data2 carrier (timestamptz offset, varchar
    length lane) fills with zeros. The single source of truth for
    concat sites merging mixed-representation parts."""
    import jax.numpy as jnp
    from .types import DecimalType
    if col.data2 is not None:
        return jnp.asarray(col.data2)
    if isinstance(col.type, DecimalType):
        return jnp.asarray(col.data).astype(jnp.int64) >> 63
    return jnp.zeros((col.capacity,), jnp.int64)


def _to_lane(values, typ: Type):
    """numpy-ify a python sequence for a non-string column; returns
    (data, valid|None, data2|None). ``data2`` is the Int128 high lane,
    present only for DECIMAL(p>18)."""
    dt = typ.np_dtype
    n = len(values)
    data = np.zeros(n, dtype=dt)
    valid = np.ones(n, dtype=bool)
    any_null = False
    long_decimal = isinstance(typ, DecimalType) and not typ.is_short
    is_tz = str(typ.name).endswith("with time zone")
    data2 = (np.zeros(n, dtype=np.int64)
             if long_decimal or is_tz else None)
    import datetime as _dt
    for i, v in enumerate(values):
        if v is None:
            valid[i] = False
            any_null = True
        elif is_tz:
            if isinstance(v, tuple):          # (utc_millis, offset_min)
                data[i], data2[i] = v
            elif isinstance(v, _dt.datetime):
                off = v.utcoffset()
                data2[i] = (0 if off is None
                            else int(off.total_seconds() // 60))
                naive = v.replace(tzinfo=None)
                data[i] = int((naive - _dt.datetime(1970, 1, 1))
                              .total_seconds() * 1000) \
                    - data2[i] * 60000
            else:
                data[i] = int(v)
        elif isinstance(v, _dt.datetime):
            data[i] = int((v - _dt.datetime(1970, 1, 1))
                          .total_seconds() * 1000)
        elif isinstance(v, _dt.date):
            data[i] = v.toordinal() - 719163  # 1970-01-01
        elif isinstance(typ, DecimalType):
            if isinstance(v, int):
                q = v * (10 ** typ.scale)
            else:
                # exact decimal scaling with HALF_UP (Trino rounding,
                # reference: spi/type/Decimals.java) — going through
                # binary float multiply would be off-by-one near .5
                import decimal
                # prec=80: the default 28-digit context silently rounds
                # DECIMAL(38) magnitudes during scaleb/multiply
                ctx = decimal.Context(prec=80)
                q = int(decimal.Decimal(str(v)).scaleb(typ.scale, ctx)
                        .to_integral_value(rounding=decimal.ROUND_HALF_UP))
            if long_decimal:
                # two's-complement split: lo = unsigned low 64 bits
                # (stored in an int64 lane), hi carries the sign
                lo = q & ((1 << 64) - 1)
                data[i] = lo - (1 << 64) if lo >= (1 << 63) else lo
                data2[i] = q >> 64
            else:
                data[i] = q
        elif typ is BOOLEAN or typ.name == "boolean":
            data[i] = bool(v)
        else:
            data[i] = v
    return data, (valid if any_null else None), data2


def column_from_pylist(values: Sequence, typ: Type) -> Column:
    """Build a host Column from python values (tests / VALUES literals)."""
    from .types import ArrayType, MapType, RowType
    if isinstance(typ, ArrayType):
        valid = np.asarray([v is not None for v in values], dtype=bool)
        lens = np.asarray([len(v) if v is not None else 0
                           for v in values], dtype=np.int64)
        offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int64)
        flat: List = []
        for v in values:
            if v is not None:
                flat.extend(v)
        elements = column_from_pylist(flat or [None], typ.element)
        return Column(typ, offs, None if valid.all() else valid, None,
                      lens, elements)
    if isinstance(typ, MapType):
        valid = np.asarray([v is not None for v in values], dtype=bool)
        lens = np.asarray([len(v) if v is not None else 0
                           for v in values], dtype=np.int64)
        offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int64)
        ks: List = []
        vs: List = []
        for v in values:
            if v is not None:
                for k, val in v.items():
                    ks.append(k)
                    vs.append(val)
        keys = column_from_pylist(ks or [None], typ.key)
        vals = column_from_pylist(vs or [None], typ.value)
        return Column(typ, offs, None if valid.all() else valid, None,
                      lens, keys, vals)
    if isinstance(typ, RowType):
        valid = np.asarray([v is not None for v in values], dtype=bool)
        kids = []
        for i, (_, ft) in enumerate(typ.fields):
            kids.append(column_from_pylist(
                [(v[i] if v is not None else None) for v in values], ft))
        return Column(typ, np.zeros(len(values), dtype=np.int8),
                      None if valid.all() else valid,
                      children=tuple(kids))
    if is_string(typ):
        dictionary, codes = StringDictionary.from_strings(
            [v for v in values])
        valid = np.asarray([v is not None for v in values], dtype=bool)
        return Column(typ, codes,
                      None if valid.all() else valid, dictionary)
    data, valid, data2 = _to_lane(values, typ)
    return Column(typ, data, valid, data2=data2)


def column_from_numpy(arr: np.ndarray, typ: Type,
                      valid: Optional[np.ndarray] = None) -> Column:
    return Column(typ, np.asarray(arr, dtype=typ.np_dtype), valid)


@dataclass(frozen=True)
class Batch:
    """A batch of rows: ordered named Columns + row count.

    ``num_rows`` may be a python int (host-known) or a 0-d device int64
    (data-dependent, e.g. post-filter). Kernels use ``num_rows_device``;
    host logic calls ``num_rows_host`` (blocks on the device value).
    """

    columns: Dict[str, Column]
    num_rows: Union[int, jax.Array]

    @property
    def names(self) -> List[str]:
        return list(self.columns.keys())

    @property
    def capacity(self) -> int:
        for c in self.columns.values():
            return c.capacity
        return 0

    def column(self, name: str) -> Column:
        return self.columns[name]

    def num_rows_device(self) -> jax.Array:
        return jnp.asarray(self.num_rows, dtype=jnp.int64)

    def num_rows_host(self) -> int:
        n = self.num_rows
        return int(n) if not isinstance(n, int) else n

    def row_valid(self) -> jax.Array:
        """iota < num_rows over the capacity."""
        return (jnp.arange(self.capacity, dtype=jnp.int64)
                < self.num_rows_device())

    def on_device(self) -> "Batch":
        return Batch({k: c.on_device() for k, c in self.columns.items()},
                     self.num_rows)

    def select_columns(self, names: Sequence[str]) -> "Batch":
        return Batch({n: self.columns[n] for n in names}, self.num_rows)

    def rename(self, mapping: Dict[str, str]) -> "Batch":
        return Batch({mapping.get(k, k): c
                      for k, c in self.columns.items()}, self.num_rows)

    def gather(self, indices: ArrayLike,
               num_rows: Union[int, jax.Array]) -> "Batch":
        return Batch({k: c.gather(indices)
                      for k, c in self.columns.items()}, num_rows)

    def _host_fetched(self) -> "Batch":
        leaves = jax.device_get(
            {k: [c.data, c.valid, c.data2]
             for k, c in self.columns.items()})
        cols = {}
        for k, c in self.columns.items():
            d, v, d2 = leaves[k]
            cols[k] = replace(c, data=d, valid=v, data2=d2)
        return Batch(cols, self.num_rows)

    # --- host materialization (result delivery / tests) ------------------
    def to_pylist(self) -> List[list]:
        """Rows as python lists (client result encoding, reference:
        server/protocol/QueryResultRows.java). All device buffers are
        fetched in ONE transfer first: every per-column np.asarray
        readback is its own device sync (cost on the chip: not
        measured)."""
        n = self.num_rows_host()
        batch = self._host_fetched()
        out_cols = []
        for c in batch.columns.values():
            data = np.asarray(c.data)[:n]
            valid = (np.ones(n, dtype=bool) if c.valid is None
                     else np.asarray(c.valid)[:n])
            t = c.type
            col: List = []
            if is_string(t) or (c.dictionary is not None
                                and t.name == "varbinary"):
                vals = c.dictionary.values
                for i in range(n):
                    col.append(str(vals[int(data[i])]) if valid[i] else None)
                    if (col[-1] is not None and isinstance(t, CharType)):
                        col[-1] = col[-1].ljust(t.length)
            elif isinstance(t, DecimalType):
                import decimal as _dec
                s = t.scale
                hi = None if c.data2 is None else np.asarray(c.data2)[:n]
                for i in range(n):
                    if not valid[i]:
                        col.append(None)
                    else:
                        if hi is not None:
                            # (hi, lo) two's-complement Int128: lo is the
                            # unsigned low 64 bits, hi carries the sign
                            lo = int(data[i]) & ((1 << 64) - 1)
                            q = (int(hi[i]) << 64) + lo
                        else:
                            q = int(data[i])
                        # type-stable exact materialization: int for
                        # scale 0, decimal.Decimal otherwise (the client
                        # layer formats; reference: client decimals are
                        # exact strings, FixJsonDataUtils.java)
                        col.append(q if not s
                                   else _dec.Decimal(q).scaleb(
                                       -s, _dec.Context(prec=80)))
            elif t.name == "geometry":
                # point lanes render as WKT; WKT-backed shapes pass
                # their dictionary text through (ops/geo.py)
                if c.dictionary is not None:
                    vals = c.dictionary.values
                    col = [(str(vals[int(data[i])])
                            if valid[i] else None) for i in range(n)]
                else:
                    ys = np.asarray(c.data2)[:n]
                    from .ops.geo import _fmt
                    col = [(f"POINT ({_fmt(data[i])} {_fmt(ys[i])})"
                            if valid[i] else None) for i in range(n)]
            elif t.name == "hyperloglog":
                # rendered like the client renders varbinary: base64 of
                # this engine's dense sketch framing (ops/hll.py)
                from .ops.hll import sketches_to_base64
                enc = sketches_to_base64(data[:n],
                                         np.asarray(c.data2)[:n],
                                         np.asarray(c.elements.data),
                                         t.bucket_bits)
                col = [(enc[i] if valid[i] else None) for i in range(n)]
            elif t.name == "tdigest" or t.name.startswith("qdigest("):
                from .ops.digest import sketches_to_base64 as _d64
                enc = _d64(data[:n], np.asarray(c.data2)[:n],
                           np.asarray(c.elements.data),
                           np.asarray(c.elements2.data))
                col = [(enc[i] if valid[i] else None) for i in range(n)]
            elif t.name.startswith("array("):
                # materialize the flat elements once, slice per row
                e = c.elements
                ecap = int(np.asarray(e.data).shape[0])
                epy = [r[0] for r in Batch({"e": e}, ecap).to_pylist()]
                lens = np.asarray(c.data2)[:n]
                col = [(epy[int(data[i]): int(data[i]) + int(lens[i])]
                        if valid[i] else None) for i in range(n)]
            elif t.name.startswith("map("):
                k, v = c.elements, c.elements2
                ecap = int(np.asarray(k.data).shape[0])
                kpy = [r[0] for r in Batch({"k": k}, ecap).to_pylist()]
                vpy = [r[0] for r in Batch({"v": v}, ecap).to_pylist()]
                lens = np.asarray(c.data2)[:n]
                col = []
                for i in range(n):
                    if not valid[i]:
                        col.append(None)
                        continue
                    s, ln = int(data[i]), int(lens[i])
                    col.append(dict(zip(kpy[s:s + ln], vpy[s:s + ln])))
            elif t.name.startswith("row("):
                kids = [
                    [r[0] for r in
                     Batch({"f": ch}, min(n, ch.capacity)).to_pylist()]
                    for ch in c.children]
                col = [(list(vals) if valid[i] else None)
                       for i, vals in enumerate(zip(*kids))][:n] \
                    if kids else [[] for _ in range(n)]
            elif t.name == "boolean":
                col = [bool(data[i]) if valid[i] else None for i in range(n)]
            elif t.name in ("real", "double"):
                col = [float(data[i]) if valid[i] else None
                       for i in range(n)]
            elif t.name == "date":
                import datetime as _dt
                epoch = _dt.date(1970, 1, 1).toordinal()
                col = [_dt.date.fromordinal(int(data[i]) + epoch)
                       if valid[i] else None for i in range(n)]
            elif t.name.endswith("with time zone"):
                import datetime as _dt
                offs = (np.asarray(c.data2)[:n] if c.data2 is not None
                        else np.zeros(n, np.int64))
                col = []
                for i in range(n):
                    if not valid[i]:
                        col.append(None)
                        continue
                    tz = _dt.timezone(
                        _dt.timedelta(minutes=int(offs[i])))
                    col.append(_dt.datetime(
                        1970, 1, 1, tzinfo=_dt.timezone.utc)
                        + _dt.timedelta(milliseconds=int(data[i])))
                    col[-1] = col[-1].astimezone(tz)
            elif t.name.startswith("timestamp"):
                import datetime as _dt
                col = [(_dt.datetime(1970, 1, 1)
                        + _dt.timedelta(milliseconds=int(data[i])))
                       if valid[i] else None for i in range(n)]
            elif t.name.startswith("time("):
                import datetime as _dt
                col = []
                for i in range(n):
                    if not valid[i]:
                        col.append(None)
                        continue
                    ms = int(data[i]) % 86400000
                    col.append(_dt.time(ms // 3600000,
                                        (ms // 60000) % 60,
                                        (ms // 1000) % 60,
                                        (ms % 1000) * 1000))
            else:
                col = [int(data[i]) if valid[i] else None for i in range(n)]
            out_cols.append(col)
        return [list(row) for row in zip(*out_cols)] if out_cols else []

    def schema(self) -> Dict[str, Type]:
        return {k: c.type for k, c in self.columns.items()}


def batch_from_pylist(data: Dict[str, Sequence], schema: Dict[str, Type],
                      pad_to_bucket: bool = True) -> Batch:
    cols = {}
    n = 0
    for name, typ in schema.items():
        col = column_from_pylist(data[name], typ)
        n = len(data[name])
        cols[name] = col
    if pad_to_bucket:
        # pad even empty batches: capacity-0 arrays break jnp.take
        cap = capacity_for(n, minimum=8)
        cols = {k: _pad(c, cap) for k, c in cols.items()}
    return Batch(cols, n)


def _pad(col: Column, cap: int) -> Column:
    n = col.data.shape[0]
    if n >= cap:
        return col
    pad = cap - n
    data = np.concatenate(
        [np.asarray(col.data),
         np.zeros(pad, dtype=np.asarray(col.data).dtype)])
    valid = None if col.valid is None else np.concatenate(
        [np.asarray(col.valid), np.zeros(pad, dtype=bool)])
    data2 = None if col.data2 is None else np.concatenate(
        [np.asarray(col.data2),
         np.zeros(pad, dtype=np.asarray(col.data2).dtype)])
    children = (None if col.children is None
                else tuple(_pad(c, cap) for c in col.children))
    return replace(col, data=data, valid=valid, data2=data2,
                   children=children)


def pad_batch(batch: Batch, cap: int) -> Batch:
    return Batch({k: _pad(c, cap) for k, c in batch.columns.items()},
                 batch.num_rows)


def empty_batch(schema: Dict[str, Type], capacity: int = 8) -> Batch:
    cols = {}
    for name, typ in schema.items():
        if is_string(typ):
            d, _ = StringDictionary.from_strings([])
            cols[name] = Column(typ, np.zeros(capacity, dtype=np.int32),
                                None, d)
        else:
            cols[name] = Column(
                typ, np.zeros(capacity, dtype=typ.np_dtype), None)
    return Batch(cols, 0)


# --- pytree registration ---------------------------------------------------
# Column/Batch flow through jit/shard_map traces (the SPMD data plane,
# parallel/spmd.py): lanes are children; type + dictionary are static
# aux data (a new dictionary identity retraces, which is correct — the
# compiled program embeds dictionary-derived lookup tables).

def _column_flatten(c: Column):
    return ((c.data, c.valid, c.data2, c.elements, c.elements2,
             c.children), (c.type, c.dictionary))


def _column_unflatten(aux, kids):
    data, valid, data2, elements, elements2, children = kids
    typ, dictionary = aux
    return Column(typ, data, valid, dictionary, data2, elements,
                  elements2, children)


def _batch_flatten(b: Batch):
    names = tuple(b.columns.keys())
    return (tuple(b.columns[n] for n in names), b.num_rows), names


def _batch_unflatten(names, children):
    cols, num_rows = children
    return Batch(dict(zip(names, cols)), num_rows)


jax.tree_util.register_pytree_node(Column, _column_flatten,
                                   _column_unflatten)
jax.tree_util.register_pytree_node(Batch, _batch_flatten,
                                   _batch_unflatten)


def concat_batches(batches: Sequence[Batch]) -> Batch:
    """Host-side concatenation of result batches (final GATHER stage)."""
    batches = [b for b in batches if b.num_rows_host() > 0] or batches[:1]
    if len(batches) == 1:
        return batches[0]
    names = batches[0].names
    total = sum(b.num_rows_host() for b in batches)
    cols: Dict[str, Column] = {}
    for name in names:
        parts = [b.column(name) for b in batches]
        typ = parts[0].type
        if parts[0].elements is not None or parts[0].children is not None:
            from .exec.complex import concat_columns_host
            cols[name] = concat_columns_host(
                parts, [b.num_rows_host() for b in batches],
                capacity_for(total))
            continue
        datas, valids = [], []
        if is_string(typ):
            merged = parts[0].dictionary
            remaps = [np.arange(len(merged), dtype=np.int32)]
            for p in parts[1:]:
                merged, rs, ro = merged.merge(p.dictionary)
                remaps = [r for r in remaps]
                remaps.append(ro)
            for p, rm, b in zip(parts, remaps, batches):
                n = b.num_rows_host()
                codes = np.asarray(p.data)[:n]
                datas.append(rm[codes])
                valids.append(np.ones(n, bool) if p.valid is None
                              else np.asarray(p.valid)[:n])
            data = np.concatenate(datas) if datas else np.zeros(0, np.int32)
            valid = np.concatenate(valids)
            cols[name] = Column(
                typ, data.astype(np.int32),
                None if valid.all() else valid, merged)
        else:
            has_hi = any(p.data2 is not None for p in parts)
            his = []
            for p, b in zip(parts, batches):
                n = b.num_rows_host()
                datas.append(np.asarray(p.data)[:n])
                valids.append(np.ones(n, bool) if p.valid is None
                              else np.asarray(p.valid)[:n])
                if has_hi:
                    if p.data2 is not None:
                        his.append(np.asarray(p.data2)[:n])
                    else:
                        # short-decimal part: hi lane is the sign extension
                        lo = np.asarray(p.data)[:n]
                        his.append(np.where(lo < 0, np.int64(-1),
                                            np.int64(0)))
            data = np.concatenate(datas)
            valid = np.concatenate(valids)
            cols[name] = Column(typ, data,
                                None if valid.all() else valid,
                                data2=(np.concatenate(his) if has_hi
                                       else None))
    return pad_batch(Batch(cols, total), capacity_for(total))
