"""Columnar page wire/spill format.

Reference parity: execution/buffer/{PagesSerde.java:41-74,
SerializedPage.java:25-47, PagesSerdeUtil.java:64-100, PageCodecMarker}
— header + per-block encodings, optional LZ4, checksum. TPU-first frame:
struct-of-arrays (one contiguous lane per column — uploads straight into
device buffers), little-endian, xxHash64 trailer. The LZ4/xxh64 hot
loops are native C++ (native/pageserde.cpp) loaded via ctypes; a
pure-python "store" codec keeps everything working when the library
hasn't been built.

Frame layout:
  magic 'TPG1' | u8 codec | u32 ncols | u64 nrows
  per column:
    u16 name_len | name utf8 | u16 type_len | type utf8 | u8 flags
    lane DATA  [flags&1: VALID lane] [flags&2: DATA2 lane]
    [flags&4: dictionary — u32 count | per value u32 len + utf8]
  u64 xxh64 of everything before the trailer
Each lane: u8 dtype_code | u64 raw_len | u64 stored_len | bytes.
"""

from __future__ import annotations

import ctypes
import os
import threading
import struct
import subprocess
from typing import Dict, Optional

import numpy as np

from .columnar import Batch, Column, StringDictionary
from .config import capacity_for
from .types import Type, parse_type

_MAGIC = b"TPG1"
CODEC_STORE = 0
CODEC_LZ4 = 1

_DTYPES = [np.dtype(x) for x in
           ("bool", "int8", "int16", "int32", "int64", "float32",
            "float64", "uint64")]
_DTYPE_CODE = {d: i for i, d in enumerate(_DTYPES)}


# --------------------------------------------------------------------------
# native library loading (build on demand, cache the result)
# --------------------------------------------------------------------------

_LIB: Optional[ctypes.CDLL] = None
_LIB_TRIED = False
_LIB_LOCK = threading.Lock()


def _load_native() -> Optional[ctypes.CDLL]:
    global _LIB, _LIB_TRIED
    if _LIB_TRIED:
        return _LIB
    with _LIB_LOCK:
        return _load_native_locked()


def _load_native_locked() -> Optional[ctypes.CDLL]:
    """Must hold _LIB_LOCK. The flag flips only AFTER the load settles:
    a concurrent first call must block, not observe a half-initialized
    state — a worker thread that raced here used to fall back to
    crc32/STORE framing while its peers (and the coordinator) used
    xxh64/LZ4, surfacing as flaky 'page checksum mismatch' on tiny
    pages serialized inside the race window."""
    global _LIB, _LIB_TRIED
    if _LIB_TRIED:
        return _LIB
    try:
        _LIB = _do_load()
    finally:
        # flips LAST so the unlocked fast path can never observe
        # TRIED=True with the load still in flight
        _LIB_TRIED = True
    return _LIB


def _do_load() -> Optional[ctypes.CDLL]:
    here = os.path.dirname(os.path.abspath(__file__))
    so = os.path.join(here, "native", "libpageserde.so")
    src = os.path.normpath(os.path.join(here, "..", "native",
                                        "pageserde.cpp"))
    # build when the library is missing OR older than its source: the
    # .so is git-ignored, so a stale one rides along in copies of the
    # tree and would otherwise shadow the committed source forever
    if os.path.exists(src) and (
            not os.path.exists(so)
            or os.path.getmtime(src) > os.path.getmtime(so)):
        try:
            os.makedirs(os.path.dirname(so), exist_ok=True)
            subprocess.run(
                ["g++", "-O3", "-fPIC", "-shared", "-std=c++17",
                 "-o", so, src],
                check=True, capture_output=True, timeout=120)
        except (OSError, subprocess.SubprocessError):
            return None
    if not os.path.exists(so):
        return None
    try:
        lib = ctypes.CDLL(so)
    except OSError:
        return None
    for name, restype, argtypes in [
        ("tt_lz4_compress", ctypes.c_int64,
         [ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
          ctypes.c_int64]),
        ("tt_lz4_decompress", ctypes.c_int64,
         [ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
          ctypes.c_int64]),
        ("tt_lz4_max_compressed", ctypes.c_int64, [ctypes.c_int64]),
        ("tt_xxh64", ctypes.c_uint64,
         [ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint64]),
    ]:
        fn = getattr(lib, name)
        fn.restype = restype
        fn.argtypes = argtypes
    return lib


def native_available() -> bool:
    return _load_native() is not None


def checksum(data: bytes, seed: int = 0) -> int:
    lib = _load_native()
    if lib is not None:
        return int(lib.tt_xxh64(data, len(data), seed))
    import zlib
    return zlib.crc32(data) ^ (seed & 0xFFFFFFFF)   # python fallback


def _compress(data: bytes, codec: int) -> bytes:
    if codec == CODEC_LZ4:
        lib = _load_native()
        cap = int(lib.tt_lz4_max_compressed(len(data)))
        out = ctypes.create_string_buffer(cap)
        n = lib.tt_lz4_compress(data, len(data), out, cap)
        if n < 0:
            raise ValueError("lz4 compression failed")
        return out.raw[:n]
    return data


def _decompress(data: bytes, raw_len: int, codec: int) -> bytes:
    if codec == CODEC_LZ4:
        lib = _load_native()
        out = ctypes.create_string_buffer(raw_len)
        n = lib.tt_lz4_decompress(data, len(data), out, raw_len)
        if n != raw_len:
            raise ValueError(
                f"lz4 decompression failed ({n} != {raw_len})")
        return out.raw
    return data


# --------------------------------------------------------------------------
# framing
# --------------------------------------------------------------------------

def _emit_lane(out: list, arr: np.ndarray, codec: int):
    arr = np.ascontiguousarray(arr)
    code = _DTYPE_CODE[arr.dtype]
    raw = arr.tobytes()
    stored = _compress(raw, codec)
    if len(stored) >= len(raw):
        stored, lane_codec = raw, CODEC_STORE
    else:
        lane_codec = codec
    out.append(struct.pack("<BBQQ", code, lane_codec, len(raw),
                           len(stored)))
    out.append(stored)


def _read_lane(buf: memoryview, off: int):
    code, lane_codec, raw_len, stored_len = struct.unpack_from(
        "<BBQQ", buf, off)
    off += struct.calcsize("<BBQQ")
    stored = bytes(buf[off:off + stored_len])
    off += stored_len
    raw = _decompress(stored, raw_len, lane_codec)
    return np.frombuffer(raw, dtype=_DTYPES[code]).copy(), off


def _emit_column(out: list, name: str, col: Column, n: int, codec: int):
    nb = name.encode()
    tb = col.type.name.encode()
    flags = ((1 if col.valid is not None else 0)
             | (2 if col.data2 is not None else 0)
             | (4 if col.dictionary is not None else 0)
             | (8 if col.elements is not None else 0))
    out.append(struct.pack("<H", len(nb)))
    out.append(nb)
    out.append(struct.pack("<H", len(tb)))
    out.append(tb)
    out.append(struct.pack("<B", flags))
    out.append(struct.pack("<Q", n))
    _emit_lane(out, np.asarray(col.data)[:n], codec)
    if col.valid is not None:
        _emit_lane(out, np.asarray(col.valid)[:n], codec)
    if col.data2 is not None:
        _emit_lane(out, np.asarray(col.data2)[:n], codec)
    if col.dictionary is not None:
        vals = col.dictionary.values
        out.append(struct.pack("<I", len(vals)))
        for v in vals:
            vb = str(v).encode()
            out.append(struct.pack("<I", len(vb)))
            out.append(vb)
    if col.elements is not None:
        # arrays ship their whole flat elements column (offsets index
        # into it; spi/block/ArrayBlock's values block analog)
        el = col.elements
        _emit_column(out, "$elements", el,
                     int(np.asarray(el.data).shape[0]), codec)


def serialize_batch(batch: Batch, codec: Optional[int] = None) -> bytes:
    """Batch -> framed bytes (live prefix only)."""
    if codec is None:
        codec = CODEC_LZ4 if native_available() else CODEC_STORE
    n = batch.num_rows_host()
    out: list = [_MAGIC, struct.pack("<BIQ", codec,
                                     len(batch.columns), n)]
    for name, col in batch.columns.items():
        _emit_column(out, name, col, n, codec)
    body = b"".join(out)
    return body + struct.pack("<Q", checksum(body))


def _read_column(buf: memoryview, off: int):
    (nlen,) = struct.unpack_from("<H", buf, off)
    off += 2
    name = bytes(buf[off:off + nlen]).decode()
    off += nlen
    (tlen,) = struct.unpack_from("<H", buf, off)
    off += 2
    typ = parse_type(bytes(buf[off:off + tlen]).decode())
    off += tlen
    (flags,) = struct.unpack_from("<B", buf, off)
    off += 1
    (n,) = struct.unpack_from("<Q", buf, off)
    off += 8
    data_arr, off = _read_lane(buf, off)
    valid = d2 = dictionary = elements = None
    if flags & 1:
        valid, off = _read_lane(buf, off)
    if flags & 2:
        d2, off = _read_lane(buf, off)
    if flags & 4:
        (cnt,) = struct.unpack_from("<I", buf, off)
        off += 4
        vals = []
        for _ in range(cnt):
            (vlen,) = struct.unpack_from("<I", buf, off)
            off += 4
            vals.append(bytes(buf[off:off + vlen]).decode())
            off += vlen
        dictionary = StringDictionary(np.asarray(vals, dtype=object))
    if flags & 8:
        _, elements, off = _read_column(buf, off)
    cap = capacity_for(max(int(n), 1), minimum=8)
    pad = cap - len(data_arr)
    data_arr = np.pad(data_arr, (0, pad))
    if valid is not None:
        valid = np.pad(valid, (0, pad))
    if d2 is not None:
        d2 = np.pad(d2, (0, pad))
    return name, Column(typ, data_arr, valid, dictionary, d2,
                        elements), off


def frame_valid(data: bytes) -> bool:
    """Cheap integrity check of a serialized frame (magic prefix +
    xxh64 trailer) WITHOUT decoding it — the exchange puller's guard
    against accepting a non-frame HTTP 200 body (a wedged or foreign
    endpoint) as a partition during its candidate-worker sweep."""
    if len(data) < 12 or data[:4] != _MAGIC:
        return False
    buf = memoryview(data)
    (csum,) = struct.unpack_from("<Q", buf, len(buf) - 8)
    return checksum(bytes(buf[:-8])) == csum


def deserialize_batch(data: bytes) -> Batch:
    buf = memoryview(data)
    body, (csum,) = buf[:-8], struct.unpack_from("<Q", buf, len(buf) - 8)
    if checksum(bytes(body)) != csum:
        raise ValueError("page checksum mismatch")
    if bytes(buf[:4]) != _MAGIC:
        raise ValueError("bad page magic")
    codec, ncols, nrows = struct.unpack_from("<BIQ", buf, 4)
    off = 4 + struct.calcsize("<BIQ")
    cols: Dict[str, Column] = {}
    for _ in range(ncols):
        # _read_column pads each column to capacity_for(its n); every
        # top-level column carries the batch's n, so they share the
        # batch's capacity bucket
        name, col, off = _read_column(buf, off)
        cols[name] = col
    return Batch(cols, int(nrows))


# --------------------------------------------------------------------------
# spill (spiller/FileSingleStreamSpiller.java analog)
# --------------------------------------------------------------------------

class Spiller:
    """Writes batches to local disk pages and reads them back — the
    HBM -> host-RAM -> disk overflow tier (SURVEY.md §5
    checkpoint/resume: spill/unspill is the reference's only
    state-offload mechanism)."""

    # every live spill file across instances, for the leak detector
    # (server/diagnostics.py — a spill file outliving its query is the
    # reference's revocable-memory leak analog)
    _LIVE: "set[str]" = set()
    _LIVE_LOCK = threading.Lock()

    def __init__(self, directory: Optional[str] = None):
        import tempfile
        self._dir = directory or tempfile.mkdtemp(prefix="trino_tpu_spill_")
        self._files: list = []

    @classmethod
    def live_files(cls) -> list:
        with cls._LIVE_LOCK:
            return sorted(cls._LIVE)

    def spill(self, batch: Batch) -> str:
        path = os.path.join(self._dir, f"page_{len(self._files)}.bin")
        with open(path, "wb") as f:
            f.write(serialize_batch(batch))
        self._files.append(path)
        with Spiller._LIVE_LOCK:
            Spiller._LIVE.add(path)
        return path

    def unspill(self, path: str) -> Batch:
        with open(path, "rb") as f:
            return deserialize_batch(f.read())

    def unspill_all(self):
        return [self.unspill(p) for p in self._files]

    def close(self):
        gone = []
        for p in self._files:
            try:
                os.unlink(p)
                gone.append(p)
            except OSError:
                pass        # stays in _LIVE: still on disk == a leak
        with Spiller._LIVE_LOCK:
            Spiller._LIVE.difference_update(gone)
        self._files.clear()
