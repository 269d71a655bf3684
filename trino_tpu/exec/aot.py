"""Ahead-of-time fragment compilation: lower + compile without data.

Reference parity: the paper's codegen layer maps to full AOT
compilation of query programs (PAPERS: Julia-to-TPU, arxiv 1810.09868)
over canonicalized operator-as-tensor-program shapes (arxiv
2203.01877). The JVM reference needs nothing like this — bytecode
generation is milliseconds — but XLA compile is 30-90s per fragment
shape, so decoupling compilation from first execution is the
difference between a worker that serves its first query at device
speed and one that stalls a fleet.

Mechanics: a hot-shape payload (exec/hotshapes.py) carries the
CANONICAL fragment (exec/progkey.py wire form) plus the observed input
lane spec at its capacity bucket. ``compile_entry`` rebuilds the exact
closure the executor would build for that program, fabricates an
argument Batch of ``jax.ShapeDtypeStruct`` avals — no real data — and
runs ``jax.jit(fn).lower(batch).compile()``. The compile:

- inserts the jitted callable into the program cache
  (exec/progkey.py ``PROGRAMS``) in the bucket and under the SAME
  canonical key the executor probes, and
- writes the compiled program into jax's persistent compilation cache
  (config.py), so even a later signature variation (a different
  capacity bucket, a fresh dictionary identity) pays only a re-trace,
  never the XLA compile.

AOT purity contract: functions lowered here must be data-independent
Python — no ``if x.item()`` / ``int(arr)`` branches on traced values
(there is no data to branch on). ``analysis/lint.py`` enforces this
statically (the ``aot-unsafe`` rule)."""

from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from ..catalog import CatalogManager
from ..obs.metrics import METRICS
from ..session import Session

_M_AOT = METRICS.counter(
    "trino_tpu_aot_compiles_total",
    "AOT fragment compilations by outcome",
    ("kind", "result"))     # result: compiled | cached | error
_M_AOT_WALL = METRICS.histogram(
    "trino_tpu_aot_compile_seconds",
    "Per-shape AOT compile wall (lower + XLA compile)",
    ("kind",))


def _aval_batch(payload: dict, schema):
    """Fabricate the argument Batch: ShapeDtypeStruct lanes at the
    recorded capacity bucket, real (small) dictionaries — everything
    jax needs to trace and compile, nothing touching real data."""
    import jax
    from ..columnar import Batch, Column, StringDictionary
    cap = int(payload["capacity"])
    cols = {}
    for ent in payload["cols"]:
        name = ent["name"]
        data = jax.ShapeDtypeStruct((cap,), np.dtype(ent["dtype"]))
        valid = (jax.ShapeDtypeStruct((cap,), np.dtype(bool))
                 if ent.get("valid") else None)
        d2 = (jax.ShapeDtypeStruct((cap,), np.dtype(ent["data2"]))
              if ent.get("data2") else None)
        dictionary = None
        if ent.get("dict") is not None:
            dictionary = StringDictionary(
                np.asarray(ent["dict"], dtype=object))
        cols[name] = Column(schema[name], data, valid, dictionary, d2)
    if payload["num_rows"] == "int":
        num_rows = cap
    else:
        import jax as _jax
        num_rows = _jax.ShapeDtypeStruct(
            (), np.dtype(payload["num_rows"]))
    literals = payload.get("literals")
    if literals:
        # the literal vectors a program with slots takes beside its
        # lanes (exec/literals.py): shapes only
        from .literals import LITERAL_SLOTS, BoundBatch
        return BoundBatch(cols, num_rows, {
            dt: jax.ShapeDtypeStruct((LITERAL_SLOTS,), np.dtype(dt))
            for dt in literals["dtypes"]}, int(literals["bound"]))
    return Batch(cols, num_rows)


def _peeled_fragment(payload: dict):
    """(top-down canonical nodes, fps key, input schema) from a
    fragment-carrying payload — the chain/stream/window transport."""
    from .progkey import node_fingerprint, peel_wire_fragment
    from ..plan.serde import from_jsonable
    root = from_jsonable(payload["fragment"])
    nodes, schema = peel_wire_fragment(root)
    fps = tuple(node_fingerprint(n) for n in nodes)
    if any(f is None for f in fps):
        raise ValueError("hot-shape fragment is not canonicalizable")
    return nodes, fps, schema


def _mjoin_programs(payload: dict) -> list:
    """The TWO programs of one materialized hash join (count + expand,
    exec/executor.py) from their shared payload — a join pre-warm is
    incomplete unless both phases land in the cache."""
    import jax
    from . import executor as ex
    from ..plan.serde import from_jsonable
    from .streamjoin import _spec_from_payload
    frag = from_jsonable(payload["fragment"])
    pschema, bschema = dict(frag.left.schema), dict(frag.right.schema)
    pkeys = [c.left for c in frag.criteria]
    bkeys = [c.right for c in frag.criteria]
    pcap = int(payload["chunk_capacity"])
    bcap = int(payload["build_capacity"])
    out_cap = int(payload["out_capacity"])
    pspec = _spec_from_payload(payload["probe_cols"])
    bspec = _spec_from_payload(payload["build_cols"])
    outer = frag.join_type == "left"
    probe = _aval_batch(
        {"cols": payload["probe_cols"], "capacity": pcap,
         "num_rows": payload.get("probe_num_rows", "int")}, pschema)
    build = _aval_batch(
        {"cols": payload["build_cols"], "capacity": bcap,
         "num_rows": payload.get("build_num_rows", "int")}, bschema)

    def i64(n: int):
        return jax.ShapeDtypeStruct((n,), np.dtype(np.int64))

    # the count program reads the whole inputs, the expand the lanes the
    # join puts out and its residual reads (executor.py expand_lanes)
    lanes = ex.expand_lanes(frag.outputs, frag.filter)
    eprobe, ebuild = ex.narrow(probe, lanes), ex.narrow(build, lanes)
    ckey = ex.mjoin_count_key(outer, pkeys, bkeys, pspec, bspec,
                              pcap, bcap)
    ekey = ex.mjoin_expand_key(
        frag.join_type, repr(frag.filter),
        tuple(e for e in pspec if e[0] in eprobe.columns),
        tuple(e for e in bspec if e[0] in ebuild.columns), pcap, bcap,
        out_cap)
    return [
        (ckey, ex.make_mjoin_count_program(pkeys, bkeys, outer),
         (probe, build), "join", ex.mjoin_kind(ckey), ckey),
        (ekey, ex.make_mjoin_expand_program(frag.join_type,
                                            frag.filter, out_cap),
         (eprobe, ebuild, i64(pcap), i64(pcap), i64(bcap)),
         "join", ex.mjoin_kind(ekey), ekey)]


def _repartition_program(payload: dict) -> tuple:
    import jax
    from ..stage import repartition as rp
    nkeys = int(payload["nkeys"])
    cap = int(payload["capacity"])
    nparts = int(payload["nparts"])
    lanes = tuple(jax.ShapeDtypeStruct((cap,), np.dtype(np.uint64))
                  for _ in range(nkeys))
    valids = tuple(jax.ShapeDtypeStruct((cap,), np.dtype(bool))
                   for _ in range(nkeys))
    key = rp.bucket_program_key(nkeys, cap, nparts)
    return (key, rp.make_bucket_program(nkeys, nparts),
            (lanes, valids), "repartition", "repartition", key)


def compile_entry(entry: dict) -> Optional[float]:
    """AOT-compile one hot-shape registry entry — every jitted program
    the entry's shape needs (a materialized join carries two: count +
    expand). Returns the total compile wall in seconds, or None when
    all programs were already resident in the program cache (a hit —
    nothing to do). Raises on a broken payload; callers treat per-entry
    failures as skippable. Every branch gives (key, function, argument
    avals, bucket, name kind, name key): one loop compiles them all."""
    from . import executor as ex
    from .progkey import PROGRAMS, named_jit

    payload = entry["payload"] if "payload" in entry else entry
    kind = str(payload["kind"])
    if kind == "streamjoin":
        # streamed-join probe programs (exec/streamjoin.py) carry
        # their own transport form: a JoinNode over two schema-
        # carrying RemoteSource leaves + both sides' lane specs, so a
        # pre-warming worker compiles the chunk kernel at its
        # canonical chunk capacity too
        from .streamjoin import aot_entry
        key, fn, args = aot_entry(payload)
        programs = [(key, fn, args, "streamjoin", "streamjoin", key)]
    elif kind == "join":
        # materialized hash join: same wire form as streamjoin, two
        # programs (exec/executor.py mjoin count/expand)
        programs = _mjoin_programs(payload)
    elif kind == "repartition":
        # the exchange bucketing kernel (stage/repartition.py) — no
        # fragment, just the (key count, capacity, nparts) signature
        programs = [_repartition_program(payload)]
    elif kind == "window":
        nodes, fps, schema = _peeled_fragment(payload)
        programs = [(fps, ex.make_window_program(nodes[0]),
                     (_aval_batch(payload, schema),),
                     "window", "window", fps)]
    else:
        nodes, fps, schema = _peeled_fragment(payload)

        # the same helper shape the executor's structural closures
        # capture: detached (no per-query state), catalogs untouched
        # by chain evaluation
        helper = ex.Executor(CatalogManager(), Session())

        if kind == "chain":
            key, bucket = fps, "chain"
            fn = ex.make_chain_program(helper, nodes)
        elif kind in ("stream", "stream_full"):
            # stream node stacks lead with the AggregationNode
            # (progkey.canonicalize_nodes order)
            agg, chain = nodes[0], nodes[1:]
            run, run_full = ex.make_stream_runners(helper, chain, agg)
            key = fps if kind == "stream" else (fps, "full")
            bucket = "stream"
            fn = run if kind == "stream" else run_full
        else:
            raise ValueError(f"unknown hot-shape kind {kind!r}")
        programs = [(key, fn, (_aval_batch(payload, schema),), bucket,
                     kind, fps)]

    wall = 0.0
    compiled = False
    # each program is jitted under the SAME name the executor would
    # give it (progkey.named_jit: kind + canonical key), so the module
    # compiled here is the one the first real query looks up
    for key, fn, args, bucket, name_kind, name_key in programs:
        if PROGRAMS.resident(bucket, key):
            continue
        t0 = time.perf_counter()
        try:
            jitted = named_jit(fn, name_kind, name_key)
            jitted.lower(*args).compile()
        except Exception:
            _M_AOT.inc(kind=kind, result="error")
            raise
        wall += time.perf_counter() - t0
        # the jitted callable (now holding the compiled program in its
        # own cache) lands under the executor's key: the first real
        # query with this shape is an in-process cache hit
        PROGRAMS.put(bucket, key, jitted)
        compiled = True
    if not compiled:
        _M_AOT.inc(kind=kind, result="cached")
        return None
    _M_AOT.inc(kind=kind, result="compiled")
    _M_AOT_WALL.observe(wall, kind=kind)
    return wall


def compile_entries(entries: List[dict]) -> dict:
    """Compile a hot-shape list (best-effort, per-entry isolation):
    returns {"compiled": n, "cached": n, "errors": n, "wall_s": total}
    — the pre-warm loop's summary (server/task_worker.py)."""
    out = {"compiled": 0, "cached": 0, "errors": 0, "wall_s": 0.0}
    for e in entries or ():
        try:
            wall = compile_entry(e)
        except Exception:       # noqa: BLE001 — one bad shape must
            # not abort the warm-up of the rest
            out["errors"] += 1
            continue
        if wall is None:
            out["cached"] += 1
        else:
            out["compiled"] += 1
            out["wall_s"] += wall
    return out
