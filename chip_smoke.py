#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served SQL path runs on
the chip.

One process, which alone touches JAX. It starts a coordinator the way
``trino_tpu/server/main.py`` does (default catalogs, no workers, so
queries execute in this process: on the one chip of a one-chip
machine; four chips are the benchmark cell ``tpch_sf10_mesh4.power``'s,
not this script's), talks to it
over HTTP with ``trino_tpu.client.StatementClient``, serves TPC-H q6,
q1 and q3 on ``tpch.sf1`` twice each (cold = with compile, warm), and
compares every result with a plain numpy computation over rows from the
HOST generator in ``connectors/tpch.py`` (exact for keys, counts and
dates, 1e-9 relative for float sums).

    python chip_smoke.py                 one chip (what the driver runs)
    python chip_smoke.py --sf10          ... plus q1 on tpch.sf10
    JAX_PLATFORMS=cpu TRINO_TPU_PALLAS=interpret TRINO_TPU_FRAGMENT_JIT=1 \\
    TRINO_TPU_WHOLE_TABLE=1 TRINO_TPU_DEVICE_GEN=1 \\
        python chip_smoke.py --rehearse --scale tiny

Output: one JSON object per line. The last line of a passing run is
exactly ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Without ``--rehearse`` the script exits non-zero at once when the
platform is not ``tpu``; with it the script walks every phase on
whatever platform JAX has and NEVER prints ``"ok": true`` nor exits 0.
Nothing is caught: an exception, a mismatch or a failed query ends the
run with a non-zero exit code.
"""

import argparse
import datetime
import json
import os
import sys
import time

import numpy as np

REL_TOL = 1e-9
Q3_SEGMENT = "BUILDING"
Q3_DATE = datetime.date(1995, 3, 15)
Q1_CUTOFF = datetime.date(1998, 12, 1) - datetime.timedelta(days=90)
EPOCH = datetime.date(1970, 1, 1)


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def _days(d: datetime.date) -> int:
    return (d - EPOCH).days


# --------------------------------------------------------------------------
# the plain reference: numpy over the host generator's rows
# --------------------------------------------------------------------------

class HostReference:
    """q6/q1/q3 answers from ``TpchConnector``'s HOST generators
    (``_lineitem``/``_orders``/``_customer``: pure numpy, independent of
    ``tpch_device.py`` and of every engine operator), streamed in chunks
    of orders so sf10 needs no more host memory than sf1."""

    ORDERS_PER_CHUNK = 250_000

    def __init__(self, schema: str, want=(6, 1, 3)):
        from trino_tpu.connectors.tpch import (SCHEMAS, TpchConnector,
                                               table_rows)
        self.sf = SCHEMAS[schema]
        self.conn = TpchConnector()
        self.n_orders = table_rows("orders", self.sf)
        self.n_customers = table_rows("customer", self.sf)
        self.n_lineitem = 0
        self.want = set(want)
        self._q1 = {}            # (flag, status) -> [6 sums..., count]
        self._q6 = 0.0
        self._q3_keys = []       # per chunk: selected orders' lanes
        self._q3_rev = []
        self._run()

    @staticmethod
    def _lanes(batch, names):
        n = int(batch.num_rows)
        out = []
        for name in names:
            c = batch.column(name)
            data = np.asarray(c.data)[:n]
            if c.dictionary is not None:
                data = np.asarray(c.dictionary.values)[data].astype(str)
            out.append(data)
        return out

    def _run(self) -> None:
        building = None
        if 3 in self.want:
            idx = np.arange(1, self.n_customers + 1, dtype=np.int64)
            key, seg = self._lanes(
                self.conn._customer(idx, self.sf,
                                    ["c_custkey", "c_mktsegment"]),
                ["c_custkey", "c_mktsegment"])
            building = np.zeros(self.n_customers + 1, bool)
            building[key[seg == Q3_SEGMENT]] = True
        for lo in range(0, self.n_orders, self.ORDERS_PER_CHUNK):
            hi = min(lo + self.ORDERS_PER_CHUNK, self.n_orders)
            idx = np.arange(lo + 1, hi + 1, dtype=np.int64)
            names = ["l_orderkey", "l_quantity", "l_extendedprice",
                     "l_discount", "l_tax", "l_shipdate",
                     "l_returnflag", "l_linestatus"]
            (okey, qty, price, disc, tax, ship, flag, status) = \
                self._lanes(self.conn._lineitem(idx, self.sf, names),
                            names)
            self.n_lineitem += len(okey)
            if 6 in self.want:
                self._fold_q6(qty, price, disc, ship)
            if 1 in self.want:
                self._fold_q1(qty, price, disc, tax, ship, flag, status)
            if 3 in self.want:
                self._fold_q3(idx, building, okey, price, disc, ship)

    def _fold_q6(self, qty, price, disc, ship) -> None:
        lo = _days(datetime.date(1994, 1, 1))
        hi = _days(datetime.date(1995, 1, 1))
        # SQL decimal literals are exact: 0.06 - 0.01 is 0.05 and
        # 0.06 + 0.01 is 0.07 (binary doubles would give 0.0699...)
        m = ((ship >= lo) & (ship < hi) & (disc >= 0.05)
             & (disc <= 0.07) & (qty < 24))
        self._q6 += float(np.sum(price[m] * disc[m]))

    def _fold_q1(self, qty, price, disc, tax, ship, flag, status) -> None:
        m = ship <= _days(Q1_CUTOFF)
        disc_price = price * (1 - disc)
        charge = disc_price * (1 + tax)
        for f in np.unique(flag[m]):
            for s in np.unique(status[m]):
                g = m & (flag == f) & (status == s)
                if not g.any():
                    continue
                acc = self._q1.setdefault((str(f), str(s)), [0.0] * 6)
                for i, lane in enumerate((qty, price, disc_price,
                                          charge, disc)):
                    acc[i] += float(np.sum(lane[g]))
                acc[5] += int(np.sum(g))

    def _fold_q3(self, idx, building, okey, price, disc, ship) -> None:
        names = ["o_orderkey", "o_custkey", "o_orderdate",
                 "o_shippriority"]
        o_key, o_cust, o_date, o_prio = self._lanes(
            self.conn._orders(idx, self.sf, names), names)
        cut = _days(Q3_DATE)
        sel = building[o_cust] & (o_date < cut)
        o_key, o_date, o_prio = o_key[sel], o_date[sel], o_prio[sel]
        # a chunk of orders holds exactly its own lineitems, so the
        # join closes inside the chunk; o_key is ascending
        pos = np.searchsorted(o_key, okey)
        pos_c = np.minimum(pos, max(len(o_key) - 1, 0))
        hit = (ship > cut) & (len(o_key) > 0)
        hit = hit & (o_key[pos_c] == okey) if len(o_key) else hit
        rev = np.bincount(pos_c[hit], weights=(price * (1 - disc))[hit],
                          minlength=len(o_key))
        has = np.bincount(pos_c[hit], minlength=len(o_key)) > 0
        self._q3_keys.append(
            np.stack([o_key[has], o_date[has], o_prio[has]], axis=1))
        self._q3_rev.append(rev[has])

    # ---- the answers, in the shape the client returns them -------------
    def q6(self):
        return [[self._q6]]

    def q1(self):
        rows = []
        for (f, s), a in sorted(self._q1.items()):
            n = a[5]
            rows.append([f, s, a[0], a[1], a[2], a[3], a[0] / n,
                         a[1] / n, a[4] / n, n])
        return rows

    def q3(self):
        keys = np.concatenate(self._q3_keys)
        rev = np.concatenate(self._q3_rev)
        order = np.lexsort((keys[:, 1], -rev))[:10]
        return [[int(keys[i, 0]), float(rev[i]),
                 (EPOCH + datetime.timedelta(days=int(keys[i, 1])))
                 .isoformat(), int(keys[i, 2])] for i in order]

    def answer(self, q: int):
        return {6: self.q6, 1: self.q1, 3: self.q3}[q]()


def compare(label: str, got, want) -> None:
    """Exact for keys, counts and dates; REL_TOL for floats. Raises."""
    if len(got) != len(want):
        raise AssertionError(
            f"{label}: {len(got)} rows, reference has {len(want)}")
    for r, (g, w) in enumerate(zip(got, want)):
        if len(g) != len(w):
            raise AssertionError(f"{label} row {r}: width {len(g)} "
                                 f"!= {len(w)}")
        for c, (a, b) in enumerate(zip(g, w)):
            if isinstance(b, float):
                ok = isinstance(a, (int, float)) and \
                    abs(a - b) <= REL_TOL * max(abs(b), 1e-300)
            else:
                ok = a == b
            if not ok:
                raise AssertionError(
                    f"{label} row {r} col {c}: got {a!r}, "
                    f"reference {b!r}")


# --------------------------------------------------------------------------
# instrumentation taken from OUTSIDE the engine
# --------------------------------------------------------------------------

class Counters:
    """jax.monitoring listeners: compile requests (in-process jit
    misses, served by a compile OR a persistent-cache read) and
    persistent-cache hits; plus a call count on the grouped-sum kernel
    entry, which is only ever called while a program is being traced."""

    def __init__(self):
        import jax
        from trino_tpu.ops import pallas_groupby
        self.compile_requests = 0
        self.persistent_hits = 0
        self.kernel_traces = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_requests += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.persistent_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

        impl = pallas_groupby._grouped_sums_impl

        def counted(*a, **kw):
            self.kernel_traces += 1
            return impl(*a, **kw)

        pallas_groupby._grouped_sums_impl = counted


def serve(client, label: str, sql: str, want, counters: Counters):
    """Run one query cold and warm through the client; both passes are
    compared with the reference."""
    out = {"query": label}
    for name in ("cold", "warm"):
        c0, k0 = counters.compile_requests, counters.kernel_traces
        p0 = counters.persistent_hits
        t0 = time.perf_counter()
        res = client.execute(sql)
        out[f"{name}_s"] = time.perf_counter() - t0
        if res.state != "FINISHED":
            raise AssertionError(f"{label} {name}: state {res.state}")
        compare(f"{label} {name}", res.rows, want)
        out[f"{name}_compile_requests"] = counters.compile_requests - c0
        out[f"{name}_persistent_cache_hits"] = \
            counters.persistent_hits - p0
        out[f"{name}_kernel_traces"] = counters.kernel_traces - k0
        out["rows"] = len(res.rows)
    out["matches_host_reference"] = True
    emit(**out)
    return res.rows, out


def rebuild_native_pageserde() -> bool:
    """Delete the git-ignored library and build it again from the
    committed source (trino_tpu/serde.py does the build)."""
    import trino_tpu.serde as serde
    so = os.path.join(os.path.dirname(os.path.abspath(serde.__file__)),
                      "native", "libpageserde.so")
    if os.path.exists(so):
        os.remove(so)
    return bool(serde.native_available())


def start_coordinator():
    from trino_tpu.server.coordinator import Coordinator
    from trino_tpu.server.main import build_catalogs
    return Coordinator(port=0, catalogs=build_catalogs(None, [])).start()


# --------------------------------------------------------------------------
# the two runs
# --------------------------------------------------------------------------

def run_one_chip(args, counters: Counters, failures: list) -> None:
    import jax
    from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
    from trino_tpu.client import StatementClient
    from trino_tpu.ops import pallas_groupby

    emit(native_pageserde=rebuild_native_pageserde())

    t0 = time.perf_counter()
    ref = HostReference(args.scale)
    emit(phase="host_reference", schema=args.scale,
         lineitem_rows=ref.n_lineitem, seconds=time.perf_counter() - t0)

    co = start_coordinator()
    client = StatementClient(co.base_uri, catalog="tpch",
                             schema=args.scale)
    warm_compiles = 0
    q1_kernel_traces = 0
    for q in (6, 1, 3):
        _, out = serve(client, f"q{q}@{args.scale}", TPCH_QUERIES[q],
                       ref.answer(q), counters)
        warm_compiles += out["warm_compile_requests"]
        if q == 1:
            q1_kernel_traces = out["cold_kernel_traces"]

    stats = jax.devices()[0].memory_stats() or {}
    mode = pallas_groupby.mode()
    emit(phase="evidence", pallas_mode=mode,
         q1_kernel_traces=q1_kernel_traces,
         bytes_in_use=stats.get("bytes_in_use"),
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         warm_pass_compile_requests=warm_compiles)
    if mode != "tpu":
        failures.append(f"pallas_mode is {mode!r}, not 'tpu'")
    if q1_kernel_traces < 1:
        failures.append("q1 was served without tracing the kernel")
    if (stats.get("bytes_in_use") or 0) < 16 * ref.n_lineitem:
        failures.append("bytes_in_use shows no resident lanes")
    if warm_compiles:
        failures.append(f"{warm_compiles} compile requests in the "
                        f"warm pass")

    if args.sf10:
        t0 = time.perf_counter()
        ref10 = HostReference("sf10", want=(1,))
        emit(phase="host_reference", schema="sf10",
             lineitem_rows=ref10.n_lineitem,
             seconds=time.perf_counter() - t0)
        c10 = StatementClient(co.base_uri, catalog="tpch",
                              schema="sf10", timeout=1800.0)
        serve(c10, "q1@sf10", TPCH_QUERIES[1], ref10.answer(1),
              counters)
        stats = jax.devices()[0].memory_stats() or {}
        emit(phase="evidence_sf10",
             bytes_in_use=stats.get("bytes_in_use"),
             peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    co.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", default="sf1",
                    help="tpch schema to serve (sf1; tiny for the "
                         "CPU rehearsal)")
    ap.add_argument("--sf10", action="store_true",
                    help="one chip: also serve q1 on tpch.sf10")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk every phase on a platform other than "
                         "tpu; never prints ok nor exits 0")
    args = ap.parse_args(argv)

    import jax
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices())}
    emit(phase="device", device=device, rehearse=args.rehearse)
    if device["platform"] != "tpu" and not args.rehearse:
        print("chip_smoke: no TPU (platform is "
              f"{device['platform']!r}); use --rehearse to walk the "
              "phases without one", file=sys.stderr)
        return 2

    import trino_tpu  # noqa: F401  (x64 + compile cache placement)
    emit(phase="config", jax=jax.__version__,
         compilation_cache_dir=jax.config.jax_compilation_cache_dir,
         x64=bool(jax.config.jax_enable_x64))

    counters = Counters()
    failures: list = []
    run_one_chip(args, counters, failures)

    if args.rehearse:
        emit(phase="rehearsal_done", failed_checks=failures)
        return 3
    if failures:
        emit(phase="failed", failed_checks=failures)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
