"""The system under test, and the only file of the benchmark that
imports the program: a coordinator started as
``trino_tpu/server/main.py`` starts one (default catalogs, default
``CONFIG``, default session properties, no workers, so every query
executes in this process on the default device), reached over HTTP with
the program's own ``StatementClient``; its engine spans at
``/v1/trace/{query_id}`` and its counters at ``/metrics``.
"""

import json
import os
import re
import shutil
import time
import urllib.request

ROOT_SPANS = ("parse", "plan", "optimize", "execute")


class Engine:
    def __init__(self, catalog: str, schema: str, state_dir: str):
        import trino_tpu  # noqa: F401  (x64, compile cache placement)
        from trino_tpu.server.coordinator import Coordinator
        from trino_tpu.server.main import build_catalogs
        # query history and learned statistics of an earlier run would
        # make this run's plans depend on it: every run starts empty
        shutil.rmtree(state_dir, ignore_errors=True)
        os.makedirs(state_dir, exist_ok=True)
        self.catalog, self.schema = catalog, schema
        self.co = Coordinator(port=0, catalogs=build_catalogs(None, []),
                              history_dir=state_dir).start()
        self._clients = {}

    def client(self, stream):
        from trino_tpu.client import StatementClient
        if stream not in self._clients:
            self._clients[stream] = StatementClient(
                self.co.base_uri, catalog=self.catalog, schema=self.schema,
                timeout=1800.0)
        return self._clients[stream]

    def stop(self) -> None:
        self.co.stop()

    def _get(self, path: str) -> bytes:
        with urllib.request.urlopen(self.co.base_uri + path,
                                    timeout=60) as resp:
            return resp.read()

    def root_spans(self, query_id: str):
        """{name: (start_unix_ns, end_unix_ns)} of the engine's root
        spans of one finished query, or None where it has no trace."""
        try:
            doc = json.loads(self._get(f"/v1/trace/{query_id}"))
        except Exception:       # noqa: BLE001 — 404: untraced query
            return None
        out = {}
        for rs in doc.get("resourceSpans", []):
            for ss in rs.get("scopeSpans", []):
                for s in ss.get("spans", []):
                    if s["name"] in ROOT_SPANS and not s.get("parentSpanId"):
                        out[s["name"]] = (int(s["startTimeUnixNano"]),
                                          int(s["endTimeUnixNano"]))
        return out or None

    def scans(self, sql: str, stream="scans"):
        """[{table, rows, lanes}] of the table scans of one statement,
        in plan order, from the program's ``EXPLAIN ANALYZE``: the rows
        each scan DELIVERED (after the conjuncts pushed into it) and,
        where the scan cache missed and filled, the lanes it filled
        (the ``scan_fill`` span; ``None`` on a hit)."""
        res = self.client(stream).execute("EXPLAIN ANALYZE " + sql)
        text = [line for r in res.rows for line in r[0].splitlines()]
        tables = [m.group(1) for line in text if
                  (m := re.search(r"- TableScan\[[\w$]+\.[\w$]+\.([\w$]+)",
                                  line))]
        rows = [int(m.group(1)) for line in text if
                (m := re.match(r"TableScan: .* out (\d+) rows", line))]
        lanes = {m.group(2): int(m.group(1)) for line in text if
                 (m := re.search(r"scan_fill: .* lanes=(\d+), table=(\w+)",
                                 line))}
        if len(tables) != len(rows):
            raise ValueError(f"EXPLAIN ANALYZE names {len(tables)} scans "
                             f"and gives statistics for {len(rows)}")
        return [{"table": t, "rows": n, "lanes": lanes.get(t)}
                for t, n in zip(tables, rows)]

    def counters(self) -> dict:
        """{'name{labels}': value} of every sample on ``/metrics``."""
        out = {}
        for line in self._get("/metrics").decode().splitlines():
            m = re.match(r"^([a-zA-Z_:][^ ]*) ([-+0-9.eE]+|NaN)$", line)
            if m and not line.startswith("#"):
                out[m.group(1)] = float(m.group(2))
        return out


class _Answer:
    state = "FINISHED"

    def __init__(self, rows, query_id):
        self.rows, self.query_id = rows, query_id


class ControlEngine:
    """The output check's control: a reference (computed one precision
    below the configuration's) answers in the program's place, from
    ``rows`` (the text of a query -> its rows). It serves no table, has
    no spans and no counters."""

    def __init__(self, rows: dict):
        self._rows = rows
        self._n = 0

    def client(self, _stream):
        return self

    def execute(self, text: str):
        self._n += 1
        time.sleep(0.001)       # a window of thousands, not millions
        return _Answer(self._rows[text], f"control_{self._n}")

    def root_spans(self, _query_id):
        return None

    def counters(self) -> dict:
        return {}

    def stop(self) -> None:
        pass


def device_info() -> dict:
    import jax
    devs = jax.local_devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip; 0 where the backend
    reports none (the CPU rehearsal)."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
