"""Server entry point: ``python -m trino_tpu.server.main`` (or the
``trino-tpu-server`` console script).

Reference parity: core/trino-server-main (TrinoServer.java) +
server/Server.java bootstrap + the airlift config loading model:
``etc/config.properties`` (http-server.http.port, coordinator=...),
``etc/catalog/*.properties`` (connector.name=tpch|memory|...) —
metadata/CatalogManager + connector/ConnectorManager analog."""

from __future__ import annotations

import argparse
import os
import signal
import sys
from typing import Dict, Optional


def load_properties(path: str) -> Dict[str, str]:
    """key=value lines, '#' comments (airlift config format)."""
    out: Dict[str, str] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if "=" in line:
                k, _, v = line.partition("=")
                out[k.strip()] = v.strip()
    return out


def build_catalogs(etc_dir: Optional[str],
                   plugins: Optional[list] = None):
    """etc/catalog/*.properties -> CatalogManager via the plugin
    registry (connector.name selects the factory — the reference's
    catalog property files + PluginManager; trino_tpu/plugin.py)."""
    from .. import plugin
    from ..catalog import CatalogManager
    for mod in plugins or []:
        plugin.load_plugin(mod)
    cat_dir = os.path.join(etc_dir, "catalog") if etc_dir else None
    mgr = CatalogManager()
    made = False
    if cat_dir and os.path.isdir(cat_dir):
        for fn in sorted(os.listdir(cat_dir)):
            if not fn.endswith(".properties"):
                continue
            name = fn[:-len(".properties")]
            props = load_properties(os.path.join(cat_dir, fn))
            kind = props.get("connector.name", name)
            try:
                mgr.register(name, plugin.create_connector(
                    kind, name, props))
            except KeyError as e:
                print(f"warning: {e} for catalog {name}",
                      file=sys.stderr)
            made = True
    if not made:
        for kind in ("tpch", "tpcds", "memory", "blackhole",
                     "stream"):
            mgr.register(kind, plugin.create_connector(kind, kind))
    return mgr


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="trino-tpu-server")
    ap.add_argument("--etc-dir", default=None,
                    help="config directory (config.properties + "
                         "catalog/*.properties)")
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--workers", default=None,
                    help="comma-separated worker base URIs to dispatch "
                         "leaf fragments to (exec/remote.py); also "
                         "settable as worker.uris in config.properties")
    ap.add_argument("--role", choices=("coordinator", "worker"),
                    default=None,
                    help="worker starts a task server instead of a "
                         "coordinator (node.role in config.properties; "
                         "the reference's coordinator=true|false). "
                         "Default: coordinator")
    ap.add_argument("--coordinator-uri", default=None,
                    help="[worker role] coordinator to announce this "
                         "worker to (/v1/announcement; re-announced on "
                         "a cadence so a restarted coordinator re-"
                         "learns the fleet). Also discovery.uri in "
                         "config.properties")
    ap.add_argument("--coordinator-token", default=None,
                    help="[worker role] Bearer token sent with every "
                         "announcement — required when the coordinator "
                         "authenticates requests. Also discovery.token "
                         "in config.properties / env "
                         "TRINO_TPU_COORDINATOR_TOKEN")
    ap.add_argument("--prewarm-top-k", type=int, default=None,
                    help="[worker role] how many of the coordinator's "
                         "hot shapes to AOT-compile before advertising "
                         "this worker warm (GET /v1/hotshapes; default "
                         "env TRINO_TPU_PREWARM_TOP_K; pre-warm "
                         "disabled entirely via TRINO_TPU_PREWARM=0 or "
                         "prewarm.enabled=false)")
    ap.add_argument("--task-runners", type=int, default=None,
                    help="[worker role] size of the shared split-"
                         "scheduler runner pool time-slicing all "
                         "concurrent queries' tasks (exec/taskexec.py; "
                         "0 = auto, max(4, 2 x cores)). Also "
                         "task.runner-threads in config.properties / "
                         "env TRINO_TPU_TASK_RUNNERS")
    ap.add_argument("--spool-backend", default=None,
                    help="fault-tolerance spool backend: 'local' "
                         "(directory tree) or 'memory' (object-store "
                         "code path, in-process emulation); also "
                         "spool.backend in config.properties / env "
                         "TRINO_TPU_SPOOL_BACKEND")
    args = ap.parse_args(argv)

    props: Dict[str, str] = {}
    if args.etc_dir:
        cfg = os.path.join(args.etc_dir, "config.properties")
        if os.path.exists(cfg):
            props = load_properties(cfg)
    # plugin.load=<module>[,<module>...] loads external plugin modules
    # before catalogs resolve (server/PluginManager.java)
    plugins = [m for m in props.get("plugin.load", "").split(",") if m]
    port = args.port if args.port is not None else \
        int(props.get("http-server.http.port", "8080"))

    # explicit CLI flag beats config.properties (same precedence as
    # --port/--workers); only an omitted flag falls through to props
    role = args.role or props.get("node.role", "coordinator")
    if role == "worker":
        return _worker_main(args, props, port)

    from .coordinator import Coordinator
    resource_groups = None
    rg_path = props.get("resource-groups.config-file")
    if rg_path:
        import json as _json
        from .resourcegroups import ResourceGroupManager
        with open(rg_path) as f:
            resource_groups = ResourceGroupManager.from_config(
                _json.load(f))
    authenticator = None
    pw_path = props.get("password-authenticator.file")
    if pw_path:
        from ..security import load_password_file
        with open(pw_path) as f:
            authenticator = load_password_file(f.read())

    workers = [w.strip() for w in
               (args.workers or props.get("worker.uris", "")).split(",")
               if w.strip()]

    spool_backend = (args.spool_backend
                     or props.get("spool.backend") or None)

    # cluster memory pool sizing (server/memory.py): config.properties
    # query.max-memory (the reference's property name, accepting its
    # DataSize strings — "50GB" — as well as raw bytes) beats the env
    # default TRINO_TPU_CLUSTER_MEMORY_POOL; None keeps the config
    # default (0 = governance off)
    pool_bytes = None
    if props.get("query.max-memory"):
        from .memory import parse_data_size
        pool_bytes = parse_data_size(props["query.max-memory"])

    co = Coordinator(port=port,
                     catalogs=build_catalogs(args.etc_dir, plugins),
                     resource_groups=resource_groups,
                     authenticator=authenticator,
                     worker_uris=workers,
                     spool_backend=spool_backend,
                     memory_pool_bytes=pool_bytes).start()
    if workers and co.failure_detector is not None:
        # a configured fleet gets the active heartbeat loop on top of
        # the scheduler's task-failure feedback
        co.failure_detector.start()
    print(f"trino-tpu coordinator listening on {co.base_uri}"
          f" (web UI: {co.base_uri}/ui)")
    _announce_fault_points()

    stop = {"flag": False}

    def on_signal(sig, frame):
        print("draining...", file=sys.stderr)
        co.drain(timeout=30.0)
        stop["flag"] = True

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    import time
    while not stop["flag"]:
        time.sleep(0.2)
    return 0


def _announce_fault_points() -> None:
    """Startup banner for TRINO_TPU_FAULTPOINTS (fte/faultpoints.py):
    an armed fault schedule changes what this process will do — an
    operator reading the log must see it, and a malformed spec must
    fail LOUDLY at boot instead of silently arming nothing."""
    spec = os.environ.get("TRINO_TPU_FAULTPOINTS", "").strip()
    if not spec:
        return
    from ..fte.faultpoints import armed_sites, parse_schedule
    parse_schedule(spec)     # raises ValueError on a malformed spec
    armed = armed_sites()
    print("FAULT INJECTION ARMED (TRINO_TPU_FAULTPOINTS): "
          + ", ".join(f"{site}={action}"
                      for site, action in sorted(armed.items())),
          file=sys.stderr)


def _worker_main(args, props: Dict[str, str], port: int) -> int:
    """Worker role: a TaskWorkerServer that joins a coordinator's
    worker set at runtime (/v1/announcement) — the elastic half of the
    cluster. Start any number of these against one coordinator; each
    announces itself now and on a cadence, so a RESTARTED coordinator
    re-learns the fleet at the next beat, and stop() sends the
    graceful leave."""
    from .task_worker import TaskWorkerServer
    spool_backend = (args.spool_backend
                     or props.get("spool.backend") or None)
    plugins = [m for m in props.get("plugin.load", "").split(",") if m]
    task_runners = args.task_runners
    if task_runners is None and props.get("task.runner-threads"):
        task_runners = int(props["task.runner-threads"])
    srv = TaskWorkerServer(
        port=port, spool_backend=spool_backend,
        task_runners=task_runners,
        # the worker resolves the same etc/catalog configs the
        # coordinator dispatches fragments against — without this a
        # fragment naming an operator-configured catalog fails on
        # every attempt
        catalogs=build_catalogs(args.etc_dir, plugins)).start()
    coordinator_uri = (args.coordinator_uri
                       or props.get("discovery.uri") or None)
    token = (args.coordinator_token or props.get("discovery.token")
             or os.environ.get("TRINO_TPU_COORDINATOR_TOKEN") or None)
    if coordinator_uri:
        from ..config import CONFIG
        prewarm = CONFIG.prewarm_enabled
        if props.get("prewarm.enabled", "").lower() in ("false", "0"):
            prewarm = False
        top_k = args.prewarm_top_k
        if top_k is None and props.get("prewarm.top-k"):
            top_k = int(props["prewarm.top-k"])
        joined = srv.announce(coordinator_uri, token=token,
                              prewarm=prewarm,
                              prewarm_top_k=top_k)
        print(f"trino-tpu worker {srv.node_id} on {srv.base_uri} "
              f"({'joined' if joined else 'announcing to'} "
              f"{coordinator_uri}"
              + (", pre-warming hot shapes" if prewarm else "") + ")")
    else:
        print(f"trino-tpu worker {srv.node_id} on {srv.base_uri} "
              "(standalone: pass --coordinator-uri to join a cluster)")
    _announce_fault_points()

    stop = {"flag": False}

    def on_signal(sig, frame):
        srv.stop()               # graceful leave + server shutdown
        stop["flag"] = True

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    import time
    while not stop["flag"]:
        time.sleep(0.2)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
