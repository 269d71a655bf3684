"""LocalQueryRunner: in-process parse -> plan -> optimize -> execute.

Reference parity: core/trino-main/.../testing/LocalQueryRunner.java:220
(994 loc) — full query execution in one process, no RPC, pluggable
catalogs — plus the DDL/utility statement dispatch that the reference
routes through execution/*Task.java (SetSessionTask, CreateTableTask,
ShowQueriesRewrite for SHOW statements).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .catalog import (CatalogManager, ColumnMetadata, TableHandle,
                      TableMetadata)
from .columnar import Batch, batch_from_pylist
from .connectors.memory import BlackholeConnector, MemoryConnector
from .connectors.tpcds import TpcdsConnector
from .connectors.tpch import TpchConnector
from .exec import Executor, QueryError
from .functions import list_functions
from .obs.trace import null_span
from .plan.nodes import OutputNode, plan_tree_lines
from .planner import LogicalPlanner, PlanningError
from .planner.optimizer import optimize
from .session import SESSION_PROPERTIES, Session
from .sql import ast as A
from .sql.parser import parse_statement
from .sql.tokenizer import ParseError
from .types import Type, VARCHAR, BIGINT, parse_type


@dataclass
class QueryResult:
    """Client-facing result (reference: client QueryResults payload,
    Appendix B.1) plus the telemetry captured while producing it
    (per-node stats, the span tree, the plan rendering — the inputs of
    /v1/query/{id} and EXPLAIN ANALYZE)."""
    columns: List[str]
    types: List[Type]
    rows: List[list]
    query_id: str = ""
    wall_s: float = 0.0
    update_type: Optional[str] = None
    update_count: Optional[int] = None
    stats: Optional[list] = None            # List[NodeStats]
    plan_lines: Optional[List[str]] = None  # captured at execution time
    trace: Optional[object] = None          # obs.trace.QueryTrace
    peak_memory_bytes: int = 0
    spill_bytes: int = 0
    # canonical plan key (exec/learnedstats.py plan_key_for): the
    # identity the query-history store and the learned-stats registry
    # share — renamed/reordered plans of one structural program match
    plan_key: str = ""

    def __iter__(self):
        return iter(self.rows)

    def __len__(self):
        return len(self.rows)


class LocalQueryRunner:
    """In-process runner; with ``distributed=True`` executes over the
    device mesh (the DistributedQueryRunner analog — N mesh devices play
    the N workers, SURVEY.md §4 tier 2)."""

    def __init__(self, session: Optional[Session] = None,
                 with_tpch: bool = True, distributed: bool = False,
                 n_devices: Optional[int] = None,
                 catalogs: Optional[CatalogManager] = None,
                 mesh=None, collect_node_stats: bool = False):
        # per-node wall/row stats on every query (OperatorStats is
        # always-on in the reference); a served query's row counts come
        # back in one read at the end of execute, only EXPLAIN ANALYZE
        # fences each node
        self.collect_node_stats = collect_node_stats
        if catalogs is not None:
            self.catalogs = catalogs
        else:
            self.catalogs = CatalogManager()
            if with_tpch:
                self.catalogs.register("tpch", TpchConnector())
                self.catalogs.register("tpcds", TpcdsConnector())
            self.catalogs.register("memory", MemoryConnector())
            self.catalogs.register("blackhole", BlackholeConnector())
            from .connectors.system import SystemConnector
            self.catalogs.register("system", SystemConnector())
            # disk-backed (CONFIG.stream_dir), so unlike memory this
            # default genuinely shares state with worker processes
            from .connectors.stream import StreamConnector
            self.catalogs.register("stream", StreamConnector())
        self.session = session or Session(catalog="tpch", schema="tiny")
        self.mesh = mesh
        # engine transaction state (reference:
        # transaction/InMemoryTransactionManager — per-catalog
        # copy-on-begin, restore-on-rollback)
        self._txn_snapshot = None
        if distributed and self.mesh is None:
            from .parallel.mesh import get_mesh
            self.mesh = get_mesh(n_devices)

    def _make_executor(self, collect_stats: bool = False) -> Executor:
        if self.mesh is not None:
            from .exec.distributed import DistributedExecutor
            return DistributedExecutor(self.catalogs, self.session,
                                       self.mesh, collect_stats)
        return Executor(self.catalogs, self.session, collect_stats)

    # ------------------------------------------------------------------
    def execute(self, sql: str) -> QueryResult:
        from .obs import adopt_or_mint
        from .obs.metrics import (QUERY_PEAK_MEMORY_BYTES,
                                  QUERY_WALL_SECONDS)
        t0 = time.perf_counter()
        prev_trace = self.session.trace
        trace, adopted = adopt_or_mint(self.session,
                                       self.collect_node_stats)
        sp = trace.span if trace is not None else null_span
        self.session.trace = trace
        # deadline derivation for standalone runs: the coordinator's
        # tracker stamps session.deadline before dispatch; a runner
        # used directly derives it here so query_max_run_time is
        # enforced (executor checks between plan nodes) without a
        # tracker above it
        owned_deadline = False
        if self.session.deadline is None:
            limit = int(self.session.get("query_max_run_time") or 0)
            if limit > 0:
                self.session.deadline = time.monotonic() + limit
                owned_deadline = True
        try:
            try:
                with sp("parse"):
                    stmt = parse_statement(sql)
            except ParseError as e:
                raise QueryError(f"SYNTAX_ERROR: {e}") from e
            # a coordinator-stamped id (QueryTracker.submit) wins so
            # split events and spans correlate with /v1/query entries;
            # it is consumed here — a reused standalone session mints a
            # fresh runner-local id per query
            qid = self.session.query_id or self.session.next_query_id()
            self.session.query_id = qid
            if trace is not None and not adopted:
                trace.query_id = qid
            try:
                result = self._dispatch(stmt, sql)
            except PlanningError as e:
                raise QueryError(str(e)) from e
            except KeyError as e:
                raise QueryError(str(e).strip('"')) from e
        finally:
            self.session.trace = prev_trace
            self.session.query_id = ""
            if owned_deadline:
                self.session.deadline = None
            # observed for failed/canceled queries too — the slowest
            # queries are exactly the ones that time out, and a latency
            # histogram that drops them reads optimistic at p99
            QUERY_WALL_SECONDS.observe(time.perf_counter() - t0)
            # OTLP export (obs/otlp.py): best-effort, sink-configured
            # — in the finally so failed queries' traces export too
            if trace is not None and not adopted and trace.roots:
                from .obs.otlp import maybe_export
                maybe_export(trace, session=self.session)
        result.query_id = qid
        result.wall_s = time.perf_counter() - t0
        result.trace = trace
        QUERY_PEAK_MEMORY_BYTES.set(result.peak_memory_bytes)
        return result

    # ------------------------------------------------------------------
    def execute_batch(self, sql: str):
        """Run a query, returning the raw result Batch (the task-worker
        data plane serializes it into page frames — server/
        task_worker.py; the reference's TaskOutputOperator hands Pages
        to the output buffer rather than JSON rows)."""
        stmt = parse_statement(sql)
        if not isinstance(stmt, A.QueryStatement):
            raise QueryError("execute_batch supports queries only")
        planner = LogicalPlanner(self.catalogs, self.session)
        plan = optimize(planner.plan(stmt), self.catalogs, self.session)
        batch = self._make_executor(False).execute(plan)
        # wire format carries DISPLAY column names, not plan symbols;
        # repeated names are disambiguated positionally (the frame is
        # keyed by name, unlike the reference's positional wire pages)
        cols = {}
        for i, (name, sym) in enumerate(zip(plan.names, plan.symbols)):
            key = name if name not in cols else f"{name}${i}"
            cols[key] = batch.column(sym)
        return Batch(cols, batch.num_rows)

    # ------------------------------------------------------------------
    def plan_sql(self, sql: str, optimized: bool = True) -> OutputNode:
        stmt = parse_statement(sql)
        if isinstance(stmt, A.Explain):
            stmt = stmt.statement
        if not isinstance(stmt, A.QueryStatement):
            raise QueryError("only queries can be planned")
        planner = LogicalPlanner(self.catalogs, self.session)
        plan = planner.plan(stmt)
        return optimize(plan, self.catalogs, self.session) \
            if optimized else plan

    # ------------------------------------------------------------------
    def _dispatch(self, stmt: A.Statement, sql: str = "") -> QueryResult:
        if isinstance(stmt, A.QueryStatement):
            return self._run_query(stmt,
                                   collect_stats=self.collect_node_stats)
        if isinstance(stmt, A.CreateView):
            return self._create_view(stmt, sql)
        if isinstance(stmt, A.DropView):
            cat, schema, name = self._qualify(stmt.name)
            if not self.catalogs.drop_view(cat, schema, name) \
                    and not stmt.if_exists:
                raise QueryError(
                    f"View '{cat}.{schema}.{name}' does not exist")
            return _msg_result("DROP VIEW")
        if isinstance(stmt, A.ShowCreate):
            return self._show_create(stmt)
        if isinstance(stmt, A.ShowStats):
            return self._show_stats(stmt)
        if isinstance(stmt, A.Describe):
            return self._dispatch(A.ShowColumns(stmt.table))
        if isinstance(stmt, A.Prepare):
            self.session.prepared[stmt.name] = stmt.statement
            return _msg_result("PREPARE")
        if isinstance(stmt, A.Deallocate):
            if stmt.name not in self.session.prepared:
                raise QueryError(
                    f"Prepared statement not found: {stmt.name}")
            del self.session.prepared[stmt.name]
            return _msg_result("DEALLOCATE")
        if isinstance(stmt, A.ExecuteStmt):
            return self._execute_prepared(stmt)
        if isinstance(stmt, A.DescribeInput):
            prep = self._prepared(stmt.name)
            n = A.count_parameters(prep)
            return QueryResult(["Position", "Type"], [BIGINT, VARCHAR],
                               [[i, "unknown"] for i in range(n)])
        if isinstance(stmt, A.DescribeOutput):
            prep = self._prepared(stmt.name)
            if not isinstance(prep, A.QueryStatement):
                return QueryResult(["Column Name", "Type"],
                                   [VARCHAR, VARCHAR], [])
            # bind dummy NULLs for parameters so the query plans
            n = A.count_parameters(prep)
            bound, _ = A.replace_parameters(
                prep, [A.Literal(None)] * n)
            planner = LogicalPlanner(self.catalogs, self.session)
            plan = planner.plan(bound)
            schema = plan.output_schema()
            return QueryResult(
                ["Column Name", "Type"], [VARCHAR, VARCHAR],
                [[name, str(schema[s])]
                 for name, s in zip(plan.names, plan.symbols)])
        if isinstance(stmt, A.CallStatement):
            parts = tuple(p.lower() for p in stmt.name)
            if len(parts) != 3:
                raise QueryError(
                    "CALL requires catalog.schema.procedure")
            cat, schema, proc = parts
            planner = LogicalPlanner(self.catalogs, self.session)
            args = [planner._const_expr(a).value for a in stmt.args]
            conn = self.catalogs.connector(cat)
            try:
                conn.call_procedure(schema, proc, args)
            except (KeyError, ValueError) as e:
                raise QueryError(str(e).strip('"')) from e
            return _msg_result("CALL")
        if isinstance(stmt, A.StartTransaction):
            if self._txn_snapshot is not None:
                raise QueryError("Nested transactions not supported")
            self._txn_snapshot = {
                name: self.catalogs.connector(name).snapshot_state()
                for name in self.catalogs.list_catalogs()}
            return _msg_result("START TRANSACTION")
        if isinstance(stmt, A.Commit):
            if self._txn_snapshot is None:
                raise QueryError("No transaction in progress")
            self._txn_snapshot = None
            return _msg_result("COMMIT")
        if isinstance(stmt, A.Rollback):
            if self._txn_snapshot is None:
                raise QueryError("No transaction in progress")
            for name, snap in self._txn_snapshot.items():
                if snap is not None:
                    self.catalogs.connector(name).restore_state(snap)
            self._txn_snapshot = None
            return _msg_result("ROLLBACK")
        if isinstance(stmt, A.Explain):
            return self._explain(stmt)
        if isinstance(stmt, A.UseStatement):
            if stmt.catalog:
                self.catalogs.connector(stmt.catalog)  # validate
                self.session.catalog = stmt.catalog
            self.session.schema = stmt.schema
            return _msg_result("USE")
        if isinstance(stmt, A.SetSession):
            planner = LogicalPlanner(self.catalogs, self.session)
            v = planner._const_expr(stmt.value).value
            self.session.set(stmt.name.split(".")[-1], v)
            return _msg_result("SET SESSION")
        if isinstance(stmt, A.ResetSession):
            self.session.reset(stmt.name.split(".")[-1])
            return _msg_result("RESET SESSION")
        if isinstance(stmt, A.ShowCatalogs):
            rows = [[c] for c in self.catalogs.list_catalogs()]
            return QueryResult(["Catalog"], [VARCHAR], rows)
        if isinstance(stmt, A.ShowSchemas):
            cat = stmt.catalog or self.session.catalog
            conn = self.catalogs.connector(cat)
            return QueryResult(["Schema"], [VARCHAR],
                               [[s] for s in conn.list_schemas()])
        if isinstance(stmt, A.ShowTables):
            cat = self.session.catalog
            schema = self.session.schema
            if stmt.schema:
                parts = stmt.schema
                if len(parts) == 2:
                    cat, schema = parts
                else:
                    schema = parts[0]
            conn = self.catalogs.connector(cat)
            tables = sorted(set(conn.list_tables(schema))
                            | set(self.catalogs.list_views(cat,
                                                           schema)))
            if stmt.like:
                import re
                from .exec.expr import like_to_regex
                rx = re.compile(like_to_regex(stmt.like))
                tables = [t for t in tables if rx.fullmatch(t)]
            return QueryResult(["Table"], [VARCHAR], [[t] for t in tables])
        if isinstance(stmt, A.ShowColumns):
            cat, schema, table = self._qualify(stmt.table)
            conn = self.catalogs.connector(cat)
            meta = conn.get_table_metadata(schema, table)
            if meta is None:
                raise QueryError(
                    f"Table '{cat}.{schema}.{table}' does not exist")
            rows = [[c.name, c.type.name, "", ""] for c in meta.columns]
            return QueryResult(["Column", "Type", "Extra", "Comment"],
                               [VARCHAR] * 4, rows)
        if isinstance(stmt, A.ShowSession):
            rows = [[k, str(self.session.get(k)).lower(), str(d).lower()]
                    for k, (_, d) in sorted(SESSION_PROPERTIES.items())]
            return QueryResult(["Name", "Value", "Default"],
                               [VARCHAR] * 3, rows)
        if isinstance(stmt, A.ShowFunctions):
            return QueryResult(["Function"], [VARCHAR],
                               [[f] for f in list_functions()])
        if isinstance(stmt, (A.Grant, A.Revoke, A.Deny)):
            return self._grant_revoke(stmt)
        if isinstance(stmt, A.ShowGrants):
            return self._show_grants(stmt)
        if isinstance(stmt, A.CreateTable):
            return self._create_table(stmt)
        if isinstance(stmt, A.DropTable):
            cat, schema, table = self._qualify(stmt.name)
            self._check_access("drop_table", cat, schema, table)
            conn = self.catalogs.connector(cat)
            if conn.get_table_metadata(schema, table) is None:
                if stmt.if_exists:
                    return _msg_result("DROP TABLE")
                raise QueryError(
                    f"Table '{cat}.{schema}.{table}' does not exist")
            conn.drop_table(schema, table)
            return _msg_result("DROP TABLE")
        if isinstance(stmt, A.Insert):
            return self._insert(stmt)
        if isinstance(stmt, A.Delete):
            return self._delete(stmt)
        if isinstance(stmt, A.Update):
            return self._update(stmt)
        if isinstance(stmt, A.Merge):
            return self._merge_stmt(stmt)
        raise QueryError(
            f"statement {type(stmt).__name__} not supported")

    # ------------------------------------------------------------------
    def _run_query(self, stmt: A.QueryStatement,
                   collect_stats: bool = False):
        trace = self.session.trace
        sp = (trace.span if trace is not None else null_span)
        with sp("plan"):
            planner = LogicalPlanner(self.catalogs, self.session)
            plan = planner.plan(stmt)
        with sp("optimize"):
            plan = optimize(plan, self.catalogs, self.session)
        ex = self._make_executor(collect_stats)
        with sp("execute"):
            batch = ex.execute(plan)
        with sp("fetch"):
            # the device-to-host fetch of the result rows
            schema = batch.schema()
            types = [schema[s] for s in plan.symbols]
            rows = batch.to_pylist()
            result = QueryResult(list(plan.names), types, rows)
        # the rendering /v1/query/{id} serves — captured HERE so the
        # detail endpoint never re-plans the query (and never silently
        # diverges from what actually ran)
        result.plan_lines = plan_tree_lines(plan)
        result.peak_memory_bytes = getattr(ex, "peak_reserved_bytes", 0)
        result.spill_bytes = getattr(ex, "spilled_bytes", 0)
        result.ragged_batched = getattr(ex, "ragged_batched", 0)
        if collect_stats:
            result.stats = ex.stats
            # learned operator statistics (exec/learnedstats.py): this
            # LOCAL execution's observed rows-in/rows-out feed the
            # selectivity/throughput EMAs under the plan's canonical
            # key — dispatched fragments report theirs via worker
            # task-status deltas instead, so nothing double-counts
            from .exec.learnedstats import (plan_key_for,
                                            record_node_stats)
            result.plan_key = plan_key_for(plan)
            try:
                record_node_stats(result.plan_key, ex.stats,
                                  self.session)
            except Exception:   # noqa: BLE001 — telemetry best-effort
                pass
        return result

    def _explain(self, stmt: A.Explain) -> QueryResult:
        from .exec.executor import render_analyze_lines
        inner = stmt.statement
        if not isinstance(inner, A.QueryStatement):
            raise QueryError("EXPLAIN supports queries only")
        if stmt.analyze:
            # EXPLAIN ANALYZE always traces, even on a runner whose
            # normal queries don't collect telemetry. The rendered plan
            # is the one _run_query captured — the plan that actually
            # ran, with no second plan+optimize pass
            trace = self.session.trace
            owned = trace is None
            if owned:
                from .obs import adopt_or_mint
                trace, _ = adopt_or_mint(self.session, True,
                                         self.session.query_id)
                self.session.trace = trace
            # the explicit analysis: each program waited for and timed
            # (device_execute, device_ms), each plan node fenced
            trace.analyze = True
            try:
                res = self._run_query(inner, collect_stats=True)
            finally:
                if owned:
                    self.session.trace = None
            lines = render_analyze_lines(res.plan_lines, res.stats,
                                         trace)
            return QueryResult(["Query Plan"], [VARCHAR],
                               [[l] for l in lines])
        planner = LogicalPlanner(self.catalogs, self.session)
        plan = optimize(planner.plan(inner), self.catalogs,
                        self.session)
        return QueryResult(["Query Plan"], [VARCHAR],
                           [[l] for l in plan_tree_lines(plan)])

    def _create_view(self, stmt: A.CreateView, sql: str) -> QueryResult:
        from .catalog import ViewDefinition
        cat, schema, name = self._qualify(stmt.name)
        self.catalogs.connector(cat)  # validate catalog
        # validate the definition by planning it now (reference:
        # CreateViewTask analyzes the view query)
        planner = LogicalPlanner(self.catalogs, self.session)
        planner.plan(A.QueryStatement(stmt.query))
        try:
            self.catalogs.create_view(
                cat, schema, name, ViewDefinition(stmt.query, sql),
                replace=stmt.replace)
        except KeyError as e:
            raise QueryError(str(e).strip('"')) from e
        return _msg_result("CREATE VIEW")

    def _resolve_table(self, parts) -> Tuple[str, str, str]:
        cat, schema, name = self._qualify(parts)
        conn = self.catalogs.connector(cat)
        if conn.get_table_metadata(schema, name) is None \
                and self.catalogs.get_view(cat, schema, name) is None:
            raise QueryError(
                f"Table '{cat}.{schema}.{name}' does not exist")
        return cat, schema, name

    def _grant_revoke(self, stmt) -> QueryResult:
        """GRANT / REVOKE / DENY on an engine-level grant store
        (reference: execution/{GrantTask,RevokeTask,DenyTask}.java; the
        reference routes to connector metadata, ours is engine-scoped
        so every connector supports grants)."""
        cat, schema, name = self._resolve_table(stmt.table)
        store = self.catalogs.grants
        if isinstance(stmt, A.Grant):
            for p in stmt.privileges:
                key = (stmt.grantee, p, cat, schema, name)
                store[key] = stmt.grant_option or store.get(key, False)
            return _msg_result("GRANT")
        if isinstance(stmt, A.Deny):
            for p in stmt.privileges:
                self.catalogs.denies.add(
                    (stmt.grantee, p, cat, schema, name))
            return _msg_result("DENY")
        for p in stmt.privileges:
            key = (stmt.grantee, p, cat, schema, name)
            if stmt.grant_option_for:
                if key in store:
                    store[key] = False
            else:
                store.pop(key, None)
                self.catalogs.denies.discard(key)
        return _msg_result("REVOKE")

    def _show_grants(self, stmt: "A.ShowGrants") -> QueryResult:
        """SHOW GRANTS [ON t] — information_schema.table_privileges
        shape (reference: ShowQueriesRewrite + TablePrivilegeInfo)."""
        from .types import BOOLEAN as _B
        flt = None
        if stmt.table is not None:
            flt = self._resolve_table(stmt.table)
        rows = []
        for (grantee, p, cat, schema, name), opt in sorted(
                self.catalogs.grants.items()):
            if flt is not None and (cat, schema, name) != flt:
                continue
            rows.append([self.session.user or "admin", "USER", grantee,
                         "USER", cat, schema, name, p.upper(), opt,
                         None])
        return QueryResult(
            ["Grantor", "Grantor Type", "Grantee", "Grantee Type",
             "Catalog", "Schema", "Table", "Privilege", "Grantable",
             "With Hierarchy"],
            [VARCHAR] * 8 + [_B, _B], rows)

    def _show_stats(self, stmt: "A.ShowStats") -> QueryResult:
        """SHOW STATS FOR table (reference: sql/rewrite/
        ShowStatsRewrite.java) — one row per column from the
        connector's ColumnStatistics plus the row-count summary row."""
        from .types import DOUBLE
        cat, schema, name = self._qualify(stmt.table)
        conn = self.catalogs.connector(cat)
        meta = conn.get_table_metadata(schema, name)
        if meta is None:
            raise QueryError(
                f"Table '{cat}.{schema}.{name}' does not exist")
        handle = TableHandle(cat, schema, name)
        rows_est = conn.table_row_count(handle)
        out = []
        for c in meta.columns:
            cs = conn.column_statistics(handle, c.name)
            if cs is None:
                out.append([c.name, None, None, None, None, None,
                            None])
                continue
            fmt = (lambda v: None if v is None else str(v))
            out.append([c.name, None, float(cs.ndv),
                        float(cs.null_fraction), None,
                        fmt(cs.min_value), fmt(cs.max_value)])
        out.append([None, None, None, None,
                    None if rows_est is None else float(rows_est),
                    None, None])
        return QueryResult(
            ["column_name", "data_size", "distinct_values_count",
             "nulls_fraction", "row_count", "low_value", "high_value"],
            [VARCHAR, DOUBLE, DOUBLE, DOUBLE, DOUBLE, VARCHAR,
             VARCHAR], out)

    def _show_create(self, stmt: A.ShowCreate) -> QueryResult:
        cat, schema, name = self._qualify(stmt.name)
        if stmt.kind == "view":
            view = self.catalogs.get_view(cat, schema, name)
            if view is None:
                raise QueryError(
                    f"View '{cat}.{schema}.{name}' does not exist")
            return QueryResult(["Create View"], [VARCHAR],
                               [[view.sql or f"CREATE VIEW "
                                 f"{cat}.{schema}.{name} AS ..."]])
        conn = self.catalogs.connector(cat)
        meta = conn.get_table_metadata(schema, name)
        if meta is None:
            raise QueryError(
                f"Table '{cat}.{schema}.{name}' does not exist")
        cols = ",\n   ".join(f"{c.name} {c.type}" for c in meta.columns)
        return QueryResult(
            ["Create Table"], [VARCHAR],
            [[f"CREATE TABLE {cat}.{schema}.{name} (\n   {cols}\n)"]])

    def _prepared(self, name: str):
        """Prepared statement by name; header-carried entries are SQL
        text (X-Trino-Prepared-Statement) and parse lazily."""
        prep = self.session.prepared.get(name)
        if prep is None:
            raise QueryError(f"Prepared statement not found: {name}")
        if isinstance(prep, str):
            prep = parse_statement(prep)
        return prep

    def _execute_prepared(self, stmt: A.ExecuteStmt) -> QueryResult:
        prep = self._prepared(stmt.name)
        planner = LogicalPlanner(self.catalogs, self.session)
        values = []
        for p in stmt.params:
            c = planner._const_expr(p)
            lit = A.Literal(c.value)
            values.append(lit)
        try:
            bound, used = A.replace_parameters(prep, values)
        except ValueError as e:
            raise QueryError(str(e)) from e
        if used < len(values):
            raise QueryError(
                f"statement takes {used} parameters but "
                f"{len(values)} were given")
        return self._dispatch(bound)

    def _create_table(self, stmt: A.CreateTable) -> QueryResult:
        cat, schema, table = self._qualify(stmt.name)
        self._check_access("create_table", cat, schema, table)
        conn = self.catalogs.connector(cat)
        if conn.get_table_metadata(schema, table) is not None:
            if stmt.if_not_exists:
                return _msg_result("CREATE TABLE")
            raise QueryError(
                f"Table '{cat}.{schema}.{table}' already exists")
        if stmt.query is not None:
            res = self._run_query(A.QueryStatement(stmt.query))
            cols = tuple(ColumnMetadata(n, t)
                         for n, t in zip(res.columns, res.types))
            conn.create_table(TableMetadata(schema, table, cols))
            data = {c.name: [row[i] for row in res.rows]
                    for i, c in enumerate(cols)}
            batch = batch_from_pylist(
                data, {c.name: c.type for c in cols})
            n = conn.insert(schema, table, batch)
            return _msg_result("CREATE TABLE AS", n)
        cols = tuple(ColumnMetadata(c.name.lower(), parse_type(c.type_name))
                     for c in stmt.columns)
        conn.create_table(TableMetadata(schema, table, cols))
        return _msg_result("CREATE TABLE")

    def _insert(self, stmt: A.Insert) -> QueryResult:
        cat, schema, table = self._qualify(stmt.table)
        self._check_access("insert", cat, schema, table)
        conn = self.catalogs.connector(cat)
        meta = conn.get_table_metadata(schema, table)
        if meta is None:
            raise QueryError(
                f"Table '{cat}.{schema}.{table}' does not exist")
        res = self._run_query(A.QueryStatement(stmt.query))
        target_cols = (list(stmt.columns) if stmt.columns
                       else [c.name for c in meta.columns
                             if not c.hidden])
        if len(res.columns) != len(target_cols):
            raise QueryError(
                f"INSERT has {len(res.columns)} columns but table "
                f"expects {len(target_cols)}")
        data = {}
        for tgt, i in zip(target_cols, range(len(target_cols))):
            data[tgt] = [row[i] for row in res.rows]
        schema_map = {c: meta.column_type(c) for c in target_cols}
        batch = batch_from_pylist(data, schema_map)
        n = conn.insert(schema, table, batch)
        return _msg_result("INSERT", n)

    def _delete(self, stmt: A.Delete) -> QueryResult:
        """DELETE as survivor rewrite (reference: plan/TableDeleteNode +
        connector delete; the memory connector swaps contents)."""
        cat, schema, table = self._qualify(stmt.table)
        self._check_access("delete", cat, schema, table)
        conn = self.catalogs.connector(cat)
        meta = conn.get_table_metadata(schema, table)
        if meta is None:
            raise QueryError(
                f"Table '{cat}.{schema}.{table}' does not exist")
        if not hasattr(conn, "replace"):
            raise QueryError(f"{conn.name}: DELETE not supported")
        total = conn.table_row_count(
            TableHandle(cat, schema, table)) or 0
        if stmt.where is None:
            from .columnar import empty_batch
            conn.replace(schema, table, empty_batch(
                {c.name: c.type for c in meta.columns}))
            return _msg_result("DELETE", int(total))
        # survivors: rows where the predicate is not TRUE (3VL)
        survivors = self._run_query(A.QueryStatement(A.Query(
            A.QuerySpecification(
                tuple(A.SelectItem(A.Identifier((c.name,)), c.name)
                      for c in meta.columns),
                from_=A.Table((cat, schema, table)),
                where=A.UnaryOp(
                    "not", A.FunctionCall(
                        "coalesce", (stmt.where,
                                     A.Literal(False))))))))
        data = {c.name: [row[i] for row in survivors.rows]
                for i, c in enumerate(meta.columns)}
        batch = batch_from_pylist(
            data, {c.name: c.type for c in meta.columns})
        conn.replace(schema, table, batch)
        return _msg_result("DELETE", int(total) - len(survivors.rows))

    def _writable_meta(self, cat: str, schema: str, table: str,
                       what: str):
        conn = self.catalogs.connector(cat)
        meta = conn.get_table_metadata(schema, table)
        if meta is None:
            raise QueryError(
                f"Table '{cat}.{schema}.{table}' does not exist")
        if not hasattr(conn, "replace"):
            raise QueryError(f"{conn.name}: {what} not supported")
        return conn, meta

    def _update(self, stmt: "A.Update") -> QueryResult:
        """UPDATE as whole-table rewrite: every column becomes
        CASE WHEN pred THEN cast(assignment) ELSE old END (reference:
        UpdateOperator + connector row change; the memory connector
        swaps contents like DELETE above)."""
        cat, schema, table = self._qualify(stmt.table)
        self._check_access("update", cat, schema, table)
        conn, meta = self._writable_meta(cat, schema, table, "UPDATE")
        names = {c.name for c in meta.columns}
        assigns = {}
        for col, e in stmt.assignments:
            if col.lower() not in names:
                raise QueryError(f"Column '{col}' does not exist")
            assigns[col.lower()] = e
        cond = (A.FunctionCall("coalesce",
                               (stmt.where, A.Literal(False)))
                if stmt.where is not None else A.Literal(True))
        items = []
        for c in meta.columns:
            if c.name in assigns:
                items.append(A.SelectItem(
                    A.Case(((cond, A.Cast(assigns[c.name],
                                          str(c.type))),),
                           A.Identifier((c.name,))), c.name))
            else:
                items.append(A.SelectItem(A.Identifier((c.name,)),
                                          c.name))
        items.append(A.SelectItem(cond, "__updated"))
        res = self._run_query(A.QueryStatement(A.Query(
            A.QuerySpecification(
                tuple(items), from_=A.Table((cat, schema, table))))))
        data = {c.name: [row[i] for row in res.rows]
                for i, c in enumerate(meta.columns)}
        batch = batch_from_pylist(
            data, {c.name: c.type for c in meta.columns})
        conn.replace(schema, table, batch)
        n = sum(1 for row in res.rows if row[-1])
        return _msg_result("UPDATE", n)

    def _merge_stmt(self, stmt: "A.Merge") -> QueryResult:
        """MERGE INTO target USING source ON cond WHEN ... — executed
        as engine queries (reference: the MERGE row-change plan):
        matched target rows flow through nested-CASE transforms (first
        satisfied clause wins; DELETE arms drop the row), unmatched
        source rows satisfying a NOT MATCHED arm are appended. A
        target row matching multiple source rows is not detected (the
        reference raises); the first join expansion wins."""
        cat, schema, table = self._qualify(stmt.target)
        self._check_access("update", cat, schema, table)
        conn, meta = self._writable_meta(cat, schema, table, "MERGE")
        talias = (stmt.target_alias or table).lower()
        trel: A.Relation = A.Table((cat, schema, table))
        if stmt.target_alias:
            trel = A.AliasedRelation(trel, talias, ())

        # source wrapped with a match indicator column
        ind = "__merge_m"
        src = stmt.source
        src_alias = None
        if isinstance(src, A.AliasedRelation):
            src_alias = src.alias.lower()
        elif isinstance(src, A.Table):
            src_alias = src.parts[-1].lower()
        else:
            raise QueryError("MERGE source subquery requires an alias")
        wrapped = A.AliasedRelation(
            A.SubqueryRelation(A.Query(A.QuerySpecification(
                (A.SelectItem(A.Star(), None),
                 A.SelectItem(A.Literal(1), ind)),
                from_=src))), src_alias, ())

        matched_flag = A.IsNull(A.Identifier((src_alias, ind)),
                                negated=True)

        def arm_cond(cl: "A.MergeClause") -> A.Expression:
            c: A.Expression = matched_flag if cl.matched else \
                A.IsNull(A.Identifier((src_alias, ind)))
            if cl.condition is not None:
                c = A.BinaryOp("and", c, A.FunctionCall(
                    "coalesce", (cl.condition, A.Literal(False))))
            return c

        matched_clauses = [c for c in stmt.clauses if c.matched]
        insert_clauses = [c for c in stmt.clauses if not c.matched]
        for cl in insert_clauses:
            if cl.action != "insert":
                raise QueryError(
                    "WHEN NOT MATCHED supports only INSERT")
        for cl in matched_clauses:
            if cl.action not in ("update", "delete"):
                raise QueryError(
                    "WHEN MATCHED supports only UPDATE or DELETE")

        # pass 1: target rows (kept/transformed)
        items = []
        for c in meta.columns:
            whens = []
            for cl in matched_clauses:
                if cl.action != "update":
                    continue
                assigns = {k.lower(): v for k, v in cl.assignments}
                if c.name in assigns:
                    whens.append((arm_cond(cl),
                                  A.Cast(assigns[c.name],
                                         str(c.type))))
                else:
                    whens.append((arm_cond(cl),
                                  A.Identifier((talias, c.name))))
            items.append(A.SelectItem(
                A.Case(tuple(whens), A.Identifier((talias, c.name)))
                if whens else A.Identifier((talias, c.name)), c.name))
        keep_whens = tuple(
            (arm_cond(cl), A.Literal(cl.action != "delete"))
            for cl in matched_clauses)
        fired_whens = tuple((arm_cond(cl), A.Literal(True))
                            for cl in matched_clauses)
        items.append(A.SelectItem(
            A.Case(keep_whens, A.Literal(True)), "__keep"))
        items.append(A.SelectItem(
            A.Case(fired_whens, A.Literal(False)), "__fired"))
        res = self._run_query(A.QueryStatement(A.Query(
            A.QuerySpecification(
                tuple(items),
                from_=A.Join("left", trel, wrapped, on=stmt.on)))))
        kept = [row[:-2] for row in res.rows if row[-2]]
        n_changed = sum(1 for row in res.rows if row[-1])

        # pass 2: NOT MATCHED inserts
        for cl in insert_clauses:
            cols = tuple(c.lower() for c in cl.insert_columns) or \
                tuple(c.name for c in meta.columns)
            if len(cols) != len(cl.insert_values):
                raise QueryError("MERGE INSERT arity mismatch")
            by_col = dict(zip(cols, cl.insert_values))
            ins_items = tuple(
                A.SelectItem(A.Cast(by_col[c.name], str(c.type))
                             if c.name in by_col else A.Literal(None),
                             c.name)
                for c in meta.columns)
            where: A.Expression = A.Exists(A.Query(
                A.QuerySpecification(
                    (A.SelectItem(A.Literal(1), None),),
                    from_=trel, where=stmt.on)), negated=True)
            if cl.condition is not None:
                where = A.BinaryOp("and", where, A.FunctionCall(
                    "coalesce", (cl.condition, A.Literal(False))))
            ires = self._run_query(A.QueryStatement(A.Query(
                A.QuerySpecification(ins_items, from_=src,
                                     where=where))))
            kept.extend(ires.rows)
            n_changed += len(ires.rows)

        data = {c.name: [row[i] for row in kept]
                for i, c in enumerate(meta.columns)}
        batch = batch_from_pylist(
            data, {c.name: c.type for c in meta.columns})
        conn.replace(schema, table, batch)
        return _msg_result("MERGE", n_changed)

    def _check_access(self, privilege: str, cat: str, schema: str,
                      table: str) -> None:
        ac = self.catalogs.access_control
        if ac is None:
            return
        from .security import AccessDeniedError
        try:
            getattr(ac, f"check_can_{privilege}")(
                self.session.user, cat, schema, table)
        except AccessDeniedError as e:
            raise QueryError(str(e)) from e

    def _qualify(self, parts: Tuple[str, ...]):
        parts = tuple(p.lower() for p in parts)
        if len(parts) == 3:
            return parts
        if len(parts) == 2:
            return (self.session.catalog,) + parts
        return (self.session.catalog, self.session.schema or "default",
                parts[0])


def _msg_result(update_type: str,
                count: Optional[int] = None) -> QueryResult:
    return QueryResult([], [], [], update_type=update_type,
                       update_count=count)
