"""Engine facade: SQL string in, rows out.

The single-process counterpart of the reference's coordinator pipeline
(dispatcher/DispatchManager.createQuery -> SqlQueryExecution.start ->
LogicalPlanner -> scheduler -> operators), collapsed to:
parse -> plan (planner.py) -> compile+execute (exec/compiler.py), plus the
statement surface (DDL/DML/EXPLAIN/SHOW/SET SESSION — the reference's
DataDefinitionTask family and writer plans).

The reference's closest analogue is PlanTester/StandaloneQueryRunner
(testing/PlanTester.java:274): the full engine in-process without HTTP.
"""

from __future__ import annotations

import time as _time
from typing import Optional

import numpy as np

from ..connectors.spi import CatalogManager, ColumnSchema, Connector
from ..data.page import Page
from ..exec.compiler import LocalExecutor, page_rows
from ..exec.resident import ResidentStore
from ..ops.kernels import JOIN_ROWS
from ..plan.nodes import PlanNode, TableScan, format_plan
from ..plan.planner import Planner, note_subqueries
from ..plan.reorder import join_estimates
from .session import SessionProperties
from .txn import run_write  # imported eagerly: registers the txn metrics

__all__ = ["Engine"]


def _rescale_column(arr, src_type, dst_type):
    """Align a query-result column with the target table's column type.
    Decimal lanes are scaled int64 (data/types.py DecimalType), so writing
    them into a double/int/differently-scaled column must rescale — a plain
    astype would persist the raw lanes (e.g. 1.5 stored as 15)."""
    src_dec = getattr(src_type, "scale", None) if src_type.is_decimal else None
    dst_dec = getattr(dst_type, "scale", None) if dst_type.is_decimal else None
    if src_dec is None and dst_dec is None:
        return arr
    mask = np.ma.getmaskarray(arr) if isinstance(arr, np.ma.MaskedArray) else None
    base = np.ma.getdata(arr) if mask is not None else np.asarray(arr)
    if src_dec is not None and dst_dec is None:
        out = (
            base.astype(np.float64) / (10.0**src_dec)
            if dst_type.is_floating
            else np.round(base.astype(np.float64) / (10.0**src_dec)).astype(np.int64)
        )
    elif src_dec is None and dst_dec is not None:
        out = np.round(base.astype(np.float64) * (10.0**dst_dec)).astype(np.int64)
    elif src_dec != dst_dec:
        out = np.round(base.astype(np.float64) * (10.0 ** (dst_dec - src_dec))).astype(
            np.int64
        )
    else:
        return arr
    return np.ma.MaskedArray(out, mask=mask) if mask is not None else out


def _kernel_lines(plan) -> list[str]:
    """EXPLAIN ANALYZE's `-- kernel:` footer: what the data-plane kernels
    dispatched in the plan's last trace (ops/kernels.py)."""
    from ..ops.kernels import describe

    return [f"-- kernel: {line}" for line in describe(plan)]


class Engine:
    """distributed=True runs every query SPMD over `devices` (default: all
    jax.devices()) with exchange collectives — the in-process analogue of the
    reference's DistributedQueryRunner (N servers, loopback HTTP)."""

    def __init__(
        self,
        default_catalog: str = "tpch",
        distributed: bool = False,
        devices=None,
    ):
        from ..utils.compilecache import enable_persistent_cache

        # warm compiles across processes: interactive latency depends on it
        # (a cold q03 costs ~36s of XLA compile; a cached one, seconds)
        enable_persistent_cache()
        self.catalogs = CatalogManager()
        self.default_catalog = default_catalog
        self.planner = Planner(self.catalogs, default_catalog)
        if distributed:
            from ..exec.spmd import SpmdExecutor

            self.executor = SpmdExecutor(self.catalogs, default_catalog, devices)
            # coordinator-local fallback for plans that cannot shard_map
            # (host-collected aggregates)
            self._local_fallback = LocalExecutor(self.catalogs, default_catalog)
        else:
            self.executor = LocalExecutor(self.catalogs, default_catalog)
            self._local_fallback = self.executor
        self.distributed = distributed
        self.session = SessionProperties()
        from .events import EventListenerManager

        self.events = EventListenerManager()
        self._query_seq = 0
        self._prepared: dict[str, str] = {}
        self._view_sql: dict[tuple[str, str], str] = {}  # SHOW CREATE VIEW
        self._tx_views = None  # (views, view_sql) snapshot inside a tx
        self._tx_snapshots = None  # name -> connector snapshot, inside a tx
        from .security import AllowAllAccessControl

        # reference: security/AccessControlManager consulted before planning
        self.access_control = AllowAllAccessControl()
        self.user = "user"
        from ..utils.tracing import Tracer, add_exporters_from_env

        # reference: OpenTelemetry spans (SqlQueryExecution.java:473)
        self.tracer = Tracer()
        add_exporters_from_env(self.tracer)
        # the executors open their spans (scan_load, compile, dispatch,
        # device_wait) under this engine's `execute`
        self.executor.tracer = self._local_fallback.tracer = self.tracer
        # table columns on the device, for every LocalExecutor of this
        # engine (the SPMD executor keeps its own sharded pages)
        self.resident = ResidentStore()
        self._local_fallback.resident = self.resident
        # result & fragment caches (runtime/resultcache.py): attached by the
        # coordinator's statement surface so DML executed here invalidates
        # the coordinator's cached results; None on a plain local engine
        self.result_cache = None
        self.fragment_memo = None
        # write-transaction plane (runtime/txn.py): the coordinator surface
        # threads its QueryJournal + FaultInjector through; a plain local
        # engine runs the same staged-commit protocol without durability
        import threading as _threading

        self.txn_journal = None
        self.write_fault_injector = None
        self._txn_local = _threading.local()
        self._last_txn_info = None  # EXPLAIN ANALYZE `-- txn:` footer

    def register_catalog(self, name: str, connector: Connector) -> None:
        self.catalogs.register(name, connector)

    def add_event_listener(self, listener) -> None:
        """Reference: EventListener SPI (eventlistener/EventListenerManager)."""
        self.events.add(listener)

    # ------------------------------------------------------------- queries
    def plan(self, sql_or_query) -> PlanNode:
        from ..plan.optimizer import optimize

        plan = optimize(self.planner.plan(sql_or_query), self.catalogs, self.session)
        # table-level SELECT checks on the final plan: base tables of views/
        # CTEs/subqueries are all visible as scans here (reference:
        # checkCanSelectFromColumns per analyzed table reference)
        from ..plan.nodes import walk

        for n in walk(plan):
            if isinstance(n, TableScan):
                self.access_control.check_can_select(
                    self.user, n.catalog, n.table, n.column_names
                )
        if self.distributed:
            from ..exec.compiler import _has_host_aggs
            from ..plan.distribute import distribute

            if _has_host_aggs(plan):
                # host-collected aggregates (array_agg/map_agg/listagg)
                # intern structured values on the host and cannot trace
                # under shard_map; their input is gathered anyway, so run
                # the whole plan coordinator-local (reference:
                # COORDINATOR_DISTRIBUTION stages)
                return plan
            plan = distribute(
                plan, self.catalogs, self.executor.num_devices, self.session
            )
        return plan

    def explain(self, sql: str) -> str:
        return format_plan(self.plan(sql))

    def execute_page(self, sql) -> Page:
        with self.tracer.span("planner") as span:
            plan = self.plan(sql)
            self._note_join_estimates(span, plan)
            note_subqueries(span, self.planner, plan)
        with self.tracer.span("execute"):
            return self._execute_planned(plan)

    def _note_join_estimates(self, span, plan: PlanNode) -> None:
        """On the `planner` span, what the join order was chosen by:
        `join_order` (each region's leaves as they are joined) and
        `join_estimates` (node id -> estimated output rows; the `device_wait`
        span's `frames` hold the rows the same nodes made)."""
        orders, estimates = join_estimates(plan, self.catalogs)
        if not estimates:
            return
        if orders:
            span.attributes["join_order"] = orders
        span.attributes["join_estimates"] = {
            f"Join#{nid}": rows for nid, rows in estimates.items()}
        JOIN_ROWS.labels("estimated").inc(sum(estimates.values()))

    def _device_memory_budget(self) -> int:
        """Per-query device-memory budget: the session property when set,
        else (0 = auto) ~80% of the accelerator's reported HBM — the
        reactive-spill trigger needs no session hint.  -1 disables the
        budget entirely (never reroute out-of-core); returns 0 when no
        budget applies."""
        budget = int(self.session.get("query_max_memory_bytes") or 0)
        if budget == -1:
            return 0
        if budget:
            return budget
        try:
            import jax

            stats = jax.devices()[0].memory_stats() or {}
            lim = int(stats.get("bytes_limit") or 0)
            return int(lim * 0.8)
        except Exception:
            return 0

    @staticmethod
    def _is_device_oom(e: Exception) -> bool:
        s = str(e)
        return "RESOURCE_EXHAUSTED" in s or "Out of memory" in s or "OOM" in s

    def _run_out_of_core(self, plan, est: int, budget: int) -> Page:
        from ..exec.spill import OutOfCoreExecutor

        parts = max(2, min(16, -(-est // max(budget, 1))))
        parts = 1 << (parts - 1).bit_length()  # pow2 slices, capped:
        # beyond 16 the per-slice compile overhead dominates any
        # memory win (deeper budgets should spill to bigger disks,
        # not thinner slices)
        ooc = OutOfCoreExecutor(
            self.catalogs, self.default_catalog, parts, self.session
        )
        self.last_spill = ooc  # observable: spilled_bytes/spill_files
        return ooc.execute(plan)

    def _apply_compile_props(self) -> None:
        """Session → executor compile-resilience knobs (exec/compilesvc.py):
        re-applied per statement so SET SESSION takes effect immediately."""
        for ex in (self.executor, getattr(self, "_local_fallback", None)):
            if ex is not None and hasattr(ex, "compile_wait_budget_ms"):
                ex.compile_wait_budget_ms = int(
                    self.session.get("compile_wait_budget_ms") or 0
                )
                ex.compile_deadline_s = float(
                    self.session.get("compile_deadline_s") or 0.0
                )
        self._apply_kernel_props()

    def _apply_kernel_props(self) -> None:
        """Session → data-plane kernel policy (ops/kernels.py): re-applied
        per statement like the compile props.  The policy fingerprint rides
        the executor jit-cache key, so SET SESSION flips recompile rather
        than silently reusing a program traced under the old policy."""
        from ..ops import kernels as _kernels

        _kernels.set_policy(_kernels.KernelPolicy(
            enabled=bool(self.session.get("data_plane_kernels")),
            interpret=bool(self.session.get("pallas_interpret")),
        ))

    def _execute_planned(self, plan) -> Page:
        self._apply_compile_props()
        if self.distributed:
            from ..exec.compiler import _has_host_aggs

            if _has_host_aggs(plan):
                return self._local_fallback.execute(plan)
        budget = self._device_memory_budget()
        if budget and not self.distributed:
            from ..exec.spill import estimate_plan_bytes

            est = estimate_plan_bytes(plan, self.catalogs)
            if est > budget:
                return self._run_out_of_core(plan, est, budget)
            try:
                return self.executor.execute(plan)
            except Exception as e:
                if not self._is_device_oom(e):
                    raise
                # REACTIVE spill (reference: revocable memory +
                # SpillableHashAggregationBuilder): the pre-plan estimate
                # admitted the query but actual state (join blowup, capacity
                # growth) exceeded HBM — rerun partitioned, sizing P from
                # the observed shortfall rather than the scan estimate
                return self._run_out_of_core(
                    plan, max(est, budget) * 2, budget
                )
        return self.executor.execute(plan)

    def query(self, sql) -> list[tuple]:
        """Run a query, return rows as python tuples (None == NULL)."""
        from .events import QueryEvent

        self._query_seq += 1
        qid = f"local_{self._query_seq}"
        text = sql if isinstance(sql, str) else "<planned>"
        self.events.fire(QueryEvent("created", qid, text))
        t0 = _time.perf_counter()
        try:
            with self.tracer.span("query", query_id=qid):
                page = self.execute_page(sql)
                rows = page_rows(self.tracer, page)
                self.tracer.annotate(rows=len(rows))
        except Exception as e:
            self.events.fire(
                QueryEvent("failed", qid, text, _time.perf_counter() - t0, error=str(e))
            )
            raise
        wall = _time.perf_counter() - t0
        self.events.fire(
            QueryEvent(
                "completed", qid, text, wall, rows=len(rows),
                cpu_ms=round(wall * 1e3, 3), stage_count=1,
            )
        )
        return rows

    def warm_from_history(self, history, limit: int = 8) -> int:
        """Replay the top-``limit`` recurring FINISHED statements from a
        QueryHistoryStore so their XLA programs land in the jit + persistent
        caches before the first client query (runtime/warmup.py); returns
        how many statements warmed successfully."""
        from .warmup import warm_from_history as _warm

        return _warm(self.query, history, limit)

    def _query_columns(self, query) -> tuple[list, list, list]:
        """(names, types, host column arrays) of a query result — the write
        path's input.  Overridable: the multi-host coordinator rebuilds the
        columns from its distributed result rows instead (runtime/
        coordinator.py _StatementSurface)."""
        plan = self.plan(query)
        page = self.executor.execute(plan)
        return (
            list(plan.output_names),
            list(plan.output_types),
            page.to_numpy_columns(),
        )

    # ---------------------------------------------------- statement surface
    def execute(self, sql: str) -> list[tuple]:
        """Full statement surface: queries, DDL/DML, EXPLAIN [ANALYZE],
        SHOW TABLES, DESCRIBE, SET SESSION."""
        from ..sql import statements as S

        return self.execute_stmt(S.parse_statement(sql))

    def fastpath(self):
        """Lazy per-surface prepared-statement fast path (runtime/
        fastpath.py): parameterized plan cache + pipelined/batched
        dispatch.  Shared by every protocol session of a coordinator."""
        fp = getattr(self, "_fastpath", None)
        if fp is None:
            from .fastpath import FastPath

            fp = self._fastpath = FastPath(self)
        return fp

    def execute_stmt(self, stmt, prepared: Optional[dict] = None) -> list[tuple]:
        """`prepared`: client-held prepared-statement overlay (name -> sql,
        from X-Trino-Prepared-Statement headers) consulted before the
        engine's own session registry."""
        from ..sql import statements as S

        # access control at statement dispatch (reference: AccessControl
        # checkCanInsertIntoTable / checkCanDropTable / ... before execution;
        # SELECT is checked per-scan in plan())
        if isinstance(stmt, (S.CreateTable, S.CreateTableAs)):
            self._check_write(stmt.name, "create")
        elif isinstance(stmt, (S.Insert, S.InsertValues)):
            self._check_write(stmt.table, "insert")
        elif isinstance(stmt, S.DropTable):
            self._check_write(stmt.name, "drop")
        elif isinstance(stmt, S.Delete):
            self._check_write(stmt.table, "delete")
        elif isinstance(stmt, S.Update):
            self._check_write(stmt.table, "update")
        elif isinstance(stmt, S.Merge):
            self._check_write(stmt.target, "merge")
        elif isinstance(stmt, S.CreateView):
            self._check_write(stmt.name, "create_view")
        elif isinstance(stmt, S.DropView):
            self._check_write(stmt.name, "drop_view")
        elif isinstance(stmt, S.SetSession):
            self.access_control.check_can_set_session(self.user, stmt.name)

        if isinstance(stmt, S.QueryStmt):
            return self.query(stmt.query)

        if isinstance(stmt, S.Explain):
            return self._execute_explain(stmt, prepared)

        if isinstance(stmt, S.CreateTable):
            from ..data.types import parse_type

            conn, name = self._target_conn(stmt.name)
            if stmt.if_not_exists and name in conn.list_tables():
                return [(0,)]
            conn.create_table(
                name, [ColumnSchema(n, parse_type(t)) for n, t in stmt.columns]
            )
            return [(0,)]

        if isinstance(stmt, S.CreateTableAs):
            conn, name = self._target_conn(stmt.name)
            if stmt.if_not_exists and name in conn.list_tables():
                return [(0,)]
            _, catalog, _ = self._target_ref(stmt.name)

            def _ctas(txn):
                # recomputed per attempt: a conflict retry must stage
                # against the fresh snapshot, not stale arrays
                names, types, cols = self._query_columns(stmt.query)
                txn.stage_create(
                    [ColumnSchema(n, t) for n, t in zip(names, types)]
                )
                txn.stage_insert(dict(zip(names, cols)))
                return 0

            n = run_write(self, catalog, name, "create", _ctas)
            return [(n,)]

        if isinstance(stmt, S.Insert):
            conn, table = self._target_conn(stmt.table)
            _, catalog, _ = self._target_ref(stmt.table)

            def _insert(txn):
                _, types, cols = self._query_columns(stmt.query)
                schema = conn.table_schema(table)
                names = (
                    list(stmt.columns)
                    if stmt.columns
                    else [c.name for c in schema.columns]
                )
                if len(names) != len(cols):
                    raise ValueError(
                        f"INSERT column count mismatch: {len(names)} vs {len(cols)}"
                    )
                cols2 = [
                    _rescale_column(arr, t, schema.type_of(n))
                    for arr, t, n in zip(cols, types, names)
                ]
                return self._insert_resolved(conn, table, names, cols2,
                                             stage=txn)

            n = run_write(self, catalog, table, "insert", _insert)
            return [(n,)]

        if isinstance(stmt, S.InsertValues):
            _, catalog, table = self._target_ref(stmt.table)
            table = table.split(".")[-1]
            n = run_write(
                self, catalog, table, "insert",
                lambda txn: self._insert_values(stmt, stage=txn),
            )
            return [(n,)]

        if isinstance(stmt, S.DropTable):
            conn, name = self._target_conn(stmt.name)
            if stmt.if_exists and name not in conn.list_tables():
                return [(0,)]
            conn.drop_table(name)
            self.cache_invalidate(stmt.name)
            return [(0,)]

        if isinstance(stmt, S.CreateView):
            conn, catalog, name = self._target_ref(stmt.name)
            name = name.split(".")[-1]  # match the planner's (catalog, table)
            key = (catalog, name)
            if name in conn.list_tables():
                # Trino: TABLE_ALREADY_EXISTS — a view must not shadow a table
                raise ValueError(f"table already exists: {stmt.name}")
            if key in self.planner.views and not stmt.or_replace:
                raise ValueError(f"view already exists: {stmt.name}")
            prev = self.planner.views.get(key)
            self.planner.views[key] = stmt.query
            try:
                self.plan(stmt.query)  # validate now: names, types, cycles
            except Exception:
                if prev is None:
                    del self.planner.views[key]
                else:
                    self.planner.views[key] = prev
                raise
            self._view_sql[key] = stmt.sql
            return [(0,)]

        if isinstance(stmt, S.DropView):
            _, catalog, name = self._target_ref(stmt.name)
            key = (catalog, name.split(".")[-1])
            if key not in self.planner.views:
                if stmt.if_exists:
                    return [(0,)]
                raise KeyError(f"view not found: {stmt.name}")
            del self.planner.views[key]
            self._view_sql.pop(key, None)
            return [(0,)]

        if isinstance(stmt, S.ShowCreateView):
            _, catalog, name = self._target_ref(stmt.name)
            name = name.split(".")[-1]
            sql_text = self._view_sql.get((catalog, name))
            if sql_text is None:
                raise KeyError(f"view not found: {stmt.name}")
            return [(f"CREATE VIEW {name} AS {sql_text}",)]

        if isinstance(stmt, S.ShowTables):
            conn = self.catalogs.get(self.default_catalog)
            views = sorted(
                n for (c, n) in self.planner.views if c == self.default_catalog
            )
            return [(t,) for t in conn.list_tables()] + [(v,) for v in views]

        if isinstance(stmt, S.DescribeTable):
            _, catalog, name = self._target_ref(stmt.name)
            vq = self.planner.views.get((catalog, name.split(".")[-1]))
            if vq is not None:
                plan = self.plan(vq)
                return [
                    (n, t.name)
                    for n, t in zip(plan.output_names, plan.output_types)
                ]
            conn, name = self._target_conn(stmt.name)
            schema = conn.table_schema(name)
            return [(c.name, c.type.name) for c in schema.columns]

        if isinstance(stmt, S.SetSession):
            self.session.set(stmt.name, stmt.value)
            return [(1,)]

        if isinstance(stmt, S.Delete):
            from .dml import execute_delete

            return [(execute_delete(self, stmt),)]

        if isinstance(stmt, S.Update):
            from .dml import execute_update

            return [(execute_update(self, stmt),)]

        if isinstance(stmt, S.Merge):
            from .dml import execute_merge

            return [(execute_merge(self, stmt),)]

        if isinstance(stmt, S.Prepare):
            self._prepared[stmt.name] = stmt.sql
            return [(1,)]

        if isinstance(stmt, S.ExecuteStmt):
            sql_text = self._resolve_prepared(stmt.name, prepared)
            from .fastpath import NotFastpath

            try:
                return self.fastpath().execute(sql_text, stmt.parameters)
            except NotFastpath:
                pass
            # legacy path: typed AST substitution + full replan (DML
            # templates, expression parameters, fast path disabled)
            bound = S.parse_statement(sql_text, params=stmt.parameters)
            return self.execute_stmt(bound)

        if isinstance(stmt, S.Deallocate):
            self._prepared.pop(stmt.name, None)
            return [(1,)]

        if isinstance(stmt, S.StartTransaction):
            # per-session transaction over writable catalogs: connectors that
            # support snapshot/restore participate (reference:
            # transaction/TransactionManager + connector tx handles; here the
            # rewrite-and-swap write path makes copy-on-write snapshots cheap)
            if self._tx_snapshots is not None:
                raise RuntimeError("transaction already in progress")
            self._tx_snapshots = {
                name: self.catalogs.get(name).snapshot()
                for name in self.catalogs.names()
                if hasattr(self.catalogs.get(name), "snapshot")
            }
            # view DDL participates: restore the registry on ROLLBACK too
            self._tx_views = (dict(self.planner.views), dict(self._view_sql))
            return [(1,)]

        if isinstance(stmt, S.Commit):
            if self._tx_snapshots is None:
                raise RuntimeError("no transaction in progress")
            self._tx_snapshots = None
            self._tx_views = None
            return [(1,)]

        if isinstance(stmt, S.Rollback):
            if self._tx_snapshots is None:
                raise RuntimeError("no transaction in progress")
            for name, snap in self._tx_snapshots.items():
                self.catalogs.get(name).restore(snap)
            self._tx_snapshots = None
            if self._tx_views is not None:
                self.planner.views, self._view_sql = self._tx_views
                self._tx_views = None
            return [(1,)]

        raise NotImplementedError(f"statement {type(stmt).__name__}")

    # ------------------------------------------------------------- explain
    def _explain_analyze_distributed(self, query):
        """Override point: the multi-host coordinator surface (runtime/
        coordinator.py _StatementSurface) returns its QueryInfo — per-stage
        plans, operator stats, wall intervals — here.  The in-process
        engine has none and uses the executor path in _execute_explain."""
        return None

    def _explain_execute(self, stmt, prepared: Optional[dict] = None) -> list[tuple]:
        """EXPLAIN [ANALYZE] EXECUTE name [USING ...]: the prepared fast
        path's plan plus a `-- fastpath:` footer with the plan-cache
        disposition (hit|miss|bypass) and binding split."""
        from ..sql import statements as S
        from .fastpath import NotFastpath

        ex_stmt = stmt.execute
        sql_text = self._resolve_prepared(ex_stmt.name, prepared)
        fp = self.fastpath()
        t0 = _time.perf_counter()
        self._apply_compile_props()  # the kernel policy keys the plan cache
        try:
            tmpl, n_params = fp._template(sql_text)
            if len(ex_stmt.parameters) != n_params:
                raise ValueError(
                    f"prepared statement takes {n_params} parameters,"
                    f" got {len(ex_stmt.parameters)}"
                )
            slots = fp._slots(ex_stmt.parameters)
            entry, info = fp._lookup(sql_text, tmpl.query, slots)
        except NotFastpath:
            bound = S.parse_statement(sql_text, params=ex_stmt.parameters)
            if not isinstance(bound, S.QueryStmt):
                raise ValueError("EXPLAIN EXECUTE requires a query template")
            inner = S.Explain(bound.query, stmt.analyze, stmt.distributed)
            text = [r[0] for r in self._execute_explain(inner)]
            text.append("-- fastpath: off (legacy substitute-and-replan path)")
            return [(line,) for line in text]
        text = format_plan(entry.plan).splitlines()
        if stmt.analyze:
            params = fp._param_values(entry.slots, slots)
            page = fp._executor().execute(entry.plan, params=params)
            rows = page.to_pylist()
            wall = _time.perf_counter() - t0
            text.append(
                f"-- output rows: {len(rows)}, wall: {wall * 1e3:.1f} ms"
            )
            text.extend(_kernel_lines(entry.plan))
        window = float(self.session.get("execute_batch_window_ms") or 0.0)
        text.append(
            f"-- fastpath: plan_cache={info.cache} bound={info.bound}"
            f" baked={info.baked} batch_window_ms={window:g} executor=local"
        )
        return [(line,) for line in text]

    def _execute_explain(self, stmt, prepared: Optional[dict] = None) -> list[tuple]:
        """EXPLAIN [ANALYZE] in the session's explain_format (text | json).
        ANALYZE prefers the distributed QueryInfo; otherwise any executor
        with eager per-operator timing (LocalExecutor, SpmdExecutor)."""
        import json as _json

        from ..plan.nodes import plan_to_obj

        if stmt.execute is not None:
            return self._explain_execute(stmt, prepared)
        if stmt.statement is not None:
            return self._explain_write(stmt, prepared)
        fmt = str(self.session.get("explain_format") or "text").lower()
        plan = self.plan(stmt.query)
        if not stmt.analyze:
            if fmt == "json":
                return [(_json.dumps(plan_to_obj(plan), indent=2),)]
            return [(line,) for line in format_plan(plan).splitlines()]

        t0 = _time.perf_counter()
        info = self._explain_analyze_distributed(stmt.query)
        if info is not None:
            wall = _time.perf_counter() - t0
            if fmt == "json":
                return [(_json.dumps(info, default=str, indent=2),)]
            return [
                (line,) for line in self._render_distributed_analyze(info, wall)
            ]

        ex = self.executor
        if self.distributed:
            from ..exec.compiler import _has_host_aggs

            if _has_host_aggs(plan):
                ex = self._local_fallback  # plan came back undistributed
        if hasattr(ex, "explain_analyze"):
            # the engine's executor is long-lived: remember where its
            # compile ledger stood so the footer shows only THIS
            # statement's jit signatures
            n_ev0 = len(getattr(ex, "compile_events", []) or [])
            self._apply_compile_props()
            page, stats = ex.explain_analyze(plan)
            wall = _time.perf_counter() - t0
            if fmt == "json":
                obj = {
                    "plan": plan_to_obj(plan, stats=stats),
                    "output_rows": len(page.to_pylist()),
                    "wall_ms": round(wall * 1e3, 1),
                }
                return [(_json.dumps(obj, indent=2),)]
            ann = {
                nid: (
                    f"   [rows: {s.get('rows', '?')}"
                    + (f", {s['ms']:.1f} ms" if "ms" in s else "")
                    + "]"
                )
                for nid, s in stats.items()
            }
            text = format_plan(plan, annotations=ann).splitlines()
            timed = [(nid, s["ms"]) for nid, s in stats.items() if "ms" in s]
            if timed:
                slow_nid, slow_ms = max(timed, key=lambda kv: kv[1])
                from ..exec.compiler import _node_ids

                slow = type(_node_ids(plan)[slow_nid]).__name__
                text.append(
                    f"-- slowest operator: {slow} (node {slow_nid}, {slow_ms:.1f} ms eager)"
                )
            text.append(
                f"-- output rows: {len(page.to_pylist())}, wall: {wall * 1000:.1f} ms"
            )
            text.extend(self._profile_footer(ex, n_ev0))
            text.extend(_kernel_lines(plan))
            return [(line,) for line in text]
        rows = self.query(stmt.query)
        wall = _time.perf_counter() - t0
        text = format_plan(plan).splitlines()
        text.append(f"-- output rows: {len(rows)}, wall: {wall * 1000:.1f} ms")
        return [(line,) for line in text]

    def _explain_write(self, stmt, prepared: Optional[dict] = None) -> list[tuple]:
        """EXPLAIN [ANALYZE] over a write statement.  Plain EXPLAIN renders
        the source query's plan (if any) plus the write target without
        executing; ANALYZE executes the statement through the transactional
        path and appends the `-- txn:` commit-protocol footer."""
        from ..sql import statements as S

        inner = stmt.statement
        text: list[str] = []
        target = getattr(inner, "table", None) or getattr(inner, "name", None) \
            or getattr(inner, "target", None)
        op = type(inner).__name__
        text.append(f"Write[{op} -> {target}]")
        src = getattr(inner, "query", None)
        if src is not None and not isinstance(inner, S.Merge):
            text.extend(
                "  " + ln for ln in format_plan(self.plan(src)).splitlines()
            )
        if not stmt.analyze:
            return [(line,) for line in text]
        t0 = _time.perf_counter()
        rows = self.execute_stmt(inner, prepared=prepared)
        wall = _time.perf_counter() - t0
        n = rows[0][0] if rows and rows[0] else 0
        text.append(f"-- output rows: {n}, wall: {wall * 1e3:.1f} ms")
        info = self._last_txn_info
        if info is not None:
            text.append(
                f"-- txn: id={info['txn_id']} table={info['table']}"
                f" op={info['operation']} expected={info['expected']}"
                f" staged_bytes={info['staged_bytes']}"
                f" retries={info.get('retries', 0)}"
                f" outcome={info['outcome']}"
                f" commit_ms={info['commit_ms']:.1f}"
            )
        return [(line,) for line in text]

    @staticmethod
    def _profile_footer(ex, n_ev0: int = 0) -> list[str]:
        """Compile/execute attribution footer (utils/profiler.py): the jit
        signatures this statement built, XLA compile wall vs dispatch wall,
        persistent-cache outcome, and the program-level roofline (flops /
        bytes-accessed from ``compiled.cost_analysis()`` over the execute
        wall).  ``n_ev0`` marks where the executor's cumulative compile
        ledger stood before the statement ran."""
        events = list(getattr(ex, "compile_events", []) or [])[n_ev0:]
        compile_ms = getattr(ex, "last_compile_ms", 0.0)
        execute_ms = getattr(ex, "last_execute_ms", 0.0)
        if not events and compile_ms <= 0.0 and execute_ms <= 0.0:
            return []
        out = [
            f"-- phases: compile {compile_ms:.1f} ms, execute {execute_ms:.1f} ms"
        ]
        for ev in events:
            if ev.get("mode") == "fallback":
                # compile didn't finish inside the wait budget / deadline:
                # the statement ran eager (exec/compilesvc.py)
                out.append(
                    f"-- compile: {ev.get('signature', '?')} fallback "
                    f"({ev.get('reason', '?')}, waited "
                    f"{ev.get('wait_ms', 0.0):.1f} ms)"
                )
                continue
            if ev.get("compile_s") is None:
                # async join / swap-in: another query (or an earlier
                # fallback execution) owns the actual compile wall
                out.append(
                    f"-- compile: {ev.get('signature', '?')} async "
                    f"(joined after {ev.get('wait_ms', 0.0):.1f} ms)"
                )
                continue
            out.append(
                f"-- compile: {ev.get('signature', '?')} "
                f"{ev.get('compile_s', 0.0) * 1e3:.1f} ms "
                f"[persistent cache: {ev.get('cache', 'uncached')}]"
            )
            flops = ev.get("flops") or 0.0
            byts = ev.get("bytes_accessed") or 0.0
            if execute_ms > 0.0 and (flops or byts):
                ex_s = execute_ms / 1e3
                out.append(
                    f"-- roofline: {ev.get('signature', '?')} "
                    f"{flops / ex_s / 1e9:.3f} GFLOP/s, "
                    f"{byts / ex_s / 1e9:.3f} GB/s achieved "
                    f"over {execute_ms:.1f} ms execute"
                )
        return out

    @staticmethod
    def _render_distributed_analyze(info: dict, wall_s: float) -> list[str]:
        """Trino-style per-fragment EXPLAIN ANALYZE text from a coordinator
        QueryInfo: each stage's annotated plan under a Fragment header with
        its wall interval, then the slowest operator across all stages."""
        text: list[str] = []
        slowest = None  # (ms, operator, stage_id, nid)
        for st in info.get("stages") or []:
            hdr = f"Fragment {st['stage_id']} [{st['output_kind']}]"
            iv = st.get("wall_interval_s")
            if iv:
                hdr += f"  wall: {iv[0] * 1e3:.0f}..{iv[1] * 1e3:.0f} ms"
            hdr += f"  tasks: {len(st.get('tasks') or [])}"
            text.append(hdr)
            text.extend("  " + ln for ln in st.get("plan") or [])
            for nid, s in (st.get("operators") or {}).items():
                ms = s.get("ms")
                if ms is not None and (slowest is None or ms > slowest[0]):
                    slowest = (ms, s.get("operator", "?"), st["stage_id"], nid)
        if slowest is not None:
            text.append(
                f"-- slowest operator: {slowest[1]} (stage {slowest[2]}, "
                f"node {slowest[3]}, {slowest[0]:.1f} ms eager)"
            )
        text.append(
            f"-- output rows: {info.get('output_rows', 0)}, "
            f"wall: {wall_s * 1e3:.1f} ms, cluster cpu: "
            f"{info.get('cpu_ms', 0):.1f} ms, stages: {info.get('stage_count', 0)}, "
            f"task retries: {info.get('task_retries', 0)}"
        )
        # memory-governance line (reference: QueryStats peakMemoryReservation
        # + blocked time): peak task reservation, total blocked-on-memory
        # wall, and how many tasks ran revocation-spilled
        text.append(
            f"-- peak memory: {info.get('peak_memory_bytes', 0)} B, "
            f"blocked on memory: {info.get('memory_blocked_ms', 0.0):.1f} ms, "
            f"revocations: {info.get('memory_revocations', 0)}"
        )
        # phase ledger footer (reference: QueryStats' queued/analysis/
        # planning/execution durations): where the wall actually went
        ledger = info.get("phase_ledger") or {}
        if ledger:
            text.append(
                "-- phases: "
                + ", ".join(
                    (
                        f"{k[: -len('_ms')]} {v:.1f} ms"
                        if k.endswith("_ms")
                        else f"{k} {v}"  # plain counts (fallback_executions)
                    )
                    for k, v in ledger.items()
                    if isinstance(v, (int, float))
                )
            )
        # result-cache footer (runtime/resultcache.py): the disposition the
        # plain query would have had (EXPLAIN ANALYZE itself always
        # executes) plus the cache key and any fragment-memo seeding
        cinfo = info.get("cache") or {}
        if cinfo.get("disposition"):
            line = f"-- cache: {cinfo['disposition']}"
            if cinfo.get("reason"):
                line += f" ({cinfo['reason']})"
            if cinfo.get("key"):
                line += f" key={cinfo['key']}"
            if cinfo.get("memo_hits"):
                line += f" [fragment memo hits: {cinfo['memo_hits']}]"
            text.append(line)
        # crash-recovery footer: present only on queries a restarted
        # coordinator resumed from the journal (runtime/journal.py)
        rec = info.get("recovery") or {}
        if rec.get("resumed"):
            text.append(
                f"-- recovery: resumed from journal (replay "
                f"{rec.get('journal_replay_ms', 0.0):.1f} ms, stages "
                f"re-read from spool: {rec.get('stages_resumed', 0)}, "
                f"parts re-read: {rec.get('parts_resumed', 0)})"
            )
        # split footer: present only under split_driven_scans — how many
        # morsels the scans enumerated and what the scheduler did with
        # them (runtime/splits.py)
        spl = info.get("splits") or {}
        if spl.get("splits"):
            line = (
                f"-- splits: {spl.get('splits', 0)} total over "
                f"{spl.get('stages', 0)} scan stage(s), pad "
                f"{spl.get('pad_rows', 0)} rows "
                f"(completed: {spl.get('completed', 0)}, retries: "
                f"{spl.get('retries', 0)}, steals: {spl.get('steals', 0)}"
            )
            if spl.get("precommitted"):
                line += f", re-read from spool: {spl['precommitted']}"
            if spl.get("parked"):
                line += f", park deferrals: {spl['parked']}"
            text.append(line + ")")
        # anomaly-sentinel footer: present only when the sentinel flagged
        # this run against its planhash's rolling baseline (coordinator
        # _score_anomalies over runtime/history.py baselines)
        for a in info.get("anomalies") or []:
            base = info.get("baseline") or {}
            line = f"-- anomaly: {a.get('kind')}"
            detail = ", ".join(
                f"{k} {v}" for k, v in sorted(a.items()) if k != "kind"
            )
            if detail:
                line += f" ({detail})"
            if base.get("samples"):
                line += f" [baseline: {base['samples']} runs]"
            text.append(line)
        # fleet footer: present only on queries a surviving fleet member
        # adopted from a dead peer's journal (runtime/fleet.py)
        flt = info.get("fleet") or {}
        if flt.get("adopted"):
            text.append(
                f"-- fleet: adopted from {flt.get('adopted_from')} by "
                f"{flt.get('coordinator_id')} (stages re-read from spool: "
                f"{flt.get('stages_resumed', 0)}, parts re-read: "
                f"{flt.get('parts_resumed', 0)})"
            )
        # per-signature compile attribution: every distinct XLA program
        # the query built, with its persistent-cache outcome breakdown
        for sig, s in (info.get("compile_signatures") or {}).items():
            cache = s.get("cache") or {}
            cache_txt = ", ".join(
                f"{k}: {v}" for k, v in sorted(cache.items()) if v
            )
            # compile-resilience disposition: async | fallback | timeout
            # (exec/compilesvc.py) — which path executions of this
            # signature actually took while the program was (or wasn't)
            # being built
            flags = []
            if s.get("timeouts"):
                flags.append(f"timeout x{s['timeouts']}")
            fb = s.get("fallbacks") or {}
            if fb:
                flags.append(
                    "fallback "
                    + ", ".join(f"{r}: {c}" for r, c in sorted(fb.items()))
                )
            if (s.get("modes") or {}).get("async"):
                flags.append("async")
            text.append(
                f"-- compile: {sig} x{s.get('compiles', 0)} "
                f"{s.get('compile_s', 0.0) * 1e3:.1f} ms"
                + (f" [persistent cache: {cache_txt}]" if cache_txt else "")
                + (f" [{'; '.join(flags)}]" if flags else "")
            )
        # roofline footer (coordinator roofline plane over
        # utils/roofline.py): achieved bandwidth per executed signature
        # as a fraction of what this device can actually sustain
        roofline = info.get("roofline") or {}
        dev = roofline.get("device") or {}
        for s in roofline.get("signatures") or []:
            line = (
                f"-- roofline: {s.get('signature', '?')} "
                f"{s.get('gflop_per_sec', 0.0):.3f} GFLOP/s, "
                f"{s.get('gb_per_sec', 0.0):.3f} GB/s achieved over "
                f"{s.get('execute_ms', 0.0):.1f} ms execute"
            )
            if dev.get("hbm_gbps"):
                line += (
                    f" ({s.get('pct_of_roofline', 0.0):.1f}% of "
                    f"{dev['hbm_gbps']:g} GB/s "
                    f"{dev.get('device_kind', '?')})"
                )
            text.append(line)
        if info.get("device_gb_per_sec") is not None:
            text.append(
                f"-- device bandwidth: {info['device_gb_per_sec']:.3f} "
                f"GB/s achieved query-wide"
            )
        # exchange footer (per-stage link accounting folded by the
        # coordinator): what the exchange plane actually moved and how fast
        for st in info.get("exchange") or []:
            if not st.get("bytes"):
                continue
            line = (
                f"-- exchange: stage {st.get('stage_id')} "
                f"{st.get('bytes', 0)} B over {st.get('wall_ms', 0.0):.1f} "
                f"ms ({st.get('fetches', 0)} fetches"
            )
            if st.get("gb_per_sec") is not None:
                line += f", {st['gb_per_sec']:.3f} GB/s"
            line += f", {len(st.get('links') or {})} link(s))"
            text.append(line)
        return text

    def cache_invalidate(self, name: str) -> None:
        """Typed result/fragment-cache invalidation for a mutated table —
        every write statement (and runtime/dml.py) routes through here so a
        cached result can never survive DML on a table it read."""
        cache = getattr(self, "result_cache", None)
        memo = getattr(self, "fragment_memo", None)
        fp = getattr(self, "_fastpath", None)
        if cache is None and memo is None and fp is None:
            return
        try:
            _, catalog, table = self._target_ref(name)
        except KeyError:
            return  # dropping an unknown catalog's table: nothing cached
        table = table.split(".")[-1]
        if cache is not None:
            cache.invalidate_table(catalog, table)
        if memo is not None:
            memo.invalidate_table(catalog, table)
        if fp is not None:
            fp.invalidate_table(catalog, table)

    def _resolve_prepared(self, name: str, prepared: Optional[dict] = None) -> str:
        """Prepared-statement lookup: the client-held overlay (protocol
        headers) wins over the engine's session registry."""
        if prepared and name in prepared:
            return prepared[name]
        if name not in self._prepared:
            raise KeyError(f"prepared statement not found: {name}")
        return self._prepared[name]

    def _target_conn(self, name: str):
        """Resolve a possibly `catalog.table`-qualified DDL/DML target
        (Trino 2-part semantics: an unknown first part falls back to a plain
        table name in the default catalog)."""
        conn, _catalog, table = self._target_ref(name)
        return conn, table

    def _check_write(self, name: str, operation: str) -> None:
        _, catalog, table = self._target_ref(name)
        self.access_control.check_can_write(self.user, catalog, table, operation)

    def _target_ref(self, name: str):
        """(connector, catalog name, table name) of a DDL/DML target."""
        if "." in name:
            parts = name.split(".")
            try:
                return self.catalogs.get(parts[0]), parts[0], parts[-1]
            except KeyError:
                pass
        return self.catalogs.get(self.default_catalog), self.default_catalog, name

    # ------------------------------------------------------------ write path
    def _insert(self, table: str, columns, cols: list) -> int:
        conn, table = self._target_conn(table)
        schema = conn.table_schema(table)
        names = list(columns) if columns else [c.name for c in schema.columns]
        return self._insert_resolved(conn, table, names, cols)

    def _insert_resolved(
        self, conn, table: str, names: list, cols: list, stage=None
    ) -> int:
        """Resolve query columns against the table schema and either insert
        directly (legacy path) or stage into the given WriteTransaction."""
        schema = conn.table_schema(table)
        if len(names) != len(cols):
            raise ValueError(f"INSERT column count mismatch: {len(names)} vs {len(cols)}")
        data = {}
        for cname, arr in zip(names, cols):
            t = schema.type_of(cname)
            if t.is_string or isinstance(arr, np.ma.MaskedArray):
                # astype on a MaskedArray preserves the mask; np.asarray
                # would silently strip it and persist garbage for NULL lanes
                data[cname] = arr if t.is_string else arr.astype(t.np_dtype)
            else:
                data[cname] = np.asarray(arr).astype(t.np_dtype)
        n = len(cols[0]) if cols else 0
        for c in schema.columns:  # unreferenced columns default to zero values
            if c.name not in data:
                data[c.name] = np.zeros(
                    (n,), dtype=object if c.type.is_string else c.type.np_dtype
                )
        if stage is not None:
            stage.stage_insert(data)
            return n
        return conn.insert(table, data)

    def _insert_values(self, stmt, stage=None) -> int:
        from ..plan.ir import Const
        from ..plan.planner import Scope, _Translator

        conn, table = self._target_conn(stmt.table)
        schema = conn.table_schema(table)
        names = list(stmt.columns) if stmt.columns else [c.name for c in schema.columns]
        from ..plan.planner import _cast_ir

        t = _Translator(Scope([]))
        rows = []
        for row in stmt.rows:
            vals = []
            for ci, e in enumerate(row):
                ir = t.translate(e)
                if not isinstance(ir, Const):
                    raise ValueError(f"INSERT VALUES must be literals: {e}")
                # coerce to the column type (e.g. 1.5 -> scaled decimal lanes)
                ir = _cast_ir(ir, schema.type_of(names[ci]))
                vals.append(ir.value)
            rows.append(vals)
        n = len(rows)
        data = {}
        for ci, cname in enumerate(names):
            typ = schema.type_of(cname)
            col = [r[ci] for r in rows]
            nulls = np.array([v is None for v in col], dtype=bool)
            if nulls.any():
                fill = "" if typ.is_string else 0
                arr = np.asarray(
                    [fill if v is None else v for v in col],
                    dtype=object if typ.is_string else typ.np_dtype,
                )
                data[cname] = np.ma.MaskedArray(arr, mask=nulls)
            else:
                data[cname] = np.asarray(
                    col, dtype=object if typ.is_string else typ.np_dtype
                )
        for c in schema.columns:
            if c.name not in data:
                data[c.name] = np.zeros(
                    (n,), dtype=object if c.type.is_string else c.type.np_dtype
                )
        if stage is not None:
            stage.stage_insert(data)
            return n
        return conn.insert(table, data)
