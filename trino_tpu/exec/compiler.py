"""Plan compiler + local executor.

The reference's LocalExecutionPlanner (sql/planner/LocalExecutionPlanner.java
:408) visits the plan and wires OperatorFactory chains that pull pages
through virtual calls (operator/Driver.java:372).  Here the visitor *traces*
the whole plan into ONE jax.jit program: every operator contributes
vectorized ops over (columns, live-mask) pairs and XLA fuses the chain —
per-page virtual dispatch becomes a single compiled kernel per fragment.

Capacity protocol (the static-shape answer to dynamic selectivity/fan-out,
replacing the reference's growable hash tables and blocking memory futures):
size, run, grow on overflow, tighten once, persist — `LocalExecutor.execute`
does it, exec/capcache.py's docstring describes it, and the compiled program
is cached per (plan, capacities).
"""

from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..connectors.spi import CatalogManager
from ..data.page import CodedStrings, Column, Page
from ..data.types import Type
from ..ops.expr import ColumnVal, column_val, eval_expr, eval_predicate, param_context
from ..ops.kernels import JOIN_ROWS
from ..ops.relops import (
    MINMAX_KINDS, AggSpec, SortSpec, broadcast_single_row, compact_rows,
    equi_join, group_aggregate, limit_mask, sort_rows, top_n, unnest_expand,
)
from .capcache import TIGHTENED, load_caps, store_caps
from ..plan.ir import Call, field_refs
from ..plan.nodes import (
    Aggregate, Compact, Concat, Distinct, EnforceSingleRow, Exchange, Filter,
    Join, Limit, MatchRecognize, PlanNode, Project, RemoteSource, Sort,
    TableScan, TopN, Unnest, Values, Window,
)
from ..plan.reorder import is_ordered_join

__all__ = ["LocalExecutor", "MemoryBudgetExceeded", "FragmentCompileError"]

# collect_stats row counters ride the same `required` pytree as capacity
# overflow counters; the dict must stay int-keyed (shard_map sorts pytree
# dict keys, and mixed int/tuple keys don't sort together).  Capacity keys
# are small preorder ids, EnforceSingleRow uses -(nid+1), so a large base
# offset keeps the three ranges disjoint.
_STATS_ROWS_BASE = 1_000_000


class FragmentCompileError(RuntimeError):
    """The TPU's compiler refused a fragment program.  Raised through the
    compile service to the query instead of taking the eager fallback, so
    a kernel the chip cannot run is seen, with the compiler's message."""


class MemoryBudgetExceeded(RuntimeError):
    """Planned capacities exceed the task's device-memory budget; the FTE
    scheduler retries the task with an exponentially larger budget."""


@dataclass
class _Stage:
    cols: list[ColumnVal]
    live: jnp.ndarray

    @property
    def capacity(self) -> int:
        return int(self.live.shape[0])


def _node_ids(plan: PlanNode) -> dict[int, PlanNode]:
    """Stable preorder numbering (plan trees are immutable)."""
    out: dict[int, PlanNode] = {}

    def visit(n: PlanNode):
        out[len(out)] = n
        for c in n.children:
            visit(c)

    visit(plan)
    return out


# Read by nothing: benchmarks/tests/test_correct_spmd.py still patches the
# name (monkeypatch.setattr raises on a missing attribute).  The next
# `benchmark` PR drops that patch, and then this line.
_EAGER_SIZING_LIMIT = 0

# Per-connector dynamic-filter keep-mask cache size (ADVICE r3): in-process
# multi-task runs (DistributedQueryRunner workers, TASK retries) each build a
# fresh LocalExecutor, so without a cache the same (scan, filter-set)
# membership test — np.isin over up to 100k values against every scan row —
# reruns per task.  The cache dict lives ON the connector object (its
# lifetime scopes the cache; an id()-keyed global could alias a recycled
# address after GC) and entries key on (table, gen, split, filters).
_KEEP_MASK_CACHE_MAX = 64


def _plain(column):
    """A connector's dictionary-coded strings as the object array the row
    filters, the concatenation of splits and the padding work on."""
    return column.decode() if isinstance(column, CodedStrings) else column


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class LocalExecutor:
    """Single-process execution over device-resident table pages (the
    reference's PlanTester.executeStatement analogue, testing/PlanTester.java
    :706 — full engine, no HTTP)."""

    def __init__(self, catalogs: CatalogManager, default_catalog: str = "tpch"):
        self.catalogs = catalogs
        self.default_catalog = default_catalog
        # (part, num_parts): which slice of every table this executor scans —
        # (0, 1) = whole table; worker tasks get their assigned split range
        # (reference: SplitAssignment in TaskUpdateRequest)
        self.split = (0, 1)
        # pad every split to ceil(total/num_parts) rows (dead-tail mask) so
        # ALL parts share one compiled program — the out-of-core executor
        # iterates parts through a single jit cache entry this way
        self.pad_splits = False
        # split-driven scans (runtime/splits.py): fixed scan-page capacity
        # every morsel pads to, regardless of how many rows its row range
        # actually holds — scan shapes (and therefore jit signatures) stop
        # depending on data scale; only the split COUNT scales.  None = off.
        self.split_pad_rows: Optional[int] = None
        # dynamic filters: scan_node_id -> (ScanFilter, ...) applied host-side
        # before upload (exec/dynfilter.py); rows outside the build-side key
        # domain never cost HBM bandwidth or kernel lanes
        self.scan_filters: dict = {}
        self.rows_pruned = 0  # observability: dynamic-filter effectiveness
        self._table_cols: dict = {}
        self._table_pages: dict = {}  # page-object identity cache (CSE memo)
        self._table_live: dict = {}  # (catalog, table, gen, split) -> live rows
        self._jit_cache: dict = {}
        # per-task device-memory budget in bytes (0/None = unlimited): the
        # FTE scheduler grows this across task retries (reference:
        # ExponentialGrowthPartitionMemoryEstimator); enforcement is an
        # up-front estimate over planned capacities, the TPU analogue of
        # reserving from a memory pool before running
        self.memory_budget_bytes: Optional[int] = None
        # last up-front estimate computed at the budget check — surfaced by
        # the worker next to its NodeMemoryPool reservation (memory plane)
        self.last_estimated_bytes = 0
        # caps that completed a query without overflow, keyed by plan: repeat
        # executions skip the growth retries (the reference's runtime-adaptive
        # statistics feedback, AdaptivePlanner, in miniature)
        self._learned_caps: dict[PlanNode, dict[int, int]] = {}
        # plans whose learned tiers were tightened and have not run since:
        # their next build says so (`compile` span, cause `caps_tightened`)
        self._tightened: set[PlanNode] = set()
        # operator-stats collection (reference: OperatorStats via
        # OperatorContext): when set, execute() reports every node's live
        # output-row count from inside the compiled program and leaves the
        # per-operator summary in last_operator_stats — works for the jitted,
        # eager and SPMD paths alike, so distributed tasks carry stats too
        self.collect_operator_stats = False
        self.last_operator_stats: dict[int, dict] = {}
        self.last_execute_wall_ms: Optional[float] = None
        # compile/execute attribution (utils/profiler.py): every jit-cache
        # miss appends {signature, compile_s, cache, flops, bytes_accessed}
        # here, and execute() rolls the walls spent THIS call into
        # last_compile_ms/last_execute_ms — the worker ships both on
        # task.stats and the coordinator folds them into the phase ledger
        self.compile_events: list[dict] = []
        self.last_compile_ms = 0.0
        self.last_execute_ms = 0.0
        # per-signature execute ledger for the LAST execute() call:
        # sig -> {executes, fallback_executes, execute_s}.  Unlike
        # compile_events (misses only) this names every dispatched
        # signature — warm runs included — so the roofline plane can
        # join it with the profiler's flops/bytes per signature
        self.execute_events: dict[str, dict] = {}
        # compile resilience plane (exec/compilesvc.py): bound how long a
        # query blocks on XLA compile.  budget 0 == wait for the compile
        # (bounded only by the deadline); deadline 0 == no deadline.  When
        # the budget expires first the query runs the eager fallback path
        # and the compiled program swaps in on the next execution.
        self.compile_wait_budget_ms = 0
        self.compile_deadline_s = 0.0
        self.compile_service = None  # None == process-global SERVICE
        # worker tasks wire their FaultInjector + task id so COMPILE_SLOW /
        # COMPILE_FAIL faults fire inside this executor's build jobs
        self.fault_injector = None
        self.fault_task_id = "local"
        # fallback attribution: every fallback execution appends
        # {signature, reason, wait_ms} here (mirrored into compile_events
        # so the worker->coordinator stats pipeline carries it for free)
        self.fallback_events: list[dict] = []
        self.last_fallback_reason: Optional[str] = None
        # the owner's utils.tracing.Tracer (Engine, Coordinator and Worker
        # hand over their own): scan_load / compile / dispatch / device_wait /
        # operator_stats open as children of whatever span the owner has
        # open on this thread.  None opens nothing.
        self.tracer = None
        # the owner's exec.resident.ResidentStore, handed over like the
        # tracer: table columns that can be asked for again live there and
        # outlive this executor.  None keeps every column in _table_cols
        # (tests, scripts, and the out-of-core executors of exec/spill.py,
        # which exist to release HBM between slices).
        self.resident = None
        self._released_seen = 0  # the store's `released` at the last execute
        # bytes table_page has put on the device, and the columns it made
        # them from, over this executor's life (scan_load reports the deltas)
        self.h2d_bytes = 0
        self.columns_loaded = 0
        # of the current `scan_load`: seconds in _load_columns outside the
        # uploads, and what the connector said of where its columns were
        self.host_prepare_s = 0.0
        self.load_sources: set = set()

    def _span(self, name: str, **attributes):
        """-> a context manager yielding the open Span, or None without a
        tracer."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, **attributes)

    # ------------------------------------------------------------- table IO
    def table_page(
        self,
        catalog: str,
        table: str,
        columns: Sequence[str],
        types,
        scan_id: Optional[int] = None,
    ) -> Page:
        """Device page for the pruned column set; columns are materialized and
        uploaded lazily, once each (the scan-level projection pushdown the
        reference does via ConnectorPageSource lazy blocks).  scan_id scopes
        dynamic filters to THIS scan site (exec/dynfilter.py) and is part of
        the cache key so filtered and unfiltered sites never share columns.

        The columns live in the owner's resident store when there is one and
        they can be asked for again: the table's own rows (no dynamic
        filter, no pad to a morsel or a slice) at a version the connector
        vouches for.  Everything else stays in this executor's own
        dictionary and dies with it."""
        conn = self.catalogs.get(catalog)
        gen = getattr(conn, "generation", 0)  # writable connectors bump this
        filters = self.scan_filters.get(scan_id, ()) if scan_id is not None else ()
        version = None
        if self.resident is not None and not (
            filters or self.split_pad_rows or self.pad_splits
        ):
            version = conn.scan_version(table)
        if version is not None:
            cols, n_live = self.resident.columns(
                conn, table, self.split, version, columns,
                lambda missing: self._load_columns(conn, table, missing, ()),
            )
            # one page per scan shape, good while the store hands out the
            # same columns: another version or a re-registered catalog makes
            # new Column objects, and the page is made again
            page_key = (catalog, table, tuple(columns), self.split)
            cached = self._table_pages.get(page_key)
            if cached is not None and all(
                a is b for a, b in zip(cached.columns, cols)
            ):
                return cached
        else:
            key_of = lambda c: (catalog, table, c, gen, self.split, filters)
            live_key = (catalog, table, gen, self.split, filters)
            missing = [c for c in columns if key_of(c) not in self._table_cols]
            if missing:
                loaded, n_live = self._load_columns(conn, table, missing, filters)
                for c, col in loaded.items():
                    self._table_cols[key_of(c)] = col
                if n_live is not None:
                    self._table_live[live_key] = n_live
            page_key = (catalog, table, tuple(columns), gen, self.split, filters)
            cached = self._table_pages.get(page_key)
            if cached is not None:
                return cached
            cols = tuple(self._table_cols[key_of(c)] for c in columns)
            n_live = self._table_live.get(live_key)
        live = None
        if n_live is not None:
            cap = cols[0].capacity if cols else 1
            live = jnp.arange(cap, dtype=jnp.int32) < n_live
        page = Page(cols, live)
        # identical scan sites get the IDENTICAL Page object: _trace_plan's
        # structural-CSE memo validates reuse by page identity, so two
        # unfiltered scans of the same table CSE while a dynamically-filtered
        # site (different `filters` key -> different object) never does
        self._table_pages[page_key] = page
        return page

    def _load_columns(self, conn, table: str, missing: list, filters: tuple,
                      place=jnp.asarray):
        """Read `missing` columns of this executor's split from the
        connector, apply the scan site's dynamic filters, pad, upload
        (`place`: a host array onto the device).
        -> ({name: Column}, live rows when padded else None)."""
        schema = conn.table_schema(table)
        gen = getattr(conn, "generation", 0)
        part, num_parts = self.split
        want = list(missing) + [
            f.column for f in filters if f.column not in missing
        ]
        splits = [
            s
            for i, s in enumerate(conn.get_splits(table, num_parts))
            if i % num_parts == part or num_parts == 1
        ]
        t_read = time.perf_counter()
        data, source = conn.read_split_from(splits[0], want)
        self.load_sources.add(source)
        if len(splits) > 1 or filters:
            data = {c: _plain(v) for c, v in data.items()}
        for s in splits[1:]:
            more, source = conn.read_split_from(s, want)
            self.load_sources.add(source)
            more = {c: _plain(v) for c, v in more.items()}
            data = {
                c: (
                    np.ma.concatenate([data[c], more[c]])
                    if isinstance(data[c], np.ma.MaskedArray)
                    or isinstance(more[c], np.ma.MaskedArray)
                    else np.concatenate([data[c], more[c]])
                )
                for c in want
            }
        if filters:
            nrows = len(next(iter(data.values()))) if data else 0
            cache = conn.__dict__.setdefault("_keep_mask_cache", {})
            mask_key = (table, gen, self.split, filters)
            keep = cache.get(mask_key)
            if keep is None or len(keep) != nrows:
                keep = np.ones((nrows,), dtype=bool)
                for f in filters:
                    vals = data[f.column]
                    if f.values is not None:
                        # dictionary-set domain (string keys): membership
                        base = (
                            np.ma.getdata(vals)
                            if isinstance(vals, np.ma.MaskedArray)
                            else vals
                        )
                        ok = np.isin(base, np.asarray(f.values, dtype=object))
                        if isinstance(vals, np.ma.MaskedArray):
                            ok &= ~np.ma.getmaskarray(vals)
                        keep &= ok
                    elif isinstance(vals, np.ma.MaskedArray):
                        # NULL probe keys never equi-match: prune them too
                        ok = (vals >= f.min) & (vals <= f.max)
                        keep &= np.asarray(ok.filled(False))
                    else:
                        keep &= (vals >= f.min) & (vals <= f.max)
                if len(cache) >= _KEEP_MASK_CACHE_MAX:
                    cache.clear()
                cache[mask_key] = keep
            self.rows_pruned += int(nrows - keep.sum())
            data = {c: data[c][keep] for c in missing}
        pad_to = 1  # kernels need capacity >= 1
        if filters:
            # pruned capacity varies run to run: pow2 padding keeps the
            # compiled-shape count logarithmic
            n_after = len(next(iter(data.values()))) if data else 0
            pad_to = 1 << max(0, (n_after - 1).bit_length())
        if self.pad_splits and num_parts > 1 and not filters:
            total = conn.estimated_row_count(table)
            if total:
                pad_to = max(1, -(-int(total) // num_parts))
        if self.split_pad_rows:
            # morsel mode: a fixed capacity wins over both the filtered
            # pow2 and the ceil(total/num_parts) pads (a filtered morsel
            # can only shrink below it, never grow past it)
            pad_to = max(pad_to, int(self.split_pad_rows))
        loaded, live_rows = {}, None
        placing = [0.0]

        def timed_place(host):
            t0 = time.perf_counter()
            try:
                return place(host)
            finally:
                placing[0] += time.perf_counter() - t0

        for c in missing:
            arr = data[c]
            n_live = len(arr)
            if n_live < pad_to:
                arr = _plain(arr)
                t = schema.type_of(c)
                fill = np.zeros(
                    (pad_to - n_live,), dtype=object if t.is_string else t.np_dtype
                )
                if t.is_string:
                    fill[:] = ""
                if isinstance(arr, np.ma.MaskedArray):
                    arr = np.ma.concatenate(
                        [arr, np.ma.MaskedArray(fill, mask=True)]
                    )
                else:
                    arr = np.concatenate([arr, fill]) if n_live else fill
                live_rows = n_live
            col = loaded[c] = Column.from_numpy(schema.type_of(c), arr, place=timed_place)
            self.columns_loaded += 1
            self.h2d_bytes += col.nbytes
        # read + code + narrow + pad: everything here but the uploads
        self.host_prepare_s += time.perf_counter() - t_read - placing[0]
        return loaded, live_rows

    def _load_inputs(self, nodes, remote_pages) -> dict[str, Page]:
        """The plan's leaf pages by node id, under one `scan_load` span."""
        inputs = {}
        with self._span("scan_load") as span:
            bytes0, loaded0, columns = self.h2d_bytes, self.columns_loaded, 0
            prepare0 = self.host_prepare_s
            self.load_sources.clear()
            store = self.resident
            if store is not None and store.released != self._released_seen:
                # the store let tables go since this (long-lived) executor
                # last ran: its page memo must not keep them on the device
                self._released_seen = store.released
                self._table_pages.clear()
            for i, n in nodes.items():
                if isinstance(n, TableScan):
                    columns += len(n.column_names)
                    inputs[str(i)] = self._scan_page(i, n)
                elif isinstance(n, RemoteSource):
                    inputs[str(i)] = remote_pages[n.fragment_id]
            if span is not None:
                span.attributes.update(
                    h2d_bytes=self.h2d_bytes - bytes0, columns=columns,
                    columns_cached=columns - (self.columns_loaded - loaded0),
                    # the furthest any column came from: made just now, read
                    # from the connector's files, or found on the device
                    source=next(
                        (s for s in ("generated", "file", "connector")
                         if s in self.load_sources), "resident"),
                    host_prepare_ms=(self.host_prepare_s - prepare0) * 1e3,
                )
        return inputs

    def _scan_page(self, nid: int, node: TableScan) -> Page:
        return self.table_page(
            node.catalog, node.table, node.column_names, node.output_types,
            scan_id=nid,
        )

    # ------------------------------------------------------------ execution
    def execute(
        self,
        plan: PlanNode,
        remote_pages: Optional[dict[int, Page]] = None,
        params: tuple = (),
    ) -> Page:
        """remote_pages: fragment_id -> input Page for RemoteSource leaves
        (multi-host task execution, runtime/worker.py).  `params`: bound
        prepared-statement parameter values (typed numpy scalars, one per
        ir.Param index) fed to the compiled program as jit ARGUMENTS — every
        binding of one prepared plan reuses a single compiled program
        (runtime/fastpath.py)."""
        import time as _time

        t0 = _time.perf_counter()
        self.last_compile_ms = 0.0  # accumulated by _run's jit-cache misses
        self.last_execute_ms = 0.0
        self.execute_events = {}
        nodes = _node_ids(plan)
        inputs = self._load_inputs(nodes, remote_pages)
        # `size`: where this run's capacities come from — `learned` by this
        # executor, `cached` by another (capcache), or `initial` from
        # statistics — their tiers and the memory estimate
        with self._span("size", source="learned") as span:
            caps = known = self._learned_caps.get(plan)
            # the protocol's one step down belongs to the run that first
            # converges the plan: in this executor, and (capcache) this process
            tighten = False
            if caps is None:
                cached, settled = load_caps(plan, inputs, self._caps_scope)
                tighten = not settled
                # nothing learned: the stats-sized capacities, which the
                # compiled program's overflow-retry loop below corrects,
                # unless the cache has them.  A cached entry from an older
                # code version may size fewer node kinds than the current
                # tracer reads — only trust it when it covers every
                # currently-sized node (else KeyError mid-trace)
                caps = self._initial_caps(nodes, inputs)
                source = "initial"
                if cached is not None and set(cached) >= set(caps):
                    caps, source = cached, "cached"
                if span is not None:
                    span.attributes["source"] = source
            # capacity bucketing (ROADMAP 2a): every cap — planner-fed,
            # stats-fed, cached from an older code version, or learned —
            # lands on a pow2 tier, so near-identical shapes collapse onto
            # ONE jit signature instead of each minting its own compiled
            # program.  Also snapshots the dict: the retry loop below mutates
            # caps in place, and learned/cached dicts must not alias it.
            caps = {nid: _pow2(max(int(c), 1)) for nid, c in caps.items()}
            budget = self.memory_budget_bytes
            if budget:
                est = self._estimate_bytes(inputs, caps)
                # recorded for the memory-governance plane: the worker
                # reports this alongside its NodeMemoryPool reservation so
                # the cluster memory manager sees estimated vs reserved
                # bytes per task
                self.last_estimated_bytes = est
                if est > budget:
                    raise MemoryBudgetExceeded(
                        f"task needs ~{est} bytes of device memory,"
                        f" budget is {budget}"
                    )
        # plans with host-collected aggregates (array_agg/map_agg/listagg)
        # cannot trace: their outputs intern structured values on the host.
        # Run them eagerly — op-by-op dispatch with concrete arrays.
        eager_only = _has_host_aggs(plan)
        # why a build of this call is for other tiers than the plan's last
        tier_cause = "caps_tightened" if plan in self._tightened else "caps_tier"
        self._tightened.discard(plan)
        grown: set[int] = set()
        for _ in range(12):  # capacity-retry loop (jitted path)
            if eager_only:
                with param_context(params):
                    out_page, required = _trace_plan(
                        plan, inputs, caps, collect_stats=self.collect_operator_stats
                    )
                required = {k: int(v) for k, v in required.items()}
            else:
                out_page, required = self._run(
                    plan, inputs, caps, params, tier_cause
                )
            for key, val in required.items():
                if isinstance(key, int) and key < 0 and int(val) > 1:
                    raise RuntimeError(
                        "Scalar sub-query has returned multiple rows"
                    )
            overflow = {
                nid: int(req)
                for nid, req in required.items()
                if nid in caps and int(req) > caps[nid]
            }
            if not overflow:
                for nid, cap in caps.items():
                    if nid in required:
                        op = type(nodes[nid]).__name__
                        FRAME_LANES.labels(op).inc(cap)
                        FRAME_LIVE_ROWS.labels(op).inc(required[nid])
                        if is_ordered_join(nodes[nid]):
                            JOIN_ROWS.labels("actual").inc(required[nid])
                self._settle(plan, nodes, inputs, caps, known, required,
                             grown, tighten)
                # execute wall = everything this call that wasn't compile
                # (table IO, kernel dispatch, an eager fallback); the compile
                # side was accumulated by _run as it hit jit-cache misses
                wall_s = _time.perf_counter() - t0
                self.last_execute_ms = max(
                    0.0, wall_s * 1e3 - self.last_compile_ms
                )
                if self.collect_operator_stats:
                    with self._span("operator_stats"):
                        jax.block_until_ready([c.data for c in out_page.columns])
                        self._record_operator_stats(
                            nodes, required, (_time.perf_counter() - t0) * 1e3
                        )
                return out_page
            for nid, req in overflow.items():
                caps[nid] = _pow2(max(req, caps[nid] * 2))
            grown.update(overflow)
            tier_cause = "caps_tier"
        raise RuntimeError(f"capacity retry loop did not converge: {caps}")

    def _settle(self, plan, nodes, inputs, caps, known, required, grown,
                tighten: bool) -> None:
        """After a converged run: the one step down, and the tiers kept for
        this executor's next run and (capcache) for other executors.  A run
        at tiers this executor had learned settles nothing and opens no
        span."""
        self._learned_caps[plan] = caps
        if not tighten and caps == known:
            return
        with self._span("settle") as span:
            if tighten and self._tighten(nodes, caps, required, grown):
                self._tightened.add(plan)
            stored = caps != known  # learned or tightened in this run
            if stored:
                store_caps(plan, inputs, caps, self._caps_scope)
            if span is not None:
                span.attributes["stored"] = stored

    @staticmethod
    def _tighten(nodes, caps, required, grown) -> bool:
        """The protocol's step down (reference: AdaptivePlanner fed by
        runtime stats): kernel work scales with a node's tier, not with its
        live rows, so a node whose observed need lies far under its tier
        gets `_pow2(2 * need + 1024)` for every later run (and, through the
        capacity cache, every later executor).  Every sized kind reports a
        need that does not depend on the tier it ran at — surviving rows
        (Compact), groups (Aggregate, Distinct), the expansion's total
        (Join, Unnest), the fullest bucket over the devices (Exchange) — so
        one rule and one headroom serve them all.  Two exceptions: a TopN
        keeps its floor (its need is the radix threshold's ties, which a
        float key can multiply; `_initial_caps` says what a wrong guess
        costs), and a node the retry loop grew in this call stays grown.
        -> whether any tier changed."""
        changed = False
        for nid, cap in caps.items():
            need = required.get(nid)
            if need is None or nid in grown or isinstance(nodes[nid], TopN):
                continue
            tight = _pow2(2 * int(need) + 1024)
            if tight < cap:
                caps[nid] = tight
                TIGHTENED.labels(type(nodes[nid]).__name__).inc()
                changed = True
        return changed

    def execute_to_rows(self, plan: PlanNode) -> list[tuple]:
        return self.execute(plan).to_pylist()

    def _estimate_bytes(self, inputs, caps) -> int:
        """Planned device-memory footprint: every stateful node's capacity
        times a nominal row width, plus the resident input pages."""
        total = 0
        for page in inputs.values():
            for col in page.columns:
                total += int(col.capacity) * col.data.dtype.itemsize
        ncols = max((len(p.columns) for p in inputs.values()), default=4)
        for cap in caps.values():
            total += int(cap) * 8 * ncols
        return total

    def _initial_caps(self, nodes, inputs) -> dict[int, int]:
        # stats-fed first guesses (plan/stats.py: group-key NDV products,
        # join fan-out); the retry loop corrects upward when stats are off.
        # This replaces round 1's blind 65536 clamp, whose guaranteed
        # retries recompiled whole fragments on high-cardinality group-bys.
        from ..plan.stats import estimate as _est

        caps: dict[int, int] = {}

        def est_groups(n: PlanNode) -> Optional[int]:
            try:
                return int(_est(n, self.catalogs).rows * 1.3) + 16
            except Exception:
                return None

        def size_of(nid: int, n: PlanNode) -> int:
            if isinstance(n, (TableScan, RemoteSource)):
                return inputs[str(nid)].capacity
            if isinstance(n, Values):
                return max(len(n.rows), 1)
            child_ids = _child_ids(nodes, nid)
            child_sizes = [size_of(c, nodes[c]) for c in child_ids]
            if isinstance(n, (Aggregate, Distinct)):
                hint = est_groups(n)
                cap = hint if hint is not None else 65536
                caps[nid] = min(_pow2(max(cap, 1024)), _pow2(max(child_sizes[0], 1)))
                return caps[nid]
            if isinstance(n, Join):
                if n.kind in ("semi", "anti", "null_anti", "mark", "mark_in"):
                    caps[nid] = _pow2(max(max(child_sizes), 1))
                    return child_sizes[0]
                if n.kind == "cross":
                    return child_sizes[0]
                hard = _pow2(max(max(child_sizes), 1))
                # stats-sized expansion frame: the join kernel's sorts,
                # searchsorteds and column gathers all run at CAPACITY lanes,
                # so a worst-case frame (max child capacity) made a 29k-row
                # join cost like an 8M-row one.  2x the Selinger estimate,
                # floored, capped by the worst case; the overflow retry loop
                # corrects underestimates (reference: join stats sizing the
                # hash table, JoinStatsRule + FlatHash growth)
                hint = est_groups(n)
                if hint is not None:
                    caps[nid] = min(hard, _pow2(max(2 * hint, 4096)))
                else:
                    caps[nid] = hard
                if n.kind == "left":
                    return caps[nid] + child_sizes[0]
                if n.kind == "full":
                    return caps[nid] + child_sizes[0] + child_sizes[1]
                return caps[nid]
            if isinstance(n, Compact):
                # start as a pass-through (cap = input frame): whether this
                # point actually compacts is learned from the first run's
                # TRUE surviving count (`_tighten`)
                caps[nid] = _pow2(max(child_sizes[0], 1))
                return caps[nid]
            if isinstance(n, TopN):
                # radix-select candidate buffer (ops/relops.py top_n): room
                # for K plus boundary ties; sort fallback never overflows it.
                # 16k floor: the 32-bit radix threshold over a float key can
                # tie thousands of rows, and an undersized guess costs a
                # whole-plan recompile (q03 SF1: 215s wasted on the retry) —
                # 16k extra lanes in the candidate sort cost microseconds
                caps[nid] = min(
                    _pow2(max(2 * n.count + 512, 16384)),
                    _pow2(max(child_sizes[0], 1)),
                )
                return min(n.count, child_sizes[0])
            if isinstance(n, Unnest):
                # unknown fan-out: guess 4x, the retry loop corrects
                caps[nid] = _pow2(max(child_sizes[0] * 4, 1024))
                return caps[nid]
            return child_sizes[0]

        size_of(0, nodes[0])
        return caps

    def _record_operator_stats(self, nodes, required, wall_ms=None) -> None:
        """Distill a run's `required` row counters into the per-operator
        summary the stats pipeline ships worker -> coordinator:
        {nid: {operator, rows, rows_in, output_bytes, invocations}}."""
        rows = {
            k - _STATS_ROWS_BASE: int(v)
            for k, v in required.items()
            if isinstance(k, int) and k >= _STATS_ROWS_BASE
        }
        stats: dict[int, dict] = {}
        for nid, node in nodes.items():
            if nid not in rows:
                continue  # CSE-reused subtree interiors carry no counter
            child_rows = [rows[c] for c in _child_ids(nodes, nid) if c in rows]
            stats[nid] = {
                "operator": type(node).__name__,
                "rows": rows[nid],
                "rows_in": sum(child_rows) if child_rows else rows[nid],
                "output_bytes": rows[nid] * _est_row_bytes(node),
                "invocations": 1,
            }
        self.last_operator_stats = stats
        self.last_execute_wall_ms = wall_ms

    def explain_analyze(
        self,
        plan: PlanNode,
        remote_pages: Optional[dict[int, Page]] = None,
        params: tuple = (),
    ) -> tuple[Page, dict]:
        """Execute with per-operator observability (the reference's
        OperatorStats rolled up by ExplainAnalyzeOperator).

        Returns (page, stats) where stats[nid] = {"rows": int, "ms": float}.
        Per-operator wall time comes from an eager pass with a block-until-
        ready hook after every node — dispatch overhead inflates absolute
        numbers, but relative attribution identifies the slow operator; the
        row counts come from the jitted run and are exact.  `remote_pages`
        lets worker tasks analyze fragments with RemoteSource leaves
        (distributed EXPLAIN ANALYZE, runtime/worker.py)."""
        import time

        # ensure capacities are learned + result correct (jitted path)
        page = self.execute(plan, remote_pages, params=params)
        caps = self._learned_caps[plan]
        nodes = _node_ids(plan)
        inputs = {}
        for i, n in nodes.items():
            if isinstance(n, TableScan):
                inputs[str(i)] = self.table_page(
                    n.catalog, n.table, n.column_names, n.output_types, scan_id=i
                )
            elif isinstance(n, RemoteSource):
                inputs[str(i)] = remote_pages[n.fragment_id]
        stats: dict[int, dict] = {}

        last = [time.perf_counter()]

        def hook(nid, node, stage):
            jax.block_until_ready(stage.live)
            now = time.perf_counter()
            stats[nid] = {"ms": (now - last[0]) * 1e3}
            last[0] = now

        with param_context(params):
            _, required = _trace_plan(
                plan, inputs, caps, node_hook=hook, collect_stats=True
            )
        for key, val in required.items():
            if isinstance(key, int) and key >= _STATS_ROWS_BASE:
                stats.setdefault(key - _STATS_ROWS_BASE, {})["rows"] = int(val)
        return page, stats

    def _cache_key(self, plan: PlanNode, inputs: dict[str, Page], caps, params=()):
        """(jit-cache key, treedef, avals) for one (plan, inputs, caps).
        The AOT-compiled entry is pinned to one input pytree + avals
        (unlike a lazy jit, which retraces transparently), so the key
        must carry the full abstract structure: a None column where a
        leaf used to be, or a reshaped dictionary, is a NEW program.
        Parameter VALUES never enter the key — only their avals (via the
        flattened (inputs, params) pytree), so distinct bindings share one
        program."""
        leaves, treedef = jax.tree_util.tree_flatten((inputs, tuple(params)))
        avals = tuple(
            (getattr(x, "shape", ()), str(getattr(x, "dtype", type(x).__name__)))
            for x in leaves
        )
        from ..ops.kernels import policy_key

        key = (plan, self.collect_operator_stats, tuple(sorted(caps.items())),
               tuple(sorted((k, p.capacity) for k, p in inputs.items())),
               treedef, avals, policy_key())
        return key, treedef, avals

    def _run(
        self,
        plan: PlanNode,
        inputs: dict[str, Page],
        caps: dict[int, int],
        params: tuple = (),
        tier_cause: str = "caps_tier",
    ):
        import time as _time

        from ..utils.compilecache import cache_events, cache_outcome
        from ..utils.profiler import PROFILER, cost_summary, signature_of
        from .compilesvc import FALLBACKS, SERVICE

        collect = self.collect_operator_stats
        params = tuple(params)
        # `program_lookup`: this executor's own cache of compiled programs,
        # and on a miss what names and makes the program to ask the compile
        # service for (the `compile` record begins at the miss, beside it)
        with self._span("program_lookup") as span:
            cache_key, treedef, avals = self._cache_key(plan, inputs, caps, params)
            outcome = "hit" if cache_key in self._jit_cache else "miss"
            _JIT_CACHE_LOOKUPS.labels(outcome).inc()
            if span is not None:
                span.attributes["jit_cache"] = outcome
            if outcome == "miss":
                # A capacity-overflow retry lands here again with new caps —
                # a new SIGNATURE: the signature carries the tiers, so a
                # compile on a statement that was warm names the node whose
                # capacity grew (profiler ledger, `compile` span), not just
                # the plan.
                t_miss = _time.perf_counter()
                cpu_miss = self.tracer.cpu_now() if self.tracer is not None else None
                sig = signature_of(plan, caps)
                svc = self.compile_service or SERVICE
                # snapshot caps for the traced closure: execute()'s overflow
                # retry loop mutates its dict in place, and a compile still
                # queued in the service after a fallback must trace the
                # tiers its signature was named for
                call, holder = self._make_call(plan, dict(caps), collect)
        if outcome == "miss":
            def build(_call=call, _holder=holder):
                # AOT lower+compile (instead of letting the first dispatch
                # compile lazily) so compile wall is measured apart from
                # execute wall and cost_analysis() is capturable
                events_before = cache_events()
                jitted = jax.jit(_call)
                t0 = _time.perf_counter()
                cost = None
                lazy = False
                try:
                    lowered = jitted.lower(inputs, params)
                    if "lowered" in _holder:  # the maker wants a look at it
                        _holder["lowered"](lowered)
                    fn = lowered.compile()
                    cost = cost_summary(fn)
                except Exception as exc:
                    if jax.default_backend() == "tpu":
                        # the chip's compiler refused the fragment (a Pallas
                        # kernel Mosaic cannot lower, a program that does
                        # not fit HBM): that is an error with the
                        # compiler's message, never a silent switch to a
                        # path that hides the device
                        raise FragmentCompileError(
                            f"fragment {sig} failed to compile for the TPU:"
                            f" {type(exc).__name__}: {exc}"
                        ) from exc
                    # AOT unsupported for this program on the CPU backend:
                    # fall back to the lazy jit; its first dispatch folds
                    # compile into execute wall (attribution degrades,
                    # results don't)
                    fn = jitted
                    lazy = True
                compile_s = _time.perf_counter() - t0
                cache_result = cache_outcome(events_before)
                PROFILER.record_compile(sig, compile_s, cache_result, cost)
                return {"fn": fn, "holder": _holder, "sig": sig,
                        "compile_s": compile_s, "cache": cache_result,
                        "cost": cost, "lazy": lazy}

            # the service key spans executors: (signature, stats mode,
            # pytree structure, avals, kernel policy).  The treedef hashes
            # trace-time Dictionary objects BY IDENTITY (data/page.py), so a
            # shared program can never decode strings through another
            # input's dictionary; the policy fingerprint keeps a program
            # traced under one kernel policy (e.g. interpreted f32 segsums)
            # from swapping in for an executor running another.
            from ..ops.kernels import policy_key

            budget_ms = int(self.compile_wait_budget_ms or 0)
            out = svc.obtain(
                (sig, collect, treedef, avals, policy_key()) + self._program_scope,
                sig, build, tier_cause=tier_cause,
                wait_budget_s=(budget_ms / 1e3) if budget_ms > 0 else None,
                deadline_s=float(self.compile_deadline_s or 0.0),
                injector=self.fault_injector,
                fault_task_id=self.fault_task_id,
            )
            wait_ms = round(out.waited_s * 1e3, 3)
            self.last_compile_ms += wait_ms
            if self.tracer is not None:
                # fresh: this call's build ran (SERVICE.builds rose by it);
                # else the service handed over, or this call waited for, a
                # program some other execution built (cause `joined`)
                built = out.result if out.fresh and out.status == "ready" else {}
                self.tracer.record(
                    "compile", t_miss, cpu_start_s=cpu_miss,
                    signature=sig, cause=out.cause,
                    status=out.status, compile_s=built.get("compile_s", 0.0),
                    cache=built.get("cache"),
                )
            if out.status == "ready":
                res = out.result
                self._jit_cache[cache_key] = (res["fn"], res["holder"], sig)
                if out.fresh:
                    event = {
                        "signature": sig,
                        "compile_s": round(res["compile_s"], 4),
                        "cache": res["cache"],
                        "mode": "async" if budget_ms > 0 else "sync",
                    }
                    if res.get("lazy"):
                        event["lazy"] = True
                    if res["cost"]:
                        event.update(res["cost"])
                else:
                    # joined an in-flight compile or swapped in a program
                    # another execution finished in the background: the
                    # compile wall belongs to the owner, only the wait here
                    event = {"signature": sig, "mode": "async",
                             "wait_ms": wait_ms}
                self.compile_events.append(event)
            else:
                if isinstance(out.error, FragmentCompileError):
                    raise out.error
                # fallback: budget exhausted / deadline / compile failure /
                # poisoned signature.  Execute the eager uncompiled trace
                # (op-by-op dispatch, the same path host-agg plans use) —
                # bounded-latency degradation instead of a compile wall.
                reason = out.reason or "compile_wait"
                FALLBACKS.labels(reason).inc()
                PROFILER.record_fallback(sig, reason)
                self.last_fallback_reason = reason
                event = {"signature": sig, "mode": "fallback",
                         "reason": reason, "wait_ms": wait_ms}
                if out.status == "timeout":
                    event["error"] = "COMPILE_TIMEOUT"
                self.compile_events.append(event)
                self.fallback_events.append(dict(event))
                t0 = _time.perf_counter()
                out_page, required = self._trace_eager(
                    plan, inputs, dict(caps), params, collect
                )
                self._note_execute(
                    sig, _time.perf_counter() - t0, fallback=True
                )
                return out_page, {k: int(v) for k, v in required.items()}
        fn, holder, sig = self._jit_cache[cache_key]
        t0 = _time.perf_counter()
        # enqueue; returns early.  holder["dispatch"]: what the program's
        # maker has to say about every dispatch of it (exec/spmd.py)
        with self._span("dispatch", signature=sig, **holder.get("dispatch", {})):
            try:
                out_page, packed = fn(inputs, params)
            except TypeError:
                # AOT programs are pinned to one input pytree structure; a
                # structure drift the key missed (e.g. weak-type promotion)
                # must not fail the query — retrace with a lazy jit, counted
                # as a cache miss.  A genuine TypeError in the traced ops
                # re-raises from the lazy dispatch.
                _JIT_CACHE_LOOKUPS.labels("miss").inc()
                call, holder = self._make_call(plan, dict(caps), collect)
                fn = jax.jit(call)
                self._jit_cache[cache_key] = (fn, holder, sig)
                out_page, packed = fn(inputs, params)
        # the host blocks on the device here; the annotation puts the same
        # interval into a profiler trace, on the trace's clock
        with self._span("device_wait", signature=sig) as span, \
                jax.profiler.TraceAnnotation("device_wait"):
            vals = np.asarray(packed)  # ONE device->host transfer
            required = dict(zip(holder["keys"], vals.tolist()))
            if span is not None:
                span.attributes["d2h_bytes"] = vals.nbytes
                # the sizing closes here, where the program says what each
                # frame held: node -> [tier in lanes, rows live (`need`)];
                # a need above its tier is the overflow the caller retries
                names = holder.get("frame_names")
                if names is None:  # once a program: its caps are in its key
                    nodes = _node_ids(plan)
                    names = holder["frame_names"] = {
                        nid: f"{type(nodes[nid]).__name__}#{nid}" for nid in caps}
                span.attributes["frames"] = {
                    name: [caps[nid], required[nid]]
                    for nid, name in names.items() if nid in required}
        _note_device_memory()
        self._note_execute(sig, _time.perf_counter() - t0)
        return out_page, required

    # What a subclass that runs the same plans as another kind of program
    # (exec/spmd.py: one shard_map over a mesh) puts in their place.
    _program_scope: tuple = ()  # joins the compile service's key
    _caps_scope = ""  # joins the capacity cache's key

    def _make_call(self, plan: PlanNode, caps: dict[int, int], collect: bool):
        return _make_call(plan, caps, collect)

    def _trace_eager(self, plan, inputs, caps, params=(), collect=False):
        """The plan op by op, uncompiled -> (page, required): what `_run`
        falls back to when the compiled program is not to be had in time."""
        with param_context(params):
            return _trace_plan(plan, inputs, caps, collect_stats=collect)

    def _note_execute(
        self, sig: str, seconds: float, fallback: bool = False
    ) -> None:
        """Record one dispatch in both the process-global profiler and
        this executor's per-call ledger (the roofline plane's join key)."""
        from ..utils.profiler import PROFILER

        PROFILER.record_execute(sig, seconds, fallback=fallback)
        e = self.execute_events.setdefault(
            sig, {"executes": 0, "fallback_executes": 0,
                  "execute_s": 0.0, "fallback_execute_s": 0.0}
        )
        # fallback (eager) dispatch wall is kept apart: cost_analysis()
        # flops/bytes describe the COMPILED program, so folding eager wall
        # into execute_s would understate achieved bandwidth
        if fallback:
            e["fallback_executes"] += 1
            e["fallback_execute_s"] = round(
                e["fallback_execute_s"] + float(seconds), 6
            )
        else:
            e["executes"] += 1
            e["execute_s"] = round(e["execute_s"] + float(seconds), 6)


def page_rows(tracer, page: Page) -> list[tuple]:
    """A result page as Python rows, under a `to_rows` span of the owner's
    tracer (Coordinator, the fast path, Engine.query): `d2h_arrays` arrays
    asked of the device in one `jax.device_get`, which takes `fetch_ms` of
    the span; the rest is numpy and Python values."""
    with tracer.span("to_rows", d2h_bytes=page.nbytes) as span:
        t0 = time.perf_counter()
        fetched = page._fetch_host()
        span.attributes["fetch_ms"] = (time.perf_counter() - t0) * 1e3
        span.attributes["d2h_arrays"] = 1 + sum(
            a is not None for c in page.columns for a in (c.data, c.valid, c.data2))
        rows = page.to_pylist(fetched)
        span.attributes["rows"] = len(rows)
    return rows


def _make_call(plan: PlanNode, caps: dict[int, int], collect: bool):
    """Build the traced entry point for one (plan, caps, stats-mode).

    Packs every overflow counter into ONE int64 vector inside the jit:
    each device->host transfer is a synchronisation of its own, and
    fetching a dict of scalars one transfer at a time dominated query
    latency.  The key order is recorded at trace time in `holder`
    (deterministic per cache entry)."""
    holder: dict = {"keys": None}

    def call(pages, params=(), _holder=holder):
        from ..ops import kernels as _kernels

        with param_context(params):
            out_page, req = _trace_plan(plan, pages, caps, collect_stats=collect)
        # what every `dispatch` span of this program says of it: the
        # kernels the trace chose, as EXPLAIN ANALYZE's `-- kernel:` lines
        chosen = _kernels.describe(plan)
        if chosen:
            _holder["dispatch"] = {"kernels": "; ".join(chosen)}
        return out_page, _pack_required(req, _holder)

    return call, holder


def _pack_required(req: dict, holder: dict):
    """One int64 vector of the counters, their order left in `holder`."""
    keys = sorted(req, key=repr)
    holder["keys"] = keys
    if not keys:
        return jnp.zeros((0,), jnp.int64)
    return jnp.stack([jnp.asarray(req[k], jnp.int64) for k in keys])


def _est_row_bytes(node: PlanNode) -> int:
    """Nominal output-row width for the stats pipeline's output_bytes
    estimate (strings count as 16B dictionary-coded payload + pointer)."""
    total = 0
    try:
        types = node.output_types
    except Exception:
        return 8
    for t in types:
        if getattr(t, "is_string", False):
            total += 16
        else:
            try:
                total += int(np.dtype(t.np_dtype).itemsize)
            except Exception:
                total += 8
        total += 1  # validity mask byte
    return max(total, 1)


from ..utils.metrics import GLOBAL as _METRICS

_JIT_CACHE_LOOKUPS = _METRICS.counter(
    "trino_tpu_jit_cache_lookups_total",
    "Fragment jit-program cache lookups in LocalExecutor._run",
    ("result",),
)

# Kernel work scales with a sized node's tier (the lanes of its frame), not
# with the rows live in it: one pair of counters says how full the frames of
# every converged run were (benchmarks/layer_metrics/frame_fill_share.py
# reads the same two numbers a node from the `device_wait` span's `frames`).
FRAME_LANES = _METRICS.counter(
    "trino_tpu_frame_lanes_total",
    "Lanes of the capacity tiers that converged executions ran their sized"
    " plan nodes at, by node kind (Aggregate, Join, Compact, TopN, ...)",
    ("op",),
)
FRAME_LIVE_ROWS = _METRICS.counter(
    "trino_tpu_frame_live_rows_total",
    "Rows the compiled program reported live in those frames (each sized"
    " node's `need`: groups, surviving rows, a join's expansion)",
    ("op",),
)
DEVICE_MEMORY_PEAK = _METRICS.gauge(
    "trino_tpu_device_memory_peak_bytes",
    "peak_bytes_in_use of the device's memory_stats(), the largest over the"
    " local devices, read when a statement's program has run",
)
DEVICE_MEMORY_LIMIT = _METRICS.gauge(
    "trino_tpu_device_memory_limit_bytes",
    "bytes_limit of the same device's memory_stats()",
)


def _note_device_memory() -> None:
    """The two gauges from the devices' own counters; a backend that keeps
    none (the CPU) moves neither."""
    peak = limit = 0
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and int(stats.get("peak_bytes_in_use", 0)) > peak:
            peak = int(stats["peak_bytes_in_use"])
            limit = int(stats.get("bytes_limit", 0))
    if peak:
        DEVICE_MEMORY_PEAK.set(peak)
        DEVICE_MEMORY_LIMIT.set(limit)


def _has_host_aggs(plan: PlanNode) -> bool:
    """Plans that must run eagerly: host-collected aggregates intern
    structured values on the host, and MATCH_RECOGNIZE's backtracking walk
    is a host loop (reference: Matcher.java is likewise interpretive)."""
    from ..ops.relops import HOST_AGGS
    from ..plan.nodes import walk

    return any(
        isinstance(n, MatchRecognize)
        or (isinstance(n, Aggregate) and any(a.fn in HOST_AGGS for a in n.aggs))
        for n in walk(plan)
    )


def _child_ids(nodes: dict[int, PlanNode], nid: int) -> list[int]:
    n = nodes[nid]
    ids = []
    next_id = nid + 1
    for c in n.children:
        ids.append(next_id)
        next_id += len(_node_ids(c))
    return ids


def _trace_plan(
    plan: PlanNode,
    pages: dict[str, Page],
    caps: dict[int, int],
    num_devices: int = 1,
    axis: Optional[str] = None,
    collect_stats: bool = False,
    node_hook=None,
):
    """Trace a plan into jax ops.  With `axis` set, the trace happens inside
    shard_map and Exchange nodes lower to collectives (parallel/exchange.py);
    overflow counters are pmax-reduced so every device agrees on retries.

    collect_stats: also report each node's live output-row count under the
    int key `_STATS_ROWS_BASE + nid` in `required` — the per-operator row
    stats EXPLAIN ANALYZE renders (reference: OperatorStats via
    OperatorContext).  Under shard_map the counts are psum-reduced, so a
    distributed stage's row count is the sum over its shards.
    node_hook(nid, node, stage): called after each node emits; in eager
    (non-jit) execution the hook can block_until_ready for wall-clock
    attribution per operator."""
    required: dict[int, jnp.ndarray] = {}
    counter = [0]
    # Structural CSE: a WITH clause referenced twice plans as two structurally
    # equal subtrees (planner re-inlines the CTE); emit each distinct subtree
    # once and reuse its stage.  The reference gets this from iterative-
    # optimizer plan-node sharing; here frozen-dataclass equality is the memo
    # key.  Node-id numbering stays in pre-order, so on reuse the counter
    # skips the subtree's id range.
    memo: dict[PlanNode, tuple["_Stage", tuple[int, ...], int]] = {}

    def report(nid: int, value):
        # a one-device mesh skips the collective: pmax over one device is
        # the identity.  The skip stays because the one-device SPMD program
        # (Engine(distributed=True) on a single chip) then compiles with no
        # all-reduce at all, like the LocalExecutor program it equals
        if axis is not None and num_devices > 1:
            from ..parallel.exchange import pmax_count

            value = pmax_count(value, axis)
        required[nid] = value

    # id(mask) -> (mask, rows) for the masks made here of ones (a page with
    # no live mask of its own): their count is their length.  Summing one is
    # a reduction of a constant, which the TPU compiler folds on the host
    # one element at a time — 53 s of compiling for 6M rows, 500 s for 60M.
    all_live: dict[int, tuple] = {}

    def page_live(page):
        live = page.live_mask()
        if page.live is None:
            all_live[id(live)] = (live, page.capacity)
        return live

    def count_rows(nid_here: int, live) -> None:
        known = all_live.get(id(live))
        cnt = (jnp.int64(known[1]) if known is not None
               else jnp.sum(live.astype(jnp.int64)))
        if axis is not None and num_devices > 1:
            cnt = jax.lax.psum(cnt, axis)
        required[_STATS_ROWS_BASE + nid_here] = cnt

    def _scan_offsets(node: PlanNode) -> tuple[int, ...]:
        # pre-order offsets of the leaf nodes that read pages[str(nid)]
        return tuple(
            off
            for off, n in enumerate(_node_ids(node).values())
            if isinstance(n, (TableScan, RemoteSource))
        )

    def emit(node: PlanNode) -> _Stage:
        nid_here = counter[0]
        try:
            cached = memo.get(node)
        except TypeError:  # unhashable payload somewhere; trace normally
            cached = None
            hashable = False
        else:
            hashable = True
        if cached is not None:
            stage_c, offsets, orig_nid = cached
            # reuse is only sound if this site reads the SAME page objects:
            # dynamic filters (exec/dynfilter.py) prune scans per site, so a
            # structurally identical scan can carry different rows here
            if all(
                pages.get(str(nid_here + off)) is pages.get(str(orig_nid + off))
                for off in offsets
            ):
                counter[0] += len(_node_ids(node))
                if collect_stats:
                    count_rows(nid_here, stage_c.live)
                return _Stage(
                    [
                        ColumnVal(cv.data, cv.valid, cv.dict, cv.type, cv.data2)
                        for cv in stage_c.cols
                    ],
                    stage_c.live,
                )
        # the node's ops carry its name into the HLO metadata and from there
        # into a profiler trace (pre-order ids: stable across runs).  Names
        # only: no plan, capacity key, service key or fusion reads them.
        with jax.named_scope(f"{type(node).__name__}#{nid_here}"):
            stage = _emit(node)
        if hashable:
            memo[node] = (stage, _scan_offsets(node), nid_here)
        if collect_stats:
            count_rows(nid_here, stage.live)
        if node_hook is not None:
            node_hook(nid_here, node, stage)
        return stage

    def check_limbed(stage: _Stage, what: str) -> _Stage:
        # decimal128 surface: scan -> filter/project -> join -> aggregate
        # (+ CASE, sort/topn gathers).  The remaining ops that re-gather
        # columns would silently drop the high limb, so they refuse loudly
        # instead (Int128 paths widen per-operator over time)
        if any(cv.data2 is not None for cv in stage.cols):
            raise NotImplementedError(f"decimal128 columns through {what}")
        return stage

    def _try_fused_aggregate(node: Aggregate, nid: int) -> Optional[_Stage]:
        """Tentpole fusion: an Aggregate whose input is a straight
        Filter/Project chain over a TableScan collapses into one Pallas
        pass (ops/pallas/fused.py) that reads the scan columns from HBM
        exactly once.  Predicates and aggregate arguments are substituted
        down to scan level (plan/ir.substitute); anything the kernel can't
        express — wide key domains, non-dictionary keys, aggregates beyond
        sum/count/avg — declines here and takes the operator-at-a-time
        path below, so this is a pure fast path."""
        from ..ops import kernels as _kernels
        from ..ops.pallas import fused as _fused
        from ..plan.ir import FieldRef, substitute

        if axis is not None:
            return None  # sharded trace: per-shard partials need a merge
        policy = _kernels.get_policy()
        if not policy.enabled:
            return None
        if not (policy.interpret or jax.default_backend() == "tpu"):
            return None
        for a in node.aggs:
            if a.distinct or a.arg2 is not None or a.order_keys:
                return None
        # Compact points (plan/optimizer.py insert_compaction wraps every
        # filter over >= 64k rows, i.e. every real-scale scan filter) only
        # repack live rows: the kernel masks rows itself, so they fuse away
        # like the filters they wrap.  Without this the kernel was selected
        # at SF0.01 and never at SF1.
        chain: list[PlanNode] = []
        cur = node.child
        while isinstance(cur, (Filter, Project, Compact)):
            chain.append(cur)
            cur = cur.child
        if not isinstance(cur, TableScan):
            return None
        scan_nid = nid + 1 + len(chain)
        page = pages.get(str(scan_nid))
        if page is None or len(page.columns) != len(cur.output_types):
            return None
        scan_cols = [column_val(c) for c in page.columns]
        for cv, t in zip(scan_cols, cur.output_types):
            cv.type = t
        colmap: list = [FieldRef(i, t) for i, t in enumerate(cur.output_types)]
        filters = []
        for link in reversed(chain):
            if isinstance(link, Filter):
                filters.append(substitute(link.predicate, colmap))
            elif isinstance(link, Project):
                colmap = [substitute(e, colmap) for e in link.expressions]
        keys = [substitute(k, colmap) for k in node.group_keys]
        args = [
            None if a.arg is None else substitute(a.arg, colmap)
            for a in node.aggs
        ]
        recipe, _why = _fused.plan_pipeline(
            scan_cols, filters, keys,
            [a.fn for a in node.aggs], args, [a.type for a in node.aggs],
        )
        if recipe is None:
            return None
        counter[0] = scan_nid + 1  # consume the whole chain's id range
        live = page_live(page)
        form, tile = _fused.scatter_form(recipe)
        masked = page.live is not None
        operands, resident = _fused.operand_counts(recipe, masked)
        _kernels.record_dispatch(
            "fused_pipeline", "pallas",
            f"{len(filters)} filters {len(recipe.streams)} streams "
            f"domain {recipe.domain} scatter {form} tile {tile} "
            f"operands {operands} resident {resident} "
            f"params {len(recipe.params)}",
        )
        _kernels.FUSED_SCATTER.labels(form=form).inc()
        _kernels.FUSED_OPERANDS.labels(form="resident").inc(resident)
        _kernels.FUSED_OPERANDS.labels(form="prepared").inc(operands - resident)
        # a prepared statement's bindings: scalars of the program (tracers
        # under jit, from the parameter context), operands of the kernel
        bound = [eval_expr(prm, (), 1).data[0] for prm in recipe.params]
        # a page with no mask of its own hands the kernel its row count, not
        # the ones page_live made up
        totals = _fused.run(
            recipe, scan_cols, live if masked else page.capacity,
            params=bound, interpret=policy.interpret,
        )
        key_codes, agg_cols, out_live, n_groups = _fused.assemble(recipe, totals)
        report(nid, n_groups)
        if collect_stats:
            count_rows(scan_nid, live)
        cols: list[ColumnVal] = []
        for code, ke, (ci, _, _) in zip(key_codes, node.group_keys, recipe.keys):
            cols.append(ColumnVal(code, None, scan_cols[ci].dict, ke.type))
        for out, a in zip(agg_cols, node.aggs):
            hi = None
            if len(out) == 4:  # decimal128 sum: (lo, valid, None, hi)
                data, valid, _d, hi = out
            else:
                data, valid = out
            cols.append(ColumnVal(data, valid, None, a.type, data2=hi))
        return _Stage(cols, out_live)

    def _emit(node: PlanNode) -> _Stage:
        nid = counter[0]
        counter[0] += 1

        if isinstance(node, (TableScan, RemoteSource)):
            page = pages[str(nid)]
            cols = [column_val(c) for c in page.columns]
            for cv, t in zip(cols, node.output_types):
                cv.type = t
            return _Stage(cols, page_live(page))

        if isinstance(node, EnforceSingleRow):
            s = emit(node.child)
            # host raises when this exceeds 1 (scalar-subquery contract;
            # reference: EnforceSingleRowOperator) — kernels cannot raise.
            # Key is -(nid+1): `required` flows through shard_map as a pytree
            # dict whose keys must sort together, so specials stay ints
            report(-(nid + 1), jnp.sum(s.live.astype(jnp.int32)))
            return s

        if isinstance(node, Filter):
            s = emit(node.child)
            mask = eval_predicate(node.predicate, s.cols, s.capacity)
            return _Stage(s.cols, s.live & mask)

        if isinstance(node, Compact):
            s = emit(node.child)
            C = caps.get(nid, s.capacity)  # unset (SPMD) == pass-through
            if C >= s.capacity:
                # pass-through tier: nothing to gain — but REPORT the live
                # count so the post-run shrink can learn the true surviving
                # rows and tighten this point for later runs
                report(nid, jnp.sum(s.live.astype(jnp.int64)))
                return s
            cols, live, req = compact_rows(s.cols, s.live, C)
            report(nid, req)
            return _Stage(cols, live)

        if isinstance(node, Project):
            s = emit(node.child)
            cols = [eval_expr(e, s.cols, s.capacity) for e in node.expressions]
            return _Stage(cols, s.live)

        if isinstance(node, Aggregate):
            fused = _try_fused_aggregate(node, nid)
            if fused is not None:
                return fused
            s = emit(node.child)
            G = caps[nid]
            keys = [eval_expr(k, s.cols, s.capacity) for k in node.group_keys]
            args = [
                None if a.arg is None else eval_expr(a.arg, s.cols, s.capacity)
                for a in node.aggs
            ]
            args2 = [
                None if a.arg2 is None else eval_expr(a.arg2, s.cols, s.capacity)
                for a in node.aggs
            ]
            specs = [
                AggSpec(a.fn, a.distinct, a.param, a.sep, a.type)
                for a in node.aggs
            ]
            aorder = [
                tuple(
                    (eval_expr(k, s.cols, s.capacity), asc, nf)
                    for k, asc, nf in a.order_keys
                )
                for a in node.aggs
            ]
            out_keys, out_aggs, out_live, n_groups = group_aggregate(
                keys, args, specs, s.live, G, agg_args2=args2, agg_order=aorder
            )
            report(nid, n_groups)
            cols: list[ColumnVal] = []
            for (data, valid, khi), kv in zip(out_keys, keys):
                cols.append(
                    ColumnVal(data, _none_if_all(valid), kv.dict, kv.type, khi)
                )
            for out, a, arg in zip(out_aggs, node.aggs, args):
                hi = None
                if len(out) == 4:  # decimal128 sum: (lo, valid, None, hi)
                    data, valid, d, hi = out
                elif len(out) == 3:  # host-collected: carries its own dictionary
                    data, valid, d = out
                else:
                    data, valid = out
                    d = arg.dict if (arg is not None and a.fn in ("min", "max")) else None
                cols.append(ColumnVal(data, valid, d, a.type, data2=hi))
            return _Stage(cols, out_live)

        if isinstance(node, Distinct):
            s = emit(node.child)
            G = caps[nid]
            out_keys, _, out_live, n_groups = group_aggregate(
                s.cols, [], [], s.live, G
            )
            report(nid, n_groups)
            cols = [
                ColumnVal(data, _none_if_all(valid), cv.dict, cv.type, khi)
                for (data, valid, khi), cv in zip(out_keys, s.cols)
            ]
            return _Stage(cols, out_live)

        if isinstance(node, Join):
            # decimal128 columns ride the join: the expansion gathers, the
            # left/full null-extension concats, and the exact key equality
            # all carry/compare the high limb (ops/relops.py equi_join)
            left = emit(node.left)
            right = emit(node.right)
            if node.kind == "cross":
                cols, live = broadcast_single_row(
                    left.cols, left.live, right.cols, right.live
                )
                return _Stage(cols, live)
            C = caps[nid]
            lkeys = [eval_expr(k, left.cols, left.capacity) for k in node.left_keys]
            rkeys = [eval_expr(k, right.cols, right.capacity) for k in node.right_keys]
            lkeys, rkeys = _align_join_keys(lkeys, rkeys)
            residual = compare = None
            if node.residual is not None:
                res_ir = node.residual

                def residual(gathered, cap, _ir=res_ir):
                    return eval_predicate(_ir, gathered, cap)

                sides = (_one_comparison(res_ir, len(left.cols))
                         if node.kind in MINMAX_KINDS else None)
                if sides is not None:
                    # a filtering join asks such a residual of its key run's
                    # smallest and largest build value (equi_join, "minmax")
                    op, probe_ir, build_ir = sides
                    compare = (
                        op, eval_expr(probe_ir, left.cols, left.capacity),
                        eval_expr(build_ir, [*left.cols, *right.cols], right.capacity))

            cols, live, req = equi_join(
                node.kind, left.cols, left.live, right.cols, right.live,
                lkeys, rkeys, residual, C, compare,
            )
            if req is not None:  # a join that built no frame has no need
                report(nid, req)
            return _Stage(cols, live)

        if isinstance(node, Unnest):
            s = check_limbed(emit(node.child), "unnest")
            C = caps[nid]
            arrays = [eval_expr(a, s.cols, s.capacity) for a in node.arrays]
            cols, live, req = unnest_expand(
                s.cols, s.live, arrays, node.element_types,
                node.with_ordinality, node.outer, C,
            )
            report(nid, req)
            return _Stage(cols, live)

        if isinstance(node, Sort):
            s = emit(node.child)  # limbed payloads ride sort_rows' gathers
            keys = [eval_expr(k.expr, s.cols, s.capacity) for k in node.keys]
            specs = [SortSpec(k.ascending, k.nulls_first) for k in node.keys]
            cols, live = sort_rows(s.cols, s.live, keys, specs)
            return _Stage(cols, live)

        if isinstance(node, TopN):
            s = emit(node.child)  # limbed payloads ride the gathers
            keys = [eval_expr(k.expr, s.cols, s.capacity) for k in node.keys]
            specs = [SortSpec(k.ascending, k.nulls_first) for k in node.keys]
            cols, live, req = top_n(
                s.cols, s.live, keys, specs, node.count, caps.get(nid)
            )
            report(nid, req)
            return _Stage(cols, live)

        if isinstance(node, Limit):
            s = emit(node.child)
            return _Stage(s.cols, limit_mask(s.live, node.count))

        if isinstance(node, Concat):
            stages = [check_limbed(emit(c), "union") for c in node.inputs]
            cols: list[ColumnVal] = []
            for ci, t in enumerate(node.output_types):
                parts = [st.cols[ci] for st in stages]
                cols.append(_concat_columns(parts, t))
            live = jnp.concatenate([st.live for st in stages])
            return _Stage(cols, live)

        if isinstance(node, Window):
            from ..ops.window import window_eval

            s = check_limbed(emit(node.child), "window")
            part = [eval_expr(k, s.cols, s.capacity) for k in node.partition_by]
            okeys = [eval_expr(k.expr, s.cols, s.capacity) for k in node.order_by]
            ospecs = [SortSpec(k.ascending, k.nulls_first) for k in node.order_by]
            argv = [
                tuple(eval_expr(a, s.cols, s.capacity) for a in c.args)
                for c in node.calls
            ]
            cols, live = window_eval(
                s.cols, s.live, part, okeys, ospecs, node.calls, argv
            )
            return _Stage(cols, live)

        if isinstance(node, Exchange):
            s = emit(node.child)  # limbed columns ride the collectives (data2)
            if node.kind == "single":
                # replicated input that must count once: keep device 0's copy
                if axis is not None:
                    on_first = jax.lax.axis_index(axis) == 0
                    return _Stage(s.cols, s.live & on_first)
                return s
            if node.kind in ("gather", "broadcast"):
                from ..parallel.exchange import gather_all

                cols, live = gather_all(s.cols, s.live, axis)
                return _Stage(cols, live)
            # repartition
            from ..parallel.exchange import repartition

            keys = [eval_expr(k, s.cols, s.capacity) for k in node.keys]
            B = caps[nid]
            cols, live, req = repartition(
                s.cols, s.live, keys, num_devices, B, axis
            )
            report(nid, req)
            return _Stage(cols, live)

        if isinstance(node, MatchRecognize):
            # host-side operator (sequential backtracking walk; the plan is
            # forced onto the eager path, like host-collected aggregates)
            from ..ops.matchrec import execute_match

            s = emit(node.child)
            cols, live = execute_match(node, s.cols, s.live)
            return _Stage(cols, live)

        if isinstance(node, Values):
            nrows = max(len(node.rows), 1)
            cols = []
            for ci, t in enumerate(node.types):
                vals = [r[ci] for r in node.rows]
                arr = jnp.asarray(np.asarray(vals, dtype=t.np_dtype))
                cols.append(ColumnVal(arr, None, None, t))
            live = jnp.asarray(np.arange(nrows) < len(node.rows))
            if not node.types:
                live = jnp.ones((len(node.rows) or 1,), jnp.bool_)
            return _Stage(cols, live)

        raise NotImplementedError(f"node {type(node).__name__}")

    from ..ops import kernels as _kernels

    events = _kernels.begin_capture()
    try:
        stage = emit(plan)
    finally:
        _kernels.end_capture()
    _kernels.remember(plan, events)
    out_page = Page(
        tuple(
            Column(cv.type, cv.data, cv.valid, cv.dict, cv.data2)
            for cv in stage.cols
        ),
        stage.live,
    )
    return out_page, required


def _none_if_all(valid):
    return valid


def _concat_columns(parts: list[ColumnVal], t) -> ColumnVal:
    """Row-concatenate column fragments; varchar fragments are re-coded into
    a merged dictionary (host-side, trace time)."""
    from ..data.page import Dictionary

    dicts = [p.dict for p in parts]
    if any(d is not None for d in dicts):
        all_values = np.concatenate([d.values for d in dicts])
        uniq = np.unique(all_values)
        merged = Dictionary(uniq)
        datas = []
        for p in parts:
            remap = np.asarray(
                [merged.code_of(v) for v in p.dict.values], dtype=np.int32
            )
            datas.append(jnp.take(jnp.asarray(remap), p.data))
        data = jnp.concatenate(datas)
        out_dict = merged
    else:
        dtype = jnp.dtype(t.np_dtype)
        data = jnp.concatenate([p.data.astype(dtype) for p in parts])
        out_dict = None
    if all(p.valid is None for p in parts):
        valid = None
    else:
        valid = jnp.concatenate(
            [
                p.valid if p.valid is not None else jnp.ones(p.data.shape, jnp.bool_)
                for p in parts
            ]
        )
    return ColumnVal(data, valid, out_dict, t)


_MIRRORED = {"ne": "ne", "lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}


def _one_comparison(ir, n_left: int):
    """-> (op, probe-side IR, build-side IR), read `probe op build`, where a
    join's residual is ONE comparison between an expression over the left
    page's fields (`< n_left`) and one over the right page's; else None."""
    if not (isinstance(ir, Call) and ir.op in _MIRRORED and len(ir.args) == 2):
        return None
    x, y = ir.args
    fx, fy = field_refs(x), field_refs(y)
    if not fx or not fy:
        return None
    if max(fx) < n_left <= min(fy):
        return ir.op, x, y
    if max(fy) < n_left <= min(fx):
        return _MIRRORED[ir.op], y, x
    return None


def _align_join_keys(lkeys: list[ColumnVal], rkeys: list[ColumnVal]):
    """Translate dictionary codes so both sides of a varchar key share one
    code space (host-side, trace time)."""
    out_l, out_r = [], []
    for a, b in zip(lkeys, rkeys):
        if a.dict is not None and b.dict is not None and a.dict is not b.dict:
            trans = np.asarray([a.dict.code_of(v) for v in b.dict.values], dtype=np.int32)
            new_b = ColumnVal(
                jnp.take(jnp.asarray(trans), b.data),
                (b.valid if b.valid is not None else jnp.ones(b.data.shape, jnp.bool_)),
                a.dict,
                b.type,
            )
            # codes of -1 (absent) must not match: mark invalid
            new_b = ColumnVal(new_b.data, new_b.valid & (new_b.data >= 0), a.dict, b.type)
            b = new_b
        out_l.append(a)
        out_r.append(b)
    return out_l, out_r
