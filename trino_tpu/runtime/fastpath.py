"""Prepared-statement serving fast path.

The legacy EXECUTE path re-parses the stored SQL with the literal values
spliced in, then re-analyzes, re-plans and re-traces — every distinct
binding is a fresh jit signature (and on novel capacities an XLA compile).
This module implements the reference's EXECUTE machinery (session-held
prepared statements, parameters bound at EXECUTE — sql/tree/Parameter,
analyzer binding) on top of the jit data plane:

  * the statement text is parsed ONCE into a template whose `?` sites are
    positional `ast.Parameter` nodes (sql/statements.parse_template);
  * at EXECUTE, bindable scalar parameters (numerics, booleans, dates,
    int64-range decimals) become `ir.Param` nodes — runtime jit ARGUMENTS,
    not plan constants — so every binding of one prepared statement shares
    a single canonical plan and ONE compiled program (zero retrace).  Where
    the plan is a scan, filters and one aggregation, they are operands of
    the fused scan kernel itself (ops/pallas/fused.py): a date, integer or
    boolean as an int32 scalar, a double, a decimal or a BIGINT as the
    hi/lo f32 pair made from the traced value — a BIGINT or a decimal of
    more than 14 digits only under a comparison; in arithmetic such a one
    keeps the plan off the kernel, a scalar of the operator-at-a-time
    program.  That program sizes nothing by its bindings, so the one
    warm-up execution learns all there is and the deferred overflow check
    has nothing to trip on;
  * value-dependent parameters (varchar — string ops are lowered per
    distinct dictionary value on the host at trace time — NULLs, beyond-
    int64 decimals) are BAKED as constants, giving a per-value plan: the
    classic generic-vs-custom-plan split, still cached per value; the
    kernel sees them as the constants a text statement has;
  * plans land in a ParameterizedPlanCache: LRU, kill switch
    (`plan_cache_enabled`), pinned to the scanned tables' version vector
    (resultcache.py discipline — DML/snapshot bumps invalidate), counted in
    `trino_tpu_plan_cache_events_total{hit|miss|evicted|invalidated|bypass}`;
  * repeated dispatch is PIPELINED: once a plan's capacities are learned
    and its program compiled, dispatch goes straight at the cached
    executable and defers the overflow-vector sync to result
    materialization, so consecutive EXECUTEs overlap host work with device
    work instead of paying a sync RTT each;
  * concurrent EXECUTEs of the same plan inside `execute_batch_window_ms`
    are stacked into one batched device dispatch — parameters become a
    leading vmap axis (donated, they are per-batch scratch) when the plan
    supports it, with a per-query pipelined fallback otherwise — using the
    result-cache in-flight-dedup idiom to arbitrate the batch leader
    (`trino_tpu_execute_batch_total{batched|single|fallback}`).

Scanned tables stay device-resident across executions for free: the
executor's resident-page plane (exec/compiler.py table_page) is keyed by
connector generation, the same version the cache pin watches.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..exec.compiler import page_rows
from ..plan.planner import note_subqueries
from ..utils.metrics import GLOBAL as _METRICS

__all__ = ["FastPath", "NotFastpath", "PLAN_CACHE_EVENTS", "EXECUTE_BATCH"]

PLAN_CACHE_EVENTS = _METRICS.counter(
    "trino_tpu_plan_cache_events_total",
    "Parameterized plan cache events on the prepared-statement fast path",
    ("event",),
)
EXECUTE_BATCH = _METRICS.counter(
    "trino_tpu_execute_batch_total",
    "Batched prepared-statement dispatch outcomes (shared small-query batching)",
    ("outcome",),
)


class NotFastpath(Exception):
    """Raised when a prepared statement cannot take the fast path (non-query
    template, expression parameters, planning feature gap, kill switch) —
    the caller falls back to the legacy substitute-and-replan path."""


# pad batch sizes onto pow2 tiers so a drifting batch width doesn't mint a
# compiled program per width (same bucketing discipline as plan capacities)
def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


@dataclass
class _PlanEntry:
    plan: object
    slots: tuple                     # ("bind"|"bake", Type, value) per param
    output_names: tuple
    version_vector: Optional[tuple]
    batchable: Optional[bool] = None  # None = not yet probed (vmap trial)
    batch_fns: dict = field(default_factory=dict)  # padded B -> jitted vmap
    # in-flight batch group (leader/follower, resultcache _Inflight idiom)
    glock: threading.Lock = field(default_factory=threading.Lock)
    queue: list = field(default_factory=list)
    leader_active: bool = False


class _Pending:
    __slots__ = ("params", "event", "rows", "error")

    def __init__(self, params):
        self.params = params
        self.event = threading.Event()
        self.rows = None
        self.error = None


@dataclass
class _Info:
    """How a lookup came by its plan: the `planner` span and the EXPLAIN footer."""

    cache: str = "miss"
    bound: int = 0
    baked: int = 0
    batched: int = 0


class FastPath:
    """Per-engine-surface prepared fast path: template registry + plan
    cache + batched dispatch.  One instance serves every protocol session
    of a coordinator (the plan cache is cross-session; the prepared-name
    registry stays on the engine/session as before)."""

    def __init__(self, engine):
        self.engine = engine
        self._templates: dict[str, tuple] = {}   # sql -> (template stmt, n)
        self._cache: OrderedDict = OrderedDict()
        self._lock = threading.RLock()
        self.last_columns: Optional[list] = None
        # the template the last EXECUTE resolved to; the coordinator stamps
        # it into the query-history record so recurrence counts replicate
        # through the fleet-shared history store (see _recurring_templates)
        self.last_template: Optional[str] = None

    # --------------------------------------------------------------- template
    def _template(self, sql: str):
        from ..sql import statements as S

        hit = self._templates.get(sql)
        if hit is None:
            try:
                hit = S.parse_template(sql)
            except Exception:
                hit = (None, 0)
            self._templates[sql] = hit
        stmt, n = hit
        if not isinstance(stmt, S.QueryStmt):
            raise NotFastpath("template is not a plain query")
        return stmt, n

    # ----------------------------------------------------------------- slots
    def _slots(self, param_exprs) -> tuple:
        """Translate EXECUTE's literal arguments into typed binding slots.
        Bindable scalars -> ("bind", type, value); value-dependent or
        null -> ("bake", type, value)."""
        from ..data.types import BIGINT, BOOLEAN, DATE, DOUBLE
        from ..plan.ir import Const
        from ..plan.planner import Scope, _Translator

        t = _Translator(Scope([]))
        slots = []
        for e in param_exprs:
            try:
                ir = t.translate(e)
            except Exception:
                raise NotFastpath(f"non-literal parameter: {e}")
            if not isinstance(ir, Const):
                raise NotFastpath(f"non-literal parameter: {e}")
            typ, val = ir.type, ir.value
            bindable = val is not None and (
                typ in (BIGINT, DOUBLE, DATE, BOOLEAN)
                or (typ.is_decimal and -(1 << 63) <= val < (1 << 63))
            )
            slots.append(("bind" if bindable else "bake", typ, val))
        return tuple(slots)

    @staticmethod
    def _param_values(entry_slots, current_slots) -> tuple:
        """The jit-argument vector: one typed numpy scalar per parameter
        index.  Modes come from the cached plan's slots (what the plan
        bound vs baked), VALUES from the current execution's slots.  Baked
        slots still occupy their index (ir.Param never reads them) so the
        argument pytree is stable for one bake mask."""
        vals = []
        for (mode, typ, _entry_val), (_m, _t, val) in zip(
            entry_slots, current_slots
        ):
            if mode == "bind":
                vals.append(np.asarray(val, dtype=typ.np_dtype).reshape(()))
            else:
                vals.append(np.int64(0))
        return tuple(vals)

    # ------------------------------------------------------------------ plan
    def _plan(self, query, slots):
        """Plan the template with bound parameters; literal-required
        positions (LIKE patterns, IN lists, ...) force a replan with every
        parameter baked — per-value plans, still cacheable."""
        from ..plan.nodes import TableScan, walk
        from ..plan.optimizer import optimize
        from ..plan.planner import param_bindings

        eng = self.engine

        def attempt(attempt_slots):
            with param_bindings(attempt_slots):
                plan = optimize(eng.planner.plan(query), eng.catalogs, eng.session)
            return plan, attempt_slots

        try:
            plan, used = attempt(slots)
        except Exception:
            baked = tuple(("bake", t, v) for _m, t, v in slots)
            try:
                plan, used = attempt(baked)
            except Exception:
                raise NotFastpath("template does not plan with parameters")
        for n in walk(plan):
            if isinstance(n, TableScan):
                eng.access_control.check_can_select(
                    eng.user, n.catalog, n.table, n.column_names
                )
        return plan, used

    def _entry_key(self, sql: str, slots) -> tuple:
        from ..ops.kernels import policy_key

        parts = []
        for mode, typ, val in slots:
            parts.append((mode, typ) if mode == "bind" else (mode, typ, val))
        return (sql, tuple(parts), policy_key())

    def _current_vector(self, plan):
        from .resultcache import plan_version_vector

        return plan_version_vector(plan, self.engine.catalogs)

    def _cache_get(self, key):
        """LRU lookup with the version-vector validity check; returns None
        on miss or stale pin."""
        entry = self._cache.get(key)
        if entry is None:
            return None
        vec = self._current_vector(entry.plan)
        if vec == entry.version_vector and vec is not None:
            self._cache.move_to_end(key)
            return entry
        del self._cache[key]
        PLAN_CACHE_EVENTS.labels("invalidated").inc()
        return None

    def _lookup(self, sql: str, query, slots) -> tuple[_PlanEntry, _Info]:
        """-> (the plan's entry, how it was come by: hit | miss | bypass)."""
        eng = self.engine
        cache_on = bool(eng.session.get("plan_cache_enabled"))
        key = self._entry_key(sql, slots)

        def found(kind, entry):
            PLAN_CACHE_EVENTS.labels(kind).inc()
            bound = sum(1 for m, _t, _v in entry.slots if m == "bind")
            return entry, _Info(kind, bound, len(entry.slots) - bound)

        with self._lock:
            entry = self._cache_get(key) if cache_on else None
            if entry is not None:
                return found("hit", entry)
        plan, used = self._plan(query, slots)
        if used != slots:
            # planning REBAKED the parameters (literal-required positions):
            # the plan depends on the concrete values, so it must live under
            # the all-baked key — values included — never the generic one
            key = self._entry_key(sql, used)
            with self._lock:
                entry = self._cache_get(key) if cache_on else None
                if entry is not None:
                    return found("hit", entry)
        entry = _PlanEntry(
            plan=plan,
            slots=used,
            output_names=tuple(plan.output_names),
            version_vector=self._current_vector(plan),
        )
        if not cache_on or entry.version_vector is None:
            # kill switch / time-travel scans: plan served, never cached
            return found("bypass", entry)
        with self._lock:
            self._cache[key] = entry
            self._cache.move_to_end(key)
            limit = int(eng.session.get("plan_cache_max_entries") or 64)
            recurring = (
                self._recurring_templates()
                if len(self._cache) > limit
                else frozenset()
            )
            while len(self._cache) > limit:
                # evict the oldest NON-recurring plan first: recurrence in
                # the (fleet-shared) history store marks templates a peer's
                # adopted traffic is about to EXECUTE again
                victim = next(
                    (k for k in self._cache if k[0] not in recurring),
                    next(iter(self._cache)),
                )
                del self._cache[victim]
                PLAN_CACHE_EVENTS.labels("evicted").inc()
        return found("miss", entry)

    def _recurring_templates(self, min_n: int = 2) -> frozenset:
        """Templates that recurred across the query history — the plan
        cache's replicated admission hint.  History records carry the
        resolved EXECUTE template (coordinator._history_record), and in
        fleet mode the history store is one shared JSONL every member tails
        (QueryHistoryStore.refresh), so a failover target inherits its
        peers' recurrence counts and shields the plans the adopted traffic
        keeps EXECUTE-ing from eviction pressure.  Mirrors
        ResultCache.admissible: no history wired -> no protection."""
        coord = getattr(self.engine, "_coord", None)
        hist = getattr(coord, "history", None) if coord is not None else None
        if hist is None:
            return frozenset()
        counts: dict[str, int] = {}
        try:
            for rec in hist.list(limit=1000):
                t = rec.get("template")
                if isinstance(t, str) and t:
                    counts[t] = counts.get(t, 0) + 1
        except Exception:
            return frozenset()
        return frozenset(t for t, n in counts.items() if n >= min_n)

    def invalidate_table(self, catalog: str, table: str) -> None:
        """Typed invalidation on DML (Engine.cache_invalidate): drop every
        cached plan scanning the mutated table.  Snapshot bumps from
        external commits are caught lazily by the version-vector check."""
        ref = f"{catalog}.{table}"
        with self._lock:
            stale = [
                k
                for k, e in self._cache.items()
                if e.version_vector is None
                or any(name == ref for name, _v in e.version_vector)
            ]
            for k in stale:
                del self._cache[k]
            if stale:
                PLAN_CACHE_EVENTS.labels("invalidated").inc(len(stale))

    # -------------------------------------------------------------- executor
    def _executor(self):
        """Coordinator-local executor: prepared EXECUTEs of small queries run
        against the resident-page plane on the coordinator process instead
        of paying worker scheduling + exchange RTTs (the fast path IS the
        latency win).  Plain local engines reuse their executor."""
        eng = self.engine
        ex = getattr(eng, "_local_fallback", None)
        if ex is None:
            ex = eng.executor
        if ex is None or not hasattr(ex, "_run"):
            from ..exec.compiler import LocalExecutor

            ex = LocalExecutor(eng.catalogs, eng.default_catalog)
            ex.tracer = eng.tracer
            ex.resident = eng.resident
            eng._local_fallback = ex
        return ex

    # -------------------------------------------------------------- dispatch
    def execute(self, sql: str, param_exprs, analyze: bool = False):
        """EXECUTE a prepared statement's template through the fast path;
        raises NotFastpath when the caller must use the legacy path."""
        eng = self.engine
        if not bool(eng.session.get("prepared_fastpath_enabled")):
            raise NotFastpath("prepared_fastpath_enabled=false")
        # the spans a text request has (utils/tracing.py; PERF.md section 3):
        # `planner` is the template, the bindings and the plan cache
        with eng.tracer.span("planner") as span:
            # first: the kernel policy is part of the plan cache's key
            eng._apply_compile_props()
            stmt, n_params = self._template(sql)
            self.last_template = sql
            if len(param_exprs) != n_params:
                raise ValueError(
                    f"prepared statement takes {n_params} parameters,"
                    f" got {len(param_exprs)}"
                )
            slots = self._slots(param_exprs)
            entry, info = self._lookup(sql, stmt.query, slots)
            span.attributes.update(
                preplanned=info.cache == "hit", plan_cache=info.cache
            )
            if info.cache != "hit":  # planned just now, on this thread
                note_subqueries(span, eng.planner, entry.plan)
        # `bind`: this request's bindings as the cached plan's typed scalars
        with eng.tracer.span("bind", params=len(slots)):
            self.last_columns = list(entry.output_names)
            params = self._param_values(entry.slots, slots)
            window_s = float(eng.session.get("execute_batch_window_ms") or 0.0) / 1e3
        if window_s > 0.0 and not analyze:
            return self._submit_batched(entry, params, window_s)
        # the coordinator runs the whole plan itself, as it runs a text
        # statement's result fragment; an Engine calls that `execute`
        on_coordinator = getattr(eng, "_coord", None) is not None
        with eng.tracer.span("root_fragment" if on_coordinator else "execute"):
            page = self._executor().execute(entry.plan, params=params)
        return page_rows(eng.tracer, page)

    # ------------------------------------------------- shared query batching
    def _submit_batched(self, entry: _PlanEntry, params, window_s: float):
        """Leader/follower batching: the first EXECUTE of a plan opens a
        window; everything queued on the same plan when it closes runs as
        one batched device dispatch (resultcache.py _Inflight idiom)."""
        pending = _Pending(params)
        with entry.glock:
            entry.queue.append(pending)
            is_leader = not entry.leader_active
            if is_leader:
                entry.leader_active = True
        if not is_leader:
            pending.event.wait(timeout=600.0)
            if not pending.event.is_set():
                raise RuntimeError("batched EXECUTE timed out")
            if pending.error is not None:
                raise pending.error
            return pending.rows
        time.sleep(window_s)
        with entry.glock:
            batch = entry.queue[:]
            entry.queue.clear()
            entry.leader_active = False
        try:
            results = self._run_batch(entry, [p.params for p in batch])
            for p, rows in zip(batch, results):
                p.rows = rows
        except Exception as e:
            for p in batch:
                p.error = e
        finally:
            for p in batch:
                p.event.set()
        if pending.error is not None:
            raise pending.error
        return pending.rows

    def _run_batch(self, entry: _PlanEntry, params_list) -> list:
        ex = self._executor()
        if len(params_list) == 1:
            EXECUTE_BATCH.labels("single").inc()
            return [ex.execute(entry.plan, params=params_list[0]).to_pylist()]
        if entry.batchable is None:
            entry.batchable = self._probe_batchable(ex, entry, params_list)
        if entry.batchable and params_list[0]:
            try:
                out = self._dispatch_vmapped(ex, entry, params_list)
                EXECUTE_BATCH.labels("batched").inc()
                return out
            except Exception:
                entry.batchable = False  # never retry a failing vmap
        # fallback: per-query, but PIPELINED — dispatch all executions
        # before materializing any, so device work overlaps host work
        EXECUTE_BATCH.labels("fallback").inc()
        return self._dispatch_pipelined(ex, entry, params_list)

    def _inputs(self, ex, plan):
        from ..exec.compiler import _node_ids
        from ..plan.nodes import TableScan

        inputs = {}
        for i, n in _node_ids(plan).items():
            if isinstance(n, TableScan):
                inputs[str(i)] = ex.table_page(
                    n.catalog, n.table, n.column_names, n.output_types, scan_id=i
                )
        return inputs

    def _compiled(self, ex, plan, params):
        """(fn, holder, caps, inputs, signature) for the plan's cached program, forcing
        one warm-up execute to learn capacities/compile if needed; None when
        the plan has no jittable cached program (host aggs, fallback)."""
        caps = ex._learned_caps.get(plan)
        if caps is None:
            ex.execute(plan, params=params)
            caps = ex._learned_caps.get(plan)
            if caps is None:
                return None
        inputs = self._inputs(ex, plan)
        key, _td, _av = ex._cache_key(plan, inputs, caps, params)
        cached = ex._jit_cache.get(key)
        if cached is None:
            ex.execute(plan, params=params)
            cached = ex._jit_cache.get(key)
            if cached is None:
                return None
        fn, holder, sig = cached
        return fn, holder, caps, inputs, sig

    def _dispatch_pipelined(self, ex, entry: _PlanEntry, params_list) -> list:
        compiled = self._compiled(ex, entry.plan, params_list[0])
        if compiled is None:
            return [
                ex.execute(entry.plan, params=p).to_pylist() for p in params_list
            ]
        fn, holder, caps, inputs, sig = compiled
        inflight = []
        for p in params_list:  # no host sync yet
            with ex._span("dispatch", signature=sig, **holder.get("dispatch", {})):
                inflight.append(fn(inputs, p))
        out = []
        for (page, packed), p in zip(inflight, params_list):
            with ex._span("device_wait", signature=sig):
                vals = np.asarray(packed)
            # nothing to trip on where the program sizes nothing by its
            # bindings (a fused scan masks rows itself)
            required = dict(zip(holder["keys"], vals.tolist()))
            if any(
                isinstance(k, int) and k in caps and int(v) > caps[k]
                for k, v in required.items()
            ):
                # deferred overflow check tripped: rerun through the full
                # capacity-retry loop (grows tiers, recompiles once)
                page = ex.execute(entry.plan, params=p)
            out.append(page.to_pylist())
        return out

    def _probe_batchable(self, ex, entry: _PlanEntry, params_list) -> bool:
        """Cheap abstract trial: can this plan trace under vmap over the
        parameter axis?  Plans with host-side value-dependent lowerings
        (dictionary string ops over param-derived values) or host aggs
        cannot; they keep the pipelined per-query path."""
        import jax

        from ..exec.compiler import _has_host_aggs, _make_call

        if _has_host_aggs(entry.plan):
            return False
        compiled = self._compiled(ex, entry.plan, params_list[0])
        if compiled is None:
            return False
        _fn, _holder, caps, inputs, _sig = compiled
        call, _h = _make_call(entry.plan, dict(caps), False)
        stacked = tuple(
            np.stack([np.asarray(p[i]) for p in params_list[:2]])
            for i in range(len(params_list[0]))
        )
        try:
            jax.eval_shape(
                jax.vmap(call, in_axes=(None, 0)), inputs, stacked
            )
            return True
        except Exception:
            return False

    def _dispatch_vmapped(self, ex, entry: _PlanEntry, params_list) -> list:
        """One batched device dispatch: parameters become a leading batch
        axis (padded to a pow2 tier), outputs are sliced per query.  The
        stacked parameter arrays are donated — they are per-batch scratch,
        unlike the resident input pages."""
        import jax

        from ..exec.compiler import _make_call

        compiled = self._compiled(ex, entry.plan, params_list[0])
        if compiled is None:
            raise RuntimeError("no compiled program to batch over")
        _fn, _holder, caps, inputs, _sig = compiled
        b = len(params_list)
        bp = _pow2(b)
        padded = list(params_list) + [params_list[0]] * (bp - b)
        stacked = tuple(
            np.stack([np.asarray(p[i]) for p in padded])
            for i in range(len(params_list[0]))
        )
        import jax.numpy as jnp

        stacked = tuple(jnp.asarray(a) for a in stacked)  # donatable buffers
        if bp not in entry.batch_fns:
            call, holder = _make_call(entry.plan, dict(caps), False)
            jfn = jax.jit(jax.vmap(call, in_axes=(None, 0)), donate_argnums=(1,))
            entry.batch_fns[bp] = (jfn, holder)
        fn, holder = entry.batch_fns[bp]
        out_page, packed = fn(inputs, stacked)
        vals = np.asarray(packed)  # ONE sync for the whole batch: [B, K]
        out = []
        for qi in range(b):
            required = dict(zip(holder["keys"], vals[qi].tolist()))
            if any(
                isinstance(k, int) and k in caps and int(v) > caps[k]
                for k, v in required.items()
            ):
                page = ex.execute(entry.plan, params=params_list[qi])
            else:
                page = jax.tree_util.tree_map(lambda a, _q=qi: a[_q], out_page)
            out.append(page.to_pylist())
        return out
