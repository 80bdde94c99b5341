"""Compile-cliff resilience plane (exec/compilesvc.py).

The engine's tallest latency cliff is a cold XLA signature: minutes of
compile wall in front of a sub-second query.  This suite covers the
whole plane: graceful fallback execution under compile_wait_budget_ms
(differential-checked rows, compiled program swapping in later),
compile-storm admission (N concurrent queries, ONE build), hard compile
deadlines with typed COMPILE_TIMEOUT attribution, the per-signature
circuit breaker with half-open recovery, startup cache warming from the
query history, the pow2 capacity-bucketing signature collapse (ROADMAP
2a), the AOT pytree-pin lazy-retrace bugfix, and the COMPILE_SLOW /
COMPILE_FAIL chaos modes on a live cluster
(scripts/chaos_tier.sh compile runs the `chaos` subset).
"""

import threading
import time

import numpy as np
import pytest

from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.spi import ColumnSchema
from trino_tpu.data.types import BIGINT
from trino_tpu.exec.compilesvc import (
    COMPILE_DEDUP, COMPILE_TIMEOUTS, FALLBACKS, CompileService,
    SignatureBreaker,
)
from trino_tpu.runtime.engine import Engine
from trino_tpu.runtime.failure import FaultInjector
from trino_tpu.runtime.history import QueryHistoryStore
from trino_tpu.utils.profiler import PROFILER, _PCACHE_EVENTS

GROUP_SQL = "select k, sum(v) as s from t group by k order by k"
# an injected compile delay that a cold eager fallback run stays far under
SLOW_COMPILE_MS = 20000


def _make_engine(seed=0, n=4000):
    """Local engine over a seeded memory table plus the oracle rows for
    GROUP_SQL, computed in numpy (differential check — no engine path)."""
    conn = MemoryConnector()
    conn.create_table(
        "t", [ColumnSchema("k", BIGINT), ColumnSchema("v", BIGINT)]
    )
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 16, n).astype(np.int64)
    v = rng.integers(0, 100, n).astype(np.int64)
    conn.insert("t", {"k": k, "v": v})
    eng = Engine(default_catalog="mem")
    eng.register_catalog("mem", conn)
    # isolated service: no done-map / breaker bleed between tests
    eng.executor.compile_service = CompileService()
    expected = [
        (int(key), int(v[k == key].sum())) for key in sorted(set(k.tolist()))
    ]
    return eng, expected


# ----------------------------------------------------- fallback + swap-in


def test_budget_exhausted_falls_back_then_swaps_in_compiled():
    """ISSUE acceptance core: under an injected slow compile and a small
    wait budget, a cold-signature query returns correct rows by fallback
    while the compile is still in flight; once the background compile
    lands, the next execution runs the compiled program with zero new
    fallbacks."""
    eng, expected = _make_engine(seed=0)
    eng.session.set("compile_wait_budget_ms", "200")
    inj = FaultInjector()
    # far above the fallback's eager run, which is the plan's first (its
    # per-op programs cold: ~4 s alone, more beside five busy workers)
    inj.arm(task_id="*", mode="COMPILE_SLOW", delay_ms=SLOW_COMPILE_MS, count=1)
    eng.executor.fault_injector = inj
    fb0 = FALLBACKS.value("compile_wait")

    rows = eng.query(GROUP_SQL)
    # the query is back and the injected compile has not finished: the
    # fallback dodged the compile wall, whatever the machine's speed
    assert eng.executor.compile_service.stats()["inflight"] == 1
    assert rows == expected
    assert ("COMPILE_SLOW", "local") in inj.fired
    assert eng.executor.last_fallback_reason == "compile_wait"
    ev = eng.executor.fallback_events[-1]
    assert ev["mode"] == "fallback" and ev["reason"] == "compile_wait"
    assert FALLBACKS.value("compile_wait") >= fb0 + 1

    # profiler ledger attributes the degraded execution separately
    snap = PROFILER.snapshot(ev["signature"])
    assert snap["fallback_executes"] >= 1
    assert snap["fallbacks"].get("compile_wait", 0) >= 1

    # the compile finishes in the background: swap in, zero new fallbacks
    eng.executor.compile_service.drain(timeout_s=60)
    assert eng.executor.compile_service.stats()["inflight"] == 0
    n_fallbacks = len(eng.executor.fallback_events)
    assert eng.query(GROUP_SQL) == expected
    assert len(eng.executor.fallback_events) == n_fallbacks
    swapped = eng.executor.compile_events[-1]
    assert swapped["mode"] == "async" and "reason" not in swapped


def test_explain_analyze_footer_names_fallback():
    eng, _ = _make_engine(seed=6, n=1000)
    eng.session.set("compile_wait_budget_ms", "100")
    inj = FaultInjector()
    inj.arm(task_id="*", mode="COMPILE_SLOW", delay_ms=1500, count=1)
    eng.executor.fault_injector = inj
    lines = [r[0] for r in eng.execute(f"explain analyze {GROUP_SQL}")]
    compile_lines = [ln for ln in lines if ln.startswith("-- compile:")]
    assert any("fallback (compile_wait" in ln for ln in compile_lines), lines
    eng.executor.compile_service.drain(timeout_s=30)


# --------------------------------------------------------- storm admission


def test_compile_storm_collapses_to_one_build():
    svc = CompileService(max_workers=4)
    dedup0 = COMPILE_DEDUP.value()

    def build():
        time.sleep(0.5)
        return {"program": object()}

    key = ("storm-sig", True, "treedef", "avals")
    results = []
    barrier = threading.Barrier(6)

    def go():
        barrier.wait()
        results.append(svc.obtain(key, "storm-sig", build))

    threads = [threading.Thread(target=go) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert svc.builds == 1, "compile storm was not deduplicated"
    assert all(r.status == "ready" for r in results)
    programs = {id(r.result["program"]) for r in results}
    assert len(programs) == 1, "joiners got different programs"
    assert sum(1 for r in results if r.fresh) == 1
    assert COMPILE_DEDUP.value() == dedup0 + 5
    # and the done-map serves later obtains without a new build
    assert svc.obtain(key, "storm-sig", build).status == "ready"
    assert svc.builds == 1


# -------------------------------------------------------------- deadlines


def test_compile_deadline_is_typed_and_never_hangs():
    svc = CompileService(max_workers=2)
    sig = "deadline-sig"
    t_before = COMPILE_TIMEOUTS.value()
    timeouts_before = (PROFILER.snapshot(sig) or {}).get("timeouts", 0)

    build_s = 4.0

    def build():
        time.sleep(build_s)
        return "program"

    key = (sig, 1)
    t0 = time.perf_counter()
    out = svc.obtain(key, sig, build, wait_budget_s=None, deadline_s=0.3)
    wall = time.perf_counter() - t0
    assert out.status == "timeout" and out.reason == "compile_timeout"
    assert wall < build_s / 2, f"deadline did not bound the wait: {wall}"
    assert COMPILE_TIMEOUTS.value() == t_before + 1
    assert PROFILER.snapshot(sig)["timeouts"] == timeouts_before + 1
    # a late completion still lands for future swap-in
    svc.drain(timeout_s=10)
    assert svc.obtain(key, sig, build).status == "ready"


def test_executor_deadline_records_typed_compile_timeout():
    eng, expected = _make_engine(seed=1)
    # budget 0 == wait for the compile, bounded only by the deadline
    eng.session.set("compile_deadline_s", "0.3")
    inj = FaultInjector()
    inj.arm(task_id="*", mode="COMPILE_SLOW", delay_ms=SLOW_COMPILE_MS, count=1)
    eng.executor.fault_injector = inj
    t0 = time.perf_counter()
    assert eng.query(GROUP_SQL) == expected
    # the deadline (0.3 s) and a cold eager run, not the compile's 20 s
    assert time.perf_counter() - t0 < SLOW_COMPILE_MS / 2e3, (
        "query hung past compile_deadline_s")
    ev = eng.executor.fallback_events[-1]
    assert ev["reason"] == "compile_timeout"
    assert ev["error"] == "COMPILE_TIMEOUT"
    eng.executor.compile_service.drain(timeout_s=60)


# -------------------------------------------------------- circuit breaker


def test_breaker_opens_and_half_open_probe_recovers():
    svc = CompileService(
        max_workers=2,
        breaker=SignatureBreaker(threshold=3, min_open_s=0.05, max_open_s=0.2),
    )
    sig = "breaker-sig"

    def boom():
        raise RuntimeError("injected compile failure")

    for i in range(3):
        out = svc.obtain((sig, i), sig, boom)
        assert out.status == "error" and out.reason == "compile_error"
    assert svc.breaker.state(sig) == "OPEN"

    # open breaker: no new build attempts (no churn)
    builds = svc.builds
    out = svc.obtain((sig, 3), sig, boom)
    assert out.status == "breaker_open" and out.reason == "breaker_open"
    assert svc.builds == builds

    # half-open probe that FAILS re-opens with a longer window
    time.sleep(0.35)
    out = svc.obtain((sig, 4), sig, boom)
    assert out.status == "error"
    assert svc.breaker.state(sig) == "OPEN"
    assert svc.obtain((sig, 5), sig, boom).status == "breaker_open"

    # half-open probe that SUCCEEDS closes the breaker
    time.sleep(0.35)
    out = svc.obtain((sig, 6), sig, lambda: "ok")
    assert out.status == "ready" and out.result == "ok"
    assert svc.breaker.state(sig) == "CLOSED"


def test_compile_fail_falls_back_and_breaker_stops_churn():
    eng, expected = _make_engine(seed=2)
    svc = CompileService(
        breaker=SignatureBreaker(threshold=3, min_open_s=30.0, max_open_s=30.0)
    )
    eng.executor.compile_service = svc
    inj = FaultInjector()
    inj.arm(task_id="*", mode="COMPILE_FAIL", count=10)
    eng.executor.fault_injector = inj
    for _ in range(3):
        assert eng.query(GROUP_SQL) == expected  # degraded, never failed
        assert eng.executor.last_fallback_reason == "compile_error"
    sig = eng.executor.fallback_events[-1]["signature"]
    assert svc.breaker.state(sig) == "OPEN"
    # poisoned signature pins fallback WITHOUT new compile attempts
    builds = svc.builds
    assert eng.query(GROUP_SQL) == expected
    assert eng.executor.last_fallback_reason == "breaker_open"
    assert svc.builds == builds


# ----------------------------------------------------------- cache warming


def test_top_statements_ranks_by_recurrence_then_recency():
    from trino_tpu.runtime.warmup import top_statements

    store = QueryHistoryStore(capacity=50)
    store.record({"query_id": "q1", "state": "FINISHED", "sql": "select a from t"})
    store.record({"query_id": "q2", "state": "FINISHED", "sql": "select b from t"})
    store.record({"query_id": "q3", "state": "FINISHED", "sql": "select a from t"})
    store.record({"query_id": "q4", "state": "FINISHED", "sql": "insert into t values (1)"})
    store.record({"query_id": "q5", "state": "FAILED", "sql": "select broken from t"})
    store.record({"query_id": "q6", "state": "FINISHED", "sql": "<planned>"})
    top = top_statements(store, 5)
    assert top == ["select a from t", "select b from t"]
    assert top_statements(store, 1) == ["select a from t"]


def test_engine_warm_from_history_prepays_the_compile():
    eng, expected = _make_engine(seed=3)
    store = QueryHistoryStore(capacity=10)
    store.record({"query_id": "w1", "state": "FINISHED", "sql": GROUP_SQL})
    store.record({"query_id": "w2", "state": "FINISHED", "sql": GROUP_SQL})
    store.record({"query_id": "w3", "state": "FAILED", "sql": "select nope"})
    warm0 = _PCACHE_EVENTS.value("warm")
    assert eng.warm_from_history(store, limit=4) == 1
    assert _PCACHE_EVENTS.value("warm") == warm0 + 1
    # the replay compiled the program: the client query is a pure hit
    n_events = len(eng.executor.compile_events)
    assert eng.query(GROUP_SQL) == expected
    assert len(eng.executor.compile_events) == n_events


def test_coordinator_startup_warming_env_gated(tmp_path, monkeypatch):
    import json

    from trino_tpu.testing import DistributedQueryRunner

    sql = "select k, sum(v + 3) as s from t group by k order by k"
    hist = tmp_path / "history.jsonl"
    hist.write_text(
        json.dumps({"query_id": "h1", "state": "FINISHED", "sql": sql}) + "\n"
        + json.dumps({"query_id": "h2", "state": "FINISHED", "sql": sql}) + "\n"
    )
    monkeypatch.setenv("TRINO_TPU_HISTORY_FILE", str(hist))
    monkeypatch.setenv("TRINO_TPU_WARM_SIGNATURES", "2")
    conn = MemoryConnector()
    conn.create_table(
        "t", [ColumnSchema("k", BIGINT), ColumnSchema("v", BIGINT)]
    )
    rng = np.random.default_rng(7)
    conn.insert("t", {
        "k": rng.integers(0, 8, 2000).astype(np.int64),
        "v": rng.integers(0, 50, 2000).astype(np.int64),
    })
    warm0 = _PCACHE_EVENTS.value("warm")
    runner = DistributedQueryRunner(num_workers=1, default_catalog="mem")
    runner.register_catalog("mem", conn)
    runner.start()
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if _PCACHE_EVENTS.value("warm") >= warm0 + 1:
                break
            time.sleep(0.2)
        assert _PCACHE_EVENTS.value("warm") >= warm0 + 1, (
            "startup warmer never replayed the history statement"
        )
    finally:
        runner.stop()


# ------------------------------------- capacity bucketing (ROADMAP 2a)


def test_pow2_bucketing_collapses_near_identical_capacities():
    """Planner/learned capacities get quantized onto pow2 tiers before the
    jit boundary, so nudged capacities (stats drift, learned-cap growth)
    collapse onto the SAME signature instead of forcing a recompile."""
    eng, expected = _make_engine(seed=4)
    assert eng.query(GROUP_SQL) == expected
    (plan_key, caps) = next(iter(eng.executor._learned_caps.items()))
    assert caps and all(
        v >= 1 and (v & (v - 1)) == 0 for v in caps.values()
    ), f"learned caps not on the pow2 grid: {caps}"
    n_events = len(eng.executor.compile_events)
    sigs_before = set(PROFILER.snapshot().keys())
    # nudge caps off the grid; quantization must route them back
    eng.executor._learned_caps[plan_key] = {
        nid: (v - 1 if v > 2 else v) for nid, v in caps.items()
    }
    assert eng.query(GROUP_SQL) == expected
    assert len(eng.executor.compile_events) == n_events, (
        "nudged capacities recompiled instead of collapsing onto the tier"
    )
    assert set(PROFILER.snapshot().keys()) == sigs_before


# ------------------------------------------- AOT pytree-pin lazy retrace


def test_aot_structure_mismatch_retraces_lazily():
    """An AOT program is pinned to one input pytree; a structure drift the
    cache key missed must lazily retrace (counted as a miss), not fail
    the query."""
    from trino_tpu.exec.compiler import _JIT_CACHE_LOOKUPS

    eng, expected = _make_engine(seed=5)
    assert eng.query(GROUP_SQL) == expected
    ex = eng.executor

    def _pinned(inputs):
        raise TypeError("Argument types differ from the types for which this "
                        "computation was compiled")

    for key, (fn, holder, sig) in list(ex._jit_cache.items()):
        ex._jit_cache[key] = (_pinned, holder, sig)
    miss0 = _JIT_CACHE_LOOKUPS.value("miss")
    assert eng.query(GROUP_SQL) == expected
    assert _JIT_CACHE_LOOKUPS.value("miss") >= miss0 + 1
    assert all(entry[0] is not _pinned for entry in ex._jit_cache.values())


# -------------------------------------------------- cluster chaos modes


def _cluster(n_rows=5000, seed=11):
    from trino_tpu.testing import DistributedQueryRunner

    conn = MemoryConnector()
    conn.create_table(
        "t", [ColumnSchema("k", BIGINT), ColumnSchema("v", BIGINT)]
    )
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 12, n_rows).astype(np.int64)
    v = rng.integers(0, 100, n_rows).astype(np.int64)
    conn.insert("t", {"k": k, "v": v})
    runner = DistributedQueryRunner(num_workers=2, default_catalog="mem")
    runner.register_catalog("mem", conn)
    runner.start()
    return runner, k, v


def test_chaos_compile_slow_completes_via_fallback(monkeypatch):
    """ISSUE acceptance, distributed: 10s COMPILE_SLOW on every worker +
    compile_wait_budget_ms=500 — the query returns differential-checked
    rows well under the compile wall via fallback."""
    import trino_tpu.exec.compilesvc as compilesvc

    # fresh service: the 10s builds must not occupy the process-global
    # pool other tests' compiles run on
    monkeypatch.setattr(compilesvc, "SERVICE", CompileService())
    sql = "select k, sum(v + 7) as s from t group by k order by k"
    runner, k, v = _cluster(seed=11)
    expected = [
        (int(key), int((v[k == key] + 7).sum()))
        for key in sorted(set(k.tolist()))
    ]
    try:
        runner.coordinator.session.set("compile_wait_budget_ms", "500")
        for i in range(len(runner.workers)):
            runner.inject_task_failure(
                worker_index=i, mode="COMPILE_SLOW", delay_ms=10_000, count=1
            )
        fb0 = FALLBACKS.value("compile_wait")
        t0 = time.perf_counter()
        rows = runner.query(sql)
        wall = time.perf_counter() - t0
        assert rows == expected
        assert wall < 8.0, f"query did not dodge the 10s compile wall: {wall}"
        fired = {m for w in runner.workers for (m, _) in w.fault_injector.fired}
        assert "COMPILE_SLOW" in fired, "the injected fault never bit"
        assert FALLBACKS.value("compile_wait") >= fb0 + 1
    finally:
        runner.stop()


def test_chaos_compile_fail_completes_via_fallback(monkeypatch):
    """COMPILE_FAIL on every worker: queries succeed via fallback (typed
    compile_error attribution), and a clean re-run compiles normally."""
    import trino_tpu.exec.compilesvc as compilesvc

    monkeypatch.setattr(compilesvc, "SERVICE", CompileService())
    sql = "select k, max(v) - min(v) as d from t group by k order by k"
    runner, k, v = _cluster(seed=13)
    expected = [
        (int(key), int(v[k == key].max() - v[k == key].min()))
        for key in sorted(set(k.tolist()))
    ]
    try:
        for i in range(len(runner.workers)):
            runner.inject_task_failure(
                worker_index=i, mode="COMPILE_FAIL", count=10
            )
        fb0 = FALLBACKS.value("compile_error")
        t0 = time.perf_counter()
        rows = runner.query(sql)
        wall = time.perf_counter() - t0
        assert rows == expected
        assert wall < 30.0, "query hung on failing compiles"
        assert FALLBACKS.value("compile_error") >= fb0 + 1
        fired = {m for w in runner.workers for (m, _) in w.fault_injector.fired}
        assert "COMPILE_FAIL" in fired
        # faults disarmed: the same query compiles and matches again
        for w in runner.workers:
            w.fault_injector.clear()
        assert runner.query(sql) == expected
    finally:
        runner.stop()


def test_chaos_harness_arms_compile_modes():
    """ChaosRunner determinism: COMPILE_MODES ride the seeded schedule with
    a delay for COMPILE_SLOW, without perturbing existing mode tuples."""
    from trino_tpu.testing.chaos import (
        COMPILE_MODES, CORRUPTION_MODES, RECOVERABLE_MODES,
    )

    assert COMPILE_MODES == ("COMPILE_SLOW", "COMPILE_FAIL")
    # seeded-replay compatibility: existing tuples unchanged
    assert RECOVERABLE_MODES == ("ERROR", "TIMEOUT", "SLOW", "EXCHANGE_DROP")
    assert CORRUPTION_MODES == RECOVERABLE_MODES + ("CORRUPT",)
    assert set(COMPILE_MODES) <= set(FaultInjector.MODES)
