"""Benchmark entry point (driver contract: prints JSON lines; every line is a
complete, self-contained record and each one supersedes the previous, so the
driver gets a full result whether it parses the first or the last line).

Measures the north-star configs (BASELINE.json) on the default jax device
(the TPU chip where JAX finds one; CPU otherwise):

  #3 TPC-H Q18 — large-state group-by + join + TopN    (runs FIRST: it was
                 deadline-skipped in round 4; never again)
  #2 TPC-H Q3  — joins + high-cardinality group-by + radix-select TopN
  #1 TPC-H Q1  — scan + fused Pallas group-by aggregation (MXU one-hot)
  q6            — selective filter + global aggregate (bandwidth probe)
  #4 TPC-DS Q64/Q95 (budget-gated) — deep join trees
  #2b SF10 Q3 (budget-gated) — the multi-million-row join config

Budgeting: a global deadline (BENCH_BUDGET_S, default 420s) is enforced —
a query only starts with headroom remaining, run counts shrink rather than
blow the deadline, and results are re-emitted cumulatively after EVERY query
so a driver-side kill loses nothing already measured.

Each query reports wall seconds, effective GB/s over the columns it touches,
the device-side steady-state GB/s (back-to-back pipelined dispatches
amortizing the per-call host sync), cold warm-up seconds, and
vs_baseline = sqlite wall / engine wall (>1 means faster than sqlite).

Baseline honesty: the reference repo publishes no absolute numbers
(BASELINE.md) and the Java engine cannot run in this image (no JVM).
Baselines are same-host single-threaded sqlite over identical rows, cached
with provenance in BASELINE_SQLITE.json (committed) so repeat runs don't
re-pay the sqlite build+scan.

Compile-latency guard (round-4 regression: q03 cold warm-up hit 407s):
any query whose warm_s exceeds BENCH_WARM_BOUND (default 240s — warm_s
covers TWO warm executes: the initial compile and the adaptive-compaction
tightened-tier recompile) is flagged in `warm_regressions` — a loud signal
in the recorded bench JSON.

Concurrency (ROADMAP item 3 seed): N protocol clients x M queries each
against a 2-worker loopback cluster — QPS + p50/p99 latency under load in
`concurrency`, not just single-query wall.

Env knobs: BENCH_SF (default 1), BENCH_RUNS (default 5),
BENCH_QUERIES (default q18,q03,q01,q06), BENCH_BUDGET_S (default 900 —
round-5 verdict: 420 s deadline-skipped q01 on cold caches; the budget is
still enforced, just sized so all four tracked queries fit a cold run),
BENCH_STEADY_ITERS (default 8; pipelined iterations behind each
`device_gb_per_sec` — every tracked query reports it now, with iters
degrading to 2 rather than skipping when the deadline is near),
BENCH_TPCDS (default q64,q95 at scale 0.01; empty disables),
BENCH_SF10_Q3 (default auto: runs if budget headroom remains),
BENCH_WARM_BOUND (default 240),
BENCH_CONCURRENCY (default 1; 0 disables), BENCH_CONC_CLIENTS (default 4),
BENCH_CONC_QUERIES (default 5 per client), BENCH_CONC_SF (default 0.01),
BENCH_CONC_SQL (default lineitem group-by), BENCH_CONC_REPEAT (default 0:
hot-set fraction of queries repeating the shared statement — drives the
result-cache hit rate; the section reports cache-on vs cache-off QPS),
BENCH_CONC_PREPARED (default 0; 1 adds the serving-fast-path section:
PREPARE once / EXECUTE with varying parameters through the parameterized
plan cache vs the same workload as ad-hoc SQL text — every literal change
replanned and retraced — reporting both QPS/p50/p99 and the speedup),
BENCH_CONC_BATCH_MS (default 0: execute_batch_window_ms applied to the
prepared pass — concurrent same-plan EXECUTEs merge into one vmapped
device dispatch),
BENCH_MULTI_SCALE (default 1; 0 disables the split-driven scale sweep:
the same queries at BENCH_MS_SFS scales through a split-scheduling
cluster, reporting per-query split counts, split retries, and the jit-
signature count per scale — `multi_scale.signature_invariant` is the
scale-invariance witness; perf_gate.py ignores the block by design),
BENCH_MS_SFS (default 0.01,0.02), BENCH_MS_QUERIES (default q01,q06),
BENCH_MS_TARGET_ROWS (default 8192).
"""

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

import jax  # noqa: E402

# Persistent compilation cache: XLA/Mosaic compiles take tens of seconds
# and dominate time-to-first-number; cached compiles bring
# repeat bench runs (each driver round) down to seconds of warmup.
from trino_tpu.utils.compilecache import enable_persistent_cache  # noqa: E402

enable_persistent_cache(_REPO)

from tests.tpch_queries import QUERIES  # noqa: E402

# columns each benchmark query touches (for effective-bandwidth accounting)
_TOUCHED = {
    "q01": [("lineitem", ["l_returnflag", "l_linestatus", "l_quantity",
                          "l_extendedprice", "l_discount", "l_tax", "l_shipdate"])],
    "q03": [("customer", ["c_mktsegment", "c_custkey"]),
            ("orders", ["o_custkey", "o_orderkey", "o_orderdate", "o_shippriority"]),
            ("lineitem", ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"])],
    "q06": [("lineitem", ["l_extendedprice", "l_discount", "l_shipdate", "l_quantity"])],
    "q18": [("customer", ["c_name", "c_custkey"]),
            ("orders", ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"]),
            ("lineitem", ["l_orderkey", "l_quantity"])],
}

# v5e per-chip HBM bandwidth (public spec: 819 GB/s); CPU runs get no roofline
_HBM_GBPS = {"tpu": 819.0}

_BASELINE_FILE = os.path.join(_REPO, "BASELINE_SQLITE.json")


def _touched_bytes(names, sf) -> int:
    from trino_tpu.connectors.tpch import tpch_data

    total = 0
    for table, cols in names:
        data = tpch_data(table, sf)
        for c in cols:
            arr = data[c]
            total += arr.size * (8 if arr.dtype == object else arr.dtype.itemsize)
    return total


class _Deadline:
    def __init__(self, budget_s: float):
        self.t_end = time.perf_counter() + budget_s

    def remaining(self) -> float:
        return self.t_end - time.perf_counter()


def _sync_rtt_ms() -> float:
    """Round-trip latency of one tiny synchronous device interaction — the
    per-query latency floor every dispatch-then-fetch pays."""
    import numpy as np
    import jax.numpy as jnp

    x = jnp.zeros((8,))
    np.asarray(x + 1)  # warm
    t0 = time.perf_counter()
    for _ in range(3):
        np.asarray(x + 1)
    return (time.perf_counter() - t0) / 3 * 1e3


def _baseline_cache() -> dict:
    try:
        with open(_BASELINE_FILE) as f:
            return json.load(f)
    except Exception:
        return {}


def _save_baseline(cache: dict) -> None:
    try:
        with open(_BASELINE_FILE, "w") as f:
            json.dump(cache, f, indent=1)
    except Exception:
        pass


def _measure_tpch_baselines(sf: float, qnames, deadline) -> dict:
    """Single-threaded sqlite wall seconds per TPC-H query over identical
    rows (no JVM in this image to run the Java reference); cached with
    provenance.  Returns {qname: wall_s} plus q01 rows/s."""
    from tests.oracle import SqliteOracle
    from trino_tpu.connectors.tpch import tpch_data
    from trino_tpu.connectors.tpch.generator import TPCH_SCHEMAS

    cache = _baseline_cache()
    key = f"sf{sf}"
    entry = cache.get(key, {})
    missing = [q for q in qnames if f"{q}_wall_s" not in entry]
    if not missing:
        return entry
    if deadline.remaining() < 90:
        return entry  # the sqlite build alone takes minutes; don't start it
    tables = {t: tpch_data(t, sf) for t in TPCH_SCHEMAS}
    oracle = SqliteOracle(tables)
    li_rows = len(tables["lineitem"]["l_quantity"])
    for q in missing:
        if deadline.remaining() < 30:
            break
        t0 = time.perf_counter()
        oracle.query(QUERIES[q])
        wall = time.perf_counter() - t0
        entry[f"{q}_wall_s"] = round(wall, 3)
        if q == "q01":
            entry["q01_rows_per_sec"] = round(li_rows / wall)
    entry["engine"] = "sqlite3 single-threaded, same host"
    entry["measured_at"] = time.strftime("%Y-%m-%d")
    cache[key] = entry
    _save_baseline(cache)
    return entry


def _bench_concurrency(deadline) -> dict:
    """N clients x M queries through the full distributed protocol stack
    (POST /v1/statement + nextUri polling against a 2-worker loopback
    cluster): QPS and tail latency under concurrent load.  Small scale
    factor on purpose — this measures scheduling/protocol throughput, not
    scan bandwidth (the single-query sections above own that).

    BENCH_CONC_REPEAT (0..1, default 0) is the hot-set fraction: that share
    of each client's queries is the one shared statement (dashboard-style
    repeated load, result-cache hits), the rest get a distinct LIMIT
    appended so every plan hash is unique (always misses).  The section
    runs TWICE on the same cluster — result cache off, then on — so the
    JSON carries a like-for-like speedup plus the hit/miss latency split."""
    import threading

    from trino_tpu.client import StatementClient
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.testing import DistributedQueryRunner

    clients = int(os.environ.get("BENCH_CONC_CLIENTS", "4"))
    per_client = int(os.environ.get("BENCH_CONC_QUERIES", "5"))
    conc_sf = float(os.environ.get("BENCH_CONC_SF", "0.01"))
    repeat = min(1.0, max(0.0, float(os.environ.get("BENCH_CONC_REPEAT", "0"))))
    sql = os.environ.get(
        "BENCH_CONC_SQL",
        "select l_returnflag, count(*), sum(l_quantity) from lineitem "
        "group by l_returnflag",
    )
    runner = DistributedQueryRunner(num_workers=2, default_catalog="tpch")
    runner.register_catalog("tpch", TpchConnector(conc_sf))
    runner.start()

    def run_pass() -> dict:
        lats: list[float] = []
        errors = [0]
        lock = threading.Lock()
        hot_per_ten = int(round(repeat * 10))

        def one_client(ci: int):
            c = StatementClient(runner.coordinator.url)
            for i in range(per_client):
                # deterministic hot/cold interleave: `repeat` of every 10
                # queries reuse the shared statement, the rest are unique
                if (i % 10) < hot_per_ten:
                    q = sql
                else:
                    q = f"{sql} limit {100000 + ci * per_client + i}"
                t0 = time.perf_counter()
                try:
                    c.execute(q, timeout=120)
                except Exception:
                    with lock:
                        errors[0] += 1
                else:
                    dt = time.perf_counter() - t0
                    with lock:
                        lats.append(dt)

        threads = [
            threading.Thread(target=one_client, args=(ci,), daemon=True)
            for ci in range(clients)
        ]
        t_start = time.time()
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        join_by = time.perf_counter() + max(deadline.remaining(), 30.0)
        for t in threads:
            t.join(timeout=max(join_by - time.perf_counter(), 0.1))
        wall = time.perf_counter() - t0
        with lock:  # a timed-out straggler may still be appending
            done = sorted(lats)
            errs = errors[0]

        def pct(vals, p):
            if not vals:
                return None
            return round(vals[min(len(vals) - 1, int(p * len(vals)))] * 1000, 1)

        # server-side hit/miss latency split: the coordinator's live query
        # records carry the cached flag and the state-machine timestamps
        hit_walls: list[float] = []
        miss_walls: list[float] = []
        for rec in list(runner.coordinator.queries.values()):
            sm = rec["sm"]
            if sm.created_at < t_start - 0.25 or not sm.finished_at:
                continue
            (hit_walls if rec.get("cached") else miss_walls).append(
                sm.finished_at - sm.created_at
            )
        hit_walls.sort()
        miss_walls.sort()
        n_seen = len(hit_walls) + len(miss_walls)
        return {
            "completed": len(done),
            "errors": errs + sum(1 for t in threads if t.is_alive()),
            "wall_s": round(wall, 3),
            "qps": round(len(done) / wall, 2) if wall > 0 else None,
            "p50_ms": pct(done, 0.50),
            "p99_ms": pct(done, 0.99),
            "cache_hit_rate": (
                round(len(hit_walls) / n_seen, 3) if n_seen else 0.0
            ),
            "hit_p50_ms": pct(hit_walls, 0.50),
            "miss_p50_ms": pct(miss_walls, 0.50),
        }

    try:
        runner.query(sql)  # warm: compile lands outside the timed window
        runner.coordinator.session.set("result_cache_enabled", "false")
        off = run_pass()
        runner.coordinator.session.set("result_cache_enabled", "true")
        # the timed window is short — admit on first execution so the demo
        # measures the cache, not the admission ramp
        runner.coordinator.session.set("result_cache_min_recurrences", "0")
        runner.coordinator.result_cache.clear()
        on = run_pass()
        out = {
            "clients": clients,
            "queries_per_client": per_client,
            "sf": conc_sf,
            "repeat_fraction": repeat,
        }
        out.update(on)
        out["cache_disabled"] = off
        if on.get("qps") and off.get("qps"):
            out["qps_speedup_vs_nocache"] = round(on["qps"] / off["qps"], 2)
        return out
    finally:
        runner.stop()


def _bench_fleet(deadline) -> dict:
    """Coordinator-fleet scaling (runtime/fleet.py): the same concurrent
    load through a 1- then 2-coordinator fleet behind the shard router.

    What a second coordinator buys is SERVING CAPACITY — concurrent
    queries in flight — so the workload is shaped the way fleet scaling
    matters in practice: queries are I/O-bound (the connector simulates
    BENCH_FLEET_IO_DELAY_S of remote-storage latency per scan, the
    dominant term for warehouse scans) and each member's admission plane
    is capped at BENCH_FLEET_CONC_PER_COORD running queries, the
    resource-group limit a real deployment sizes per coordinator.  QPS is
    then N*cap/latency: it doubles with the member count, and the bench
    verifies the fleet plane (router sharding, leases, shared admission)
    delivers that instead of serializing.  CPU-bound scaling is NOT
    measurable here — bench hosts are single-core, and in-process members
    share one GIL — which is exactly why the load is latency-bound.
    Reports the per-coordinator QPS split at each N plus the 1->2
    speedup."""
    import threading

    import numpy as np

    from trino_tpu.client import StatementClient
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.connectors.spi import ColumnSchema
    from trino_tpu.data.types import BIGINT
    from trino_tpu.runtime.resourcegroups import (
        ResourceGroupConfig,
        ResourceGroupManager,
    )
    from trino_tpu.testing import DistributedQueryRunner

    clients = int(os.environ.get("BENCH_FLEET_CLIENTS", "8"))
    per_client = int(os.environ.get("BENCH_FLEET_QUERIES", "4"))
    cap = int(os.environ.get("BENCH_FLEET_CONC_PER_COORD", "2"))
    io_delay = float(os.environ.get("BENCH_FLEET_IO_DELAY_S", "0.8"))
    sql = "select count(*), sum(v) from t"

    class _SlowScanConnector(MemoryConnector):
        def read_split(self, split, columns):
            time.sleep(io_delay)
            return super().read_split(split, columns)

    def run_n(n: int) -> dict:
        conn = _SlowScanConnector()
        conn.create_table(
            "t", [ColumnSchema("k", BIGINT), ColumnSchema("v", BIGINT)]
        )
        conn.insert("t", {
            "k": np.arange(64, dtype=np.int64),
            "v": np.arange(64, dtype=np.int64) * 3,
        })
        runner = DistributedQueryRunner(
            num_workers=2, default_catalog="memory", num_coordinators=n
        )
        runner.register_catalog("memory", conn)
        runner.start()
        try:
            for c in runner.coordinators:
                c.session.set("result_cache_enabled", "false")
                c.execute_query(sql)  # warm: compile outside the window
            for c in runner.coordinators:
                c.resource_groups = ResourceGroupManager(
                    ResourceGroupConfig(max_concurrency=cap)
                )
            before = [len(c.queries) for c in runner.coordinators]
            lats: list[float] = []
            errors = [0]
            lock = threading.Lock()

            def one_client(ci: int):
                c = StatementClient(runner.client_url)
                for _ in range(per_client):
                    t0 = time.perf_counter()
                    try:
                        c.execute(sql, timeout=180)
                    except Exception:
                        with lock:
                            errors[0] += 1
                    else:
                        with lock:
                            lats.append(time.perf_counter() - t0)

            threads = [
                threading.Thread(target=one_client, args=(ci,), daemon=True)
                for ci in range(clients)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            wall = time.perf_counter() - t0
            lats.sort()
            per_coord = {}
            for i, c in enumerate(runner.coordinators):
                served = len(c.queries) - before[i]
                per_coord[f"c{i}"] = {
                    "queries": served,
                    "qps": round(served / wall, 2),
                }
            return {
                "completed": len(lats),
                "errors": errors[0],
                "wall_s": round(wall, 2),
                "qps": round(len(lats) / wall, 2),
                "p50_ms": (
                    round(lats[len(lats) // 2] * 1e3, 1) if lats else None
                ),
                "per_coordinator": per_coord,
            }
        finally:
            runner.stop()

    out: dict = {
        "clients": clients,
        "queries_per_client": per_client,
        "conc_per_coordinator": cap,
        "io_delay_s": io_delay,
        "sql": sql,
    }
    out["n1"] = run_n(1)
    if deadline.remaining() > 60:
        out["n2"] = run_n(2)
        if out["n1"].get("qps") and out["n2"].get("qps"):
            out["qps_speedup_1_to_2"] = round(
                out["n2"]["qps"] / out["n1"]["qps"], 2
            )
    return out


def _bench_observability(deadline) -> dict:
    """Flight-recorder overhead harness (ISSUE 17): warm p50 for q01/q06 on
    a local Engine with the recorder enabled vs disabled.  The recorder is a
    process-global bounded ring behind one lock; the acceptance budget is
    <5% warm-p50 overhead, reported per query as regression_pct +
    within_budget so perf CI can check it without a prior-run baseline."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine
    from trino_tpu.utils import flightrecorder as fr

    sf = float(os.environ.get("BENCH_OBS_SF", "0.1"))
    iters = int(os.environ.get("BENCH_OBS_ITERS", "9"))
    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(sf))
    out = {"sf": sf, "iters": iters, "budget_pct": 5.0, "queries": {}}

    def paired_p50(plan) -> tuple:
        # interleave one off-run and one on-run per iteration so host drift
        # (thermal, allocator state, noisy neighbours) lands on both sides
        # instead of biasing whichever pass ran second
        offs: list = []
        ons: list = []
        for _ in range(iters):
            fr.configure(enabled=False)
            t0 = time.perf_counter()
            eng.executor.execute(plan)
            offs.append(time.perf_counter() - t0)
            fr.configure(enabled=True)
            t0 = time.perf_counter()
            eng.executor.execute(plan)
            ons.append(time.perf_counter() - t0)
            if deadline.remaining() < 5:
                break
        return (sorted(offs)[len(offs) // 2], sorted(ons)[len(ons) // 2])

    prior = fr.stats()["enabled"]
    try:
        for name in ("q01", "q06"):
            if deadline.remaining() < 30:
                out["queries"][name] = {"skipped": "deadline"}
                continue
            plan = eng.plan(QUERIES[name])
            eng.executor.execute(plan)  # cold: generation + upload + compile
            eng.executor.execute(plan)  # adaptive-compaction recompile
            eng.executor.execute(plan)  # settle before the timed pairs
            off, on = paired_p50(plan)
            pct = 100.0 * (on - off) / off if off > 0 else 0.0
            out["queries"][name] = {
                "warm_p50_off_s": round(off, 4),
                "warm_p50_on_s": round(on, 4),
                "regression_pct": round(pct, 2),
                "within_budget": pct < 5.0,
            }
    finally:
        fr.configure(enabled=prior)
    out["within_budget"] = all(
        q.get("within_budget", True)
        for q in out["queries"].values()
        if isinstance(q, dict)
    )
    return out


def _bench_observatory(deadline) -> dict:
    """Telemetry-observatory harness (ISSUE 20), two halves:

    1. sampler overhead — warm p50 for q01/q06 with the time-series
       sampler thread running vs stopped, paired-interleaved like the
       flight-recorder harness; acceptance budget <5% warm-p50 overhead.
    2. roofline consistency — the live per-query figure (cost_analysis
       bytes_accessed x dispatches / measured execute wall, the same
       join the coordinator performs) must land within 2x of the same
       bytes over a dedicated steady-state device-wall measurement.
       Both sides use the profiler's byte totals, so the check isolates
       the WALL measurement (live in-band timing vs pipelined
       steady_state_time) — the part the observatory could get wrong."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine
    from trino_tpu.utils import timeseries as ts
    from trino_tpu.utils.profiler import PROFILER

    sf = float(os.environ.get("BENCH_OBS_SF", "0.1"))
    iters = int(os.environ.get("BENCH_OBS_ITERS", "9"))
    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(sf))
    out = {"sf": sf, "iters": iters, "budget_pct": 5.0, "queries": {}}

    sampler = ts.Sampler(
        "bench-observatory",
        {"cpu_s": ts.cpu_seconds, "rss_bytes": ts.current_rss_bytes},
        deltas={"cpu_s"},
    )

    def paired_p50(plan) -> tuple:
        # same interleave as _bench_observability: one off-run and one
        # on-run per iteration so host drift lands on both sides
        offs: list = []
        ons: list = []
        for _ in range(iters):
            sampler.stop()
            t0 = time.perf_counter()
            eng.executor.execute(plan)
            offs.append(time.perf_counter() - t0)
            sampler.start()
            t0 = time.perf_counter()
            eng.executor.execute(plan)
            ons.append(time.perf_counter() - t0)
            if deadline.remaining() < 5:
                break
        return (sorted(offs)[len(offs) // 2], sorted(ons)[len(ons) // 2])

    def live_figures() -> tuple:
        # join the executor's per-signature dispatch ledger with the
        # profiler's cost figures — the coordinator's roofline math.
        # Returns (bytes moved by the LAST execute() call, its summed
        # dispatch wall).
        byts = 0.0
        exec_s = 0.0
        for sig, ev in (getattr(eng.executor, "execute_events", None)
                        or {}).items():
            prof = PROFILER.snapshot(sig) or {}
            ba = prof.get("bytes_accessed")
            if ba and ev.get("executes") and ev.get("execute_s"):
                byts += float(ba) * ev["executes"]
                exec_s += ev["execute_s"]
        return byts, exec_s

    try:
        for name in ("q01", "q06"):
            if deadline.remaining() < 30:
                out["queries"][name] = {"skipped": "deadline"}
                continue
            plan = eng.plan(QUERIES[name])
            eng.executor.execute(plan)  # cold: generation + upload + compile
            eng.executor.execute(plan)  # adaptive-compaction recompile
            eng.executor.execute(plan)  # settle before the timed pairs
            off, on = paired_p50(plan)
            pct = 100.0 * (on - off) / off if off > 0 else 0.0
            entry = {
                "warm_p50_off_s": round(off, 4),
                "warm_p50_on_s": round(on, 4),
                "regression_pct": round(pct, 2),
                "within_budget": pct < 5.0,
            }
            byts, exec_s = live_figures()
            if byts > 0 and exec_s > 0 and hasattr(
                eng.executor, "steady_state_time"
            ):
                live = byts / exec_s / 1e9
                dev_s = eng.executor.steady_state_time(plan, iters=3)
                bench_gbps = byts / dev_s / 1e9 if dev_s > 0 else 0.0
                ratio = live / bench_gbps if bench_gbps > 0 else 0.0
                entry["live_device_gb_per_sec"] = round(live, 3)
                entry["bench_device_gb_per_sec"] = round(bench_gbps, 3)
                entry["live_vs_bench_ratio"] = round(ratio, 3)
                entry["within_2x"] = 0.5 <= ratio <= 2.0
            out["queries"][name] = entry
    finally:
        sampler.stop()
    out["within_budget"] = all(
        q.get("within_budget", True)
        for q in out["queries"].values()
        if isinstance(q, dict)
    )
    return out


def _bench_prepared(deadline) -> dict:
    """Serving fast path (runtime/fastpath.py): PREPARE once, EXECUTE with a
    different parameter every time, against the same workload issued the old
    way — distinct literal SQL text per query, so every statement re-parses,
    re-plans, and re-traces.  Same cluster, same data, same clients; the
    only variable is whether parameters ride the parameterized plan cache as
    jit arguments or get baked into fresh plans as constants.

    The prepared pass replays the client-held registry header
    (X-Trino-Prepared-Statement) instead of a server-side PREPARE, i.e. the
    stateless-client mode a connection pool would use."""
    import threading

    from trino_tpu.client import StatementClient
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.testing import DistributedQueryRunner

    clients = int(os.environ.get("BENCH_CONC_CLIENTS", "4"))
    per_client = int(os.environ.get("BENCH_CONC_QUERIES", "5"))
    conc_sf = float(os.environ.get("BENCH_CONC_SF", "0.01"))
    batch_ms = float(os.environ.get("BENCH_CONC_BATCH_MS", "0"))
    template = (
        "select l_returnflag, count(*) c, sum(l_quantity) s from lineitem "
        "where l_quantity < ? group by l_returnflag order by l_returnflag"
    )

    def param(ci: int, i: int) -> float:
        # distinct per (client, query) so the ad-hoc pass can never reuse a
        # plan and the prepared pass proves value-independence
        return 1.5 + ((ci * per_client + i) * 7) % 47

    runner = DistributedQueryRunner(num_workers=2, default_catalog="tpch")
    runner.register_catalog("tpch", TpchConnector(conc_sf))
    runner.start()

    def run_pass(prepared: bool) -> dict:
        lats: list[float] = []
        errors = [0]
        lock = threading.Lock()

        def one_client(ci: int):
            c = StatementClient(runner.coordinator.url)
            if prepared:
                c.prepared["bp"] = template
            for i in range(per_client):
                v = param(ci, i)
                if prepared:
                    q = f"EXECUTE bp USING {v}"
                else:
                    q = template.replace("?", str(v))
                t0 = time.perf_counter()
                try:
                    c.execute(q, timeout=300)
                except Exception:
                    with lock:
                        errors[0] += 1
                else:
                    dt = time.perf_counter() - t0
                    with lock:
                        lats.append(dt)

        threads = [
            threading.Thread(target=one_client, args=(ci,), daemon=True)
            for ci in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        join_by = time.perf_counter() + max(deadline.remaining(), 60.0)
        for t in threads:
            t.join(timeout=max(join_by - time.perf_counter(), 0.1))
        wall = time.perf_counter() - t0
        with lock:
            done = sorted(lats)
            errs = errors[0]

        def pct(vals, p):
            if not vals:
                return None
            return round(vals[min(len(vals) - 1, int(p * len(vals)))] * 1000, 1)

        return {
            "completed": len(done),
            "errors": errs + sum(1 for t in threads if t.is_alive()),
            "wall_s": round(wall, 3),
            "qps": round(len(done) / wall, 2) if wall > 0 else None,
            "p50_ms": pct(done, 0.50),
            "p99_ms": pct(done, 0.99),
        }

    try:
        # both passes measure the plan path, not the result cache; distinct
        # parameters per query would defeat it anyway, this makes it explicit
        runner.coordinator.session.set("result_cache_enabled", "false")
        # warm data residency + the prepared statement's one compile; the
        # ad-hoc pass gets the same residency warmth (its plans can't be
        # pre-compiled — that asymmetry IS the thing being measured)
        c = StatementClient(runner.coordinator.url)
        c.prepared["bp"] = template
        c.execute("EXECUTE bp USING 0.5")
        adhoc = run_pass(prepared=False)
        if batch_ms > 0:
            runner.coordinator.session.set(
                "execute_batch_window_ms", str(batch_ms)
            )
        prep = run_pass(prepared=True)
        out = {
            "clients": clients,
            "queries_per_client": per_client,
            "sf": conc_sf,
            "batch_window_ms": batch_ms,
        }
        out.update(prep)
        out["adhoc"] = adhoc
        if prep.get("qps") and adhoc.get("qps"):
            out["qps_speedup_vs_adhoc"] = round(prep["qps"] / adhoc["qps"], 2)
        return out
    finally:
        runner.stop()


def _bench_multi_scale(deadline) -> dict:
    """Split-driven scale sweep (ISSUE 14): the same queries at several
    BENCH_MS_SFS data scales through a split-scheduling cluster.  Reports,
    per scale and query: split count, split retries, wall time, and the
    number of distinct jit signatures the run touched — the tentpole claim
    is that the split COUNT moves with data while the signature count does
    NOT (``signature_invariant`` per query).  Each scale also reports the
    storage-pressure counters from the workers' governed disk pools
    (``disk``: spool/spill peak bytes, pressure reclaims, reclaimed
    bytes, typed sheds) — at sf10 the spool grows ~100x, and these show
    whether the run lived off reclaim or started shedding.  Informational
    only: scripts/perf_gate.py ignores this block by design.

    Knobs: BENCH_MS_SFS (default "0.01,0.02"), BENCH_MS_QUERIES (default
    "q01,q06"), BENCH_MS_TARGET_ROWS (default 8192), BENCH_MS_DISK_BUDGET
    (per-worker disk pool bytes, default 1 GiB).
    """
    import shutil
    import tempfile

    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.testing import DistributedQueryRunner
    from trino_tpu.utils.profiler import PROFILER

    sfs = [float(s) for s in
           os.environ.get("BENCH_MS_SFS", "0.01,0.02").split(",") if s]
    qnames = [q for q in
              os.environ.get("BENCH_MS_QUERIES", "q01,q06").split(",") if q]
    target = int(os.environ.get("BENCH_MS_TARGET_ROWS", "8192"))
    disk_budget = int(os.environ.get("BENCH_MS_DISK_BUDGET", str(1 << 30)))

    def uses(e):
        return (e.get("executes", 0) + e.get("compiles", 0)
                + e.get("fallback_executes", 0))

    out: dict = {"target_rows": target, "scales": {}}
    sig_counts: dict[str, list[int]] = {}
    for sf in sfs:
        if deadline.remaining() < 60:
            out["scales"][str(sf)] = {"skipped": "deadline"}
            continue
        runner = DistributedQueryRunner(
            num_workers=2, default_catalog="tpch", heartbeat_interval=0.5,
            disk_budget_bytes=disk_budget,
        )
        runner.register_catalog("tpch", TpchConnector(sf))
        runner.start()
        spool_dir = tempfile.mkdtemp(prefix="bench_ms_spool_")
        s = runner.coordinator.session
        s.set("retry_policy", "TASK")
        s.set("exchange_spool_dir", spool_dir)
        s.set("split_driven_scans", "true")
        s.set("split_target_rows", str(target))
        per_scale: dict = {}
        try:
            for q in qnames:
                if deadline.remaining() < 30:
                    per_scale[q] = {"skipped": "deadline"}
                    continue
                before = PROFILER.snapshot()
                t0 = time.perf_counter()
                runner.query(QUERIES[q])
                wall = time.perf_counter() - t0
                after = PROFILER.snapshot()
                nsigs = sum(
                    1 for sig, e in after.items()
                    if uses(e) > uses(before.get(sig, {}))
                )
                info = None
                for rec in runner.coordinator.queries.values():
                    qi = rec.get("query_info") or {}
                    if qi.get("splits"):
                        info = qi["splits"]
                per_scale[q] = {
                    "wall_s": round(wall, 3),
                    "splits": (info or {}).get("splits"),
                    "split_retries": (info or {}).get("retries", 0),
                    "jit_signatures": nsigs,
                }
                sig_counts.setdefault(q, []).append(nsigs)
        except Exception as e:
            per_scale["error"] = str(e)[:200]
        finally:
            # storage pressure for the whole scale: max peak across the
            # workers' disk pools, summed reclaim/shed counters
            disk = {"budget_bytes": disk_budget, "peak_bytes": 0,
                    "reclaims": 0, "reclaimed_bytes": 0, "sheds": 0}
            for w in runner.workers:
                if getattr(w, "disk_pool", None) is not None:
                    snap = w.disk_pool.snapshot()
                    disk["peak_bytes"] = max(disk["peak_bytes"], snap["peak"])
                    disk["reclaims"] += snap["reclaims"]
                    disk["reclaimed_bytes"] += snap["reclaimed_bytes"]
                    disk["sheds"] += snap["sheds"]
            per_scale["disk"] = disk
            runner.stop()
            shutil.rmtree(spool_dir, ignore_errors=True)
        out["scales"][str(sf)] = per_scale
    out["signature_invariant"] = {
        q: len(set(c)) == 1 for q, c in sig_counts.items() if len(c) > 1
    }
    return out


def main() -> None:
    sf = float(os.environ.get("BENCH_SF", "1"))
    runs = int(os.environ.get("BENCH_RUNS", "5"))
    qnames = os.environ.get("BENCH_QUERIES", "q18,q03,q01,q06").split(",")
    deadline = _Deadline(float(os.environ.get("BENCH_BUDGET_S", "900")))
    warm_bound = float(os.environ.get("BENCH_WARM_BOUND", "240"))
    steady_iters = int(os.environ.get("BENCH_STEADY_ITERS", "8"))

    from trino_tpu.connectors.tpch import TpchConnector, tpch_data
    from trino_tpu.runtime.engine import Engine

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(sf))
    li_rows = len(tpch_data("lineitem", sf)["l_quantity"])
    baseline = _baseline_cache().get(f"sf{sf}", {})

    result = {
        "metric": f"tpch_q1_sf{sf}_rows_per_sec",
        "value": None,  # null (not 0) when unmeasured: "no measurement"
        "unit": "rows/s",
        # baseline = same-host single-threaded sqlite over identical rows;
        # per-query ratios in queries[q]["vs_baseline"] (>1 == faster)
        "vs_baseline": None,
        "sf": sf,
        "device": jax.default_backend(),
        "sync_rtt_ms": None,
        "queries": {},
        "roofline": None,
        "warm_regressions": [],
        "compile": None,
    }

    def emit():
        print(json.dumps(result), flush=True)

    def bench_one(name):
        # A query is only STARTED with headroom for a cold warm-up; an XLA
        # compile already in flight cannot be preempted, so a driver-side kill
        # mid-warm loses only the in-flight query — everything measured before
        # it was already emitted cumulatively.
        if deadline.remaining() < 45:
            result["queries"][name] = {"skipped": "deadline"}
            return
        try:
            t0 = time.perf_counter()
            plan = eng.plan(QUERIES[name])
            eng.executor.execute(plan)  # warm: generation + upload + compile
            # second warm: adaptive compaction may have TIGHTENED capacity
            # tiers after observing true row counts (exec/compiler.py) — the
            # tightened program compiles here, not inside the timed runs
            eng.executor.execute(plan)
            warm_s = time.perf_counter() - t0
            if warm_s > warm_bound:
                result["warm_regressions"].append(
                    {"query": name, "warm_s": round(warm_s, 1), "bound": warm_bound}
                )
            # shrink run count instead of blowing the global deadline
            per_run = max(warm_s * 0.1, 0.05)  # steady runs are ~10x faster
            n_runs = max(1, min(runs, int((deadline.remaining() - 10) / max(per_run, 1e-3))))
            times = []
            for _ in range(n_runs):
                t0 = time.perf_counter()
                eng.executor.execute(plan)
                # no extra block_until_ready: execute() fetches the packed
                # overflow vector synchronously, and that host copy completes
                # only after the WHOLE XLA program
                times.append(time.perf_counter() - t0)
                if deadline.remaining() < 5:
                    break
            elapsed = sorted(times)[len(times) // 2]
            nbytes = _touched_bytes(_TOUCHED[name], sf)
            entry = {
                "wall_s": round(elapsed, 4),
                # bytes moved over touched columns / wall — comparable across
                # queries (rows/s flatters narrow single-table scans)
                "effective_gb_per_sec": round(nbytes / elapsed / 1e9, 3),
                "warm_s": round(warm_s, 2),
            }
            base_wall = baseline.get(f"{name}_wall_s")
            if base_wall:
                entry["vs_baseline"] = round(base_wall / elapsed, 2)
            if deadline.remaining() > 5 and hasattr(eng.executor, "steady_state_time"):
                # device-side time with pipelined dispatch: the RTT-free
                # number.  Every tracked query reports it (round-5 gap: q03
                # lacked device_gb_per_sec): when the deadline is close the
                # iteration count degrades instead of the metric vanishing.
                iters = steady_iters if deadline.remaining() > 15 else 2
                dev_s = eng.executor.steady_state_time(plan, iters=iters)
                entry["device_s"] = round(dev_s, 4)
                entry["device_gb_per_sec"] = round(nbytes / dev_s / 1e9, 3)
            if name == "q01":
                entry["rows_per_sec"] = round(li_rows / elapsed)
            result["queries"][name] = entry
        except Exception as e:  # keep the rest of the bench alive
            result["queries"][name] = {"error": str(e)[:200]}

    def compile_stats():
        # compile-latency distribution across the whole sweep, from the
        # executor's per-signature compile ledger (fresh compiles only —
        # joins/waits measure queueing, not XLA)
        walls = sorted(
            ev["compile_s"]
            for ev in getattr(eng.executor, "compile_events", [])
            if "compile_s" in ev
        )
        if not walls:
            return None

        def pct(p):
            return round(walls[min(len(walls) - 1, int(p * len(walls)))], 3)

        return {
            "compiles": len(walls),
            "total_s": round(sum(walls), 2),
            "p50_s": pct(0.50),
            "p99_s": pct(0.99),
            "max_s": walls[-1],
        }

    # q18 FIRST (round-4 verdict: it must never be deadline-skipped), then
    # q03, then the q01 headline, then q06
    for name in qnames:
        bench_one(name)
        result["compile"] = compile_stats()
        if name == "q01":
            rps = result["queries"].get("q01", {}).get("rows_per_sec")
            result["value"] = rps
            base_rps = baseline.get("q01_rows_per_sec")
            if rps and base_rps:
                result["vs_baseline"] = round(rps / base_rps, 2)
            result["sync_rtt_ms"] = round(_sync_rtt_ms(), 1)
            q01 = result["queries"].get("q01", {})
            hbm = _HBM_GBPS.get(result["device"])
            if hbm and "device_gb_per_sec" in q01:
                best = max(
                    (q.get("device_gb_per_sec", 0.0) or 0.0, n)
                    for n, q in result["queries"].items()
                    if isinstance(q, dict)
                )
                result["roofline"] = {
                    "hbm_gbps": hbm,
                    "q01_device_gbps": q01["device_gb_per_sec"],
                    "q01_pct_of_hbm": round(100 * q01["device_gb_per_sec"] / hbm, 1),
                    "best_device_gbps": best[0],
                    "best_query": best[1],
                    "best_pct_of_hbm": round(100 * best[0] / hbm, 1),
                    "note": "wall = sync RTT (host dispatch) + device time;"
                            " device time from back-to-back pipelined runs",
                }
        emit()

    # ---- TPC-DS north-star pair (config #4), budget-gated ----------------
    ds_names = [q for q in os.environ.get("BENCH_TPCDS", "q64,q95").split(",") if q]
    if ds_names and deadline.remaining() > 90:
        try:
            from tests.tpcds_queries import QUERIES as DSQ
            from trino_tpu.connectors.tpcds import TpcdsConnector, tpcds_data
            from trino_tpu.connectors.tpcds.generator import TPCDS_SCHEMAS

            ds_scale = float(os.environ.get("BENCH_TPCDS_SF", "0.01"))
            ds_eng = Engine(default_catalog="tpcds")
            ds_eng.register_catalog("tpcds", TpcdsConnector(ds_scale))
            cache = _baseline_cache()
            ds_key = f"tpcds_sf{ds_scale}"
            ds_base = cache.get(ds_key, {})
            for q in ds_names:
                if deadline.remaining() < 60:
                    break
                if q not in DSQ:
                    continue
                t0 = time.perf_counter()
                plan = ds_eng.plan(DSQ[q])
                ds_eng.executor.execute(plan)
                warm_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                ds_eng.executor.execute(plan)
                wall = time.perf_counter() - t0
                entry = {"wall_s": round(wall, 4), "warm_s": round(warm_s, 2),
                         "scale": ds_scale}
                if f"{q}_wall_s" not in ds_base and deadline.remaining() > 45:
                    from tests.oracle import SqliteOracle

                    needed = [t for t in TPCDS_SCHEMAS if t in DSQ[q]]
                    oracle = SqliteOracle(
                        {t: tpcds_data(t, ds_scale) for t in needed},
                        schemas=TPCDS_SCHEMAS,
                    )
                    t0 = time.perf_counter()
                    oracle.query(DSQ[q])
                    ds_base[f"{q}_wall_s"] = round(time.perf_counter() - t0, 3)
                    ds_base["engine"] = "sqlite3 single-threaded, same host"
                    ds_base["measured_at"] = time.strftime("%Y-%m-%d")
                    cache[ds_key] = ds_base
                    _save_baseline(cache)
                if ds_base.get(f"{q}_wall_s"):
                    entry["vs_baseline"] = round(ds_base[f"{q}_wall_s"] / wall, 2)
                result["queries"][f"tpcds_{q}"] = entry
                emit()
        except Exception as e:
            result["queries"]["tpcds"] = {"error": str(e)[:200]}
            emit()

    # ---- SF10 Q3 (north-star config #2), budget-gated --------------------
    want_sf10 = os.environ.get("BENCH_SF10_Q3", "auto")
    if want_sf10 != "0" and (want_sf10 == "1" or deadline.remaining() > 240):
        try:
            eng10 = Engine()
            eng10.register_catalog("tpch", TpchConnector(10.0))
            t0 = time.perf_counter()
            plan = eng10.plan(QUERIES["q03"])
            eng10.executor.execute(plan)
            warm_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            eng10.executor.execute(plan)
            wall = time.perf_counter() - t0
            nbytes = _touched_bytes(_TOUCHED["q03"], 10.0)
            entry = {
                "wall_s": round(wall, 4),
                "warm_s": round(warm_s, 2),
                "effective_gb_per_sec": round(nbytes / wall / 1e9, 3),
            }
            if deadline.remaining() > 15 and hasattr(eng10.executor, "steady_state_time"):
                dev_s = eng10.executor.steady_state_time(plan, iters=4)
                entry["device_s"] = round(dev_s, 4)
                entry["device_gb_per_sec"] = round(nbytes / dev_s / 1e9, 3)
            result["queries"]["q03_sf10"] = entry
            emit()
        except Exception as e:
            result["queries"]["q03_sf10"] = {"error": str(e)[:200]}
            emit()

    # ---- concurrency: N clients x M queries (ROADMAP item 3 seed) --------
    if os.environ.get("BENCH_CONCURRENCY", "1") != "0" and deadline.remaining() > 60:
        try:
            result["concurrency"] = _bench_concurrency(deadline)
        except Exception as e:
            result["concurrency"] = {"error": str(e)[:200]}
        emit()
        # fleet: per-coordinator QPS split at N=1 vs N=2 through the
        # shard router (ISSUE 13)
        if os.environ.get("BENCH_FLEET", "1") != "0" and deadline.remaining() > 90:
            try:
                result["concurrency"]["fleet"] = _bench_fleet(deadline)
            except Exception as e:
                result["concurrency"]["fleet"] = {"error": str(e)[:200]}
            emit()

    # ---- split-driven multi-scale sweep (ISSUE 14), budget-gated ---------
    if os.environ.get("BENCH_MULTI_SCALE", "1") != "0" and deadline.remaining() > 120:
        try:
            result["multi_scale"] = _bench_multi_scale(deadline)
        except Exception as e:
            result["multi_scale"] = {"error": str(e)[:200]}
        emit()

    # ---- flight-recorder overhead: warm p50 on vs off (ISSUE 17) ---------
    if os.environ.get("BENCH_OBSERVABILITY", "1") != "0" and deadline.remaining() > 60:
        try:
            result["observability"] = _bench_observability(deadline)
        except Exception as e:
            result["observability"] = {"error": str(e)[:200]}
        emit()

    # ---- telemetry observatory: sampler overhead + roofline check -------
    if os.environ.get("BENCH_OBSERVATORY", "1") != "0" and deadline.remaining() > 60:
        try:
            result["observatory"] = _bench_observatory(deadline)
        except Exception as e:
            result["observatory"] = {"error": str(e)[:200]}
        emit()

    # ---- serving fast path: PREPARE/EXECUTE vs ad-hoc text (ISSUE 10) ----
    if os.environ.get("BENCH_CONC_PREPARED", "0") == "1" and deadline.remaining() > 60:
        try:
            result["prepared"] = _bench_prepared(deadline)
        except Exception as e:
            result["prepared"] = {"error": str(e)[:200]}
        emit()

    # sqlite baselines LAST (the expendable part of the budget); cached
    # measurements from a prior run make this free
    tpch_qs = [q for q in qnames if q in _TOUCHED]
    fresh = _measure_tpch_baselines(sf, tpch_qs, deadline)
    changed = False
    for q in tpch_qs:
        entry = result["queries"].get(q, {})
        base_wall = fresh.get(f"{q}_wall_s")
        if isinstance(entry, dict) and "wall_s" in entry and base_wall:
            entry["vs_baseline"] = round(base_wall / entry["wall_s"], 2)
            changed = True
    rps = result.get("value")
    if rps and fresh.get("q01_rows_per_sec"):
        result["vs_baseline"] = round(rps / fresh["q01_rows_per_sec"], 2)
        changed = True
    if changed:
        emit()

    # hard perf-regression gate (scripts/perf_gate.py): point BENCH_GATE_PREV
    # at the previous run's BENCH_*.json and any NEW warm regression or
    # wall-ratio blowup flips this process's exit code — the advisory
    # warm_regressions list becomes CI-enforceable
    prev_path = os.environ.get("BENCH_GATE_PREV")
    if prev_path:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "perf_gate", os.path.join(_REPO, "scripts", "perf_gate.py")
        )
        gate = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(gate)
        try:
            prev = gate.load(prev_path)
        except (OSError, ValueError) as e:
            print(f"perf gate: cannot read {prev_path}: {e}", file=sys.stderr)
            sys.exit(1)
        failures = gate.compare(prev, result)
        if failures:
            for f in failures:
                print(f"PERF GATE FAIL {f}", file=sys.stderr)
            sys.exit(2)
        print(f"perf gate: ok vs {prev_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
