#!/usr/bin/env python3
"""The quickest proof that trino_tpu still starts on the chip.

    python chip_smoke.py             one TPU chip: the main path at TPC-H SF1
    python chip_smoke.py --queries q05,q08,q03,q18
                                     the same phases over other TPC-H queries
                                     (minutes of compilation each: not for a
                                     1200 s limit)
    python chip_smoke.py --chips 4   four chips: Engine(distributed=True)
                                     against the one-device Engine, nothing else
    python chip_smoke.py --cpu-rehearsal [--scale 0.01]
                                     the same control flow on the CPU with
                                     interpreted kernels; never prints the ok
                                     line and always exits non-zero

One process holds the chip.  The main path is driven through the two entry
points a user calls:

  library  Engine().query(sql)                         (README quick start)
  served   StatementClient -> coordinator -> worker -> device, all in this
           process (trino_tpu/testing/runner.py), over /v1/statement + nextUri

Every result is compared, outside any timing, with the sqlite oracle
(tests/oracle.py) over the same generated rows: integers exact, doubles at
rtol 1e-6.  The oracle is a child process pinned to the CPU platform — it
never asks for the chip — and is waited for and reaped before the script
ends.

The script fails (non-zero exit, no ok line) when JAX finds no TPU, when any
phase raises, when any execution took a fallback that hides the device, when
an execution still compiles after capacities have settled, when an output array is not on the
TPU, when a Pallas kernel of the main path neither was selected by a query
nor ran in the kernels phase, or when the native page serde did not build.  Times printed here are the smoke's own
output, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# What the default run can hold.  The TPU backend takes ~20-50 s to compile
# ONE lax.sort over a million-row frame (measured chiplessly: a 1-key int32
# sort of 2M rows 26 s, the 7-operand group-by sort 543 s), and every
# sort-based join or group-by brings several: q03 compiles for ~10 minutes,
# q18 for ~20, each again on the served path whose fragments are different
# programs.  Inside 1200 s, compilation included, fit: q06 and q01 (the
# fused scan kernel, no big sort) and q12 (a 6M-row sort join, a group-by,
# the segment-reduce kernel).  The query that selects the radix top-k kernel
# through SQL (q18: TopN over 8M lanes) runs behind --queries, with a longer
# time limit; the default run drives that kernel and the segment reduction on
# the chip directly, at SF1 shapes, against numpy (kernels_phase).
QUERY_NAMES = ("q06", "q01", "q12")
HEAVY_QUERY_NAMES = ("q05", "q08", "q03", "q18")
DIST_QUERY_NAMES = ("q01", "q12")

# dispatch op (ops/kernels.py) -> the kernel module it selects
KERNEL_FILES = {
    "fused_pipeline": "fused.py",
    "segment_reduce": "segreduce.py",
    "top_n": "topk.py",
}
RTOL = 1e-6


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ oracle


def oracle_child(scale: float, names: list[str]) -> int:
    """Child process body: sqlite over the same generated rows.  Pinned to
    the CPU platform before anything imports jax; prints one JSON line."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO)
    from tests.oracle import SqliteOracle
    from tests.tpch_queries import QUERIES
    from trino_tpu.connectors.tpch import tpch_data
    from trino_tpu.connectors.tpch.generator import TPCH_SCHEMAS

    text = " ".join(QUERIES[n] for n in names)
    tables = {}
    for table, cols in TPCH_SCHEMAS.items():
        need = [c for c, _ in cols if c in text]
        if need:
            data = tpch_data(table, scale)
            tables[table] = {c: data[c] for c in need}
    t0 = time.perf_counter()
    oracle = SqliteOracle(tables)
    out = {"load_s": round(time.perf_counter() - t0, 1), "rows": {}, "s": {}}
    for n in names:
        t0 = time.perf_counter()
        out["rows"][n] = [list(r) for r in oracle.query(QUERIES[n])]
        out["s"][n] = round(time.perf_counter() - t0, 1)
    print("ORACLE:" + json.dumps(out), flush=True)
    return 0


def start_oracle(scale: float, names) -> subprocess.Popen:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the child never needs the chip
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--oracle-child",
         "--scale", str(scale), "--queries", ",".join(names)],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True,
    )


def finish_oracle(proc: subprocess.Popen) -> dict:
    """Wait for the child's answer (main() reaps it whatever happens)."""
    stdout, _ = proc.communicate(timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"oracle child exited {proc.returncode}")
    line = [l for l in stdout.splitlines() if l.startswith("ORACLE:")][-1]
    return json.loads(line[len("ORACLE:"):])


def plain(rows) -> list[tuple]:
    """Engine/protocol rows -> comparable tuples (dates and decimals as the
    oracle sees them: ISO strings and floats)."""
    import datetime
    import decimal

    def conv(v):
        if isinstance(v, decimal.Decimal):
            return float(v)
        if isinstance(v, (datetime.date, datetime.datetime)):
            return v.isoformat()
        return v

    return [tuple(conv(v) for v in r) for r in rows]


def check_rows(tag: str, name: str, got, want) -> None:
    from tests.oracle import assert_rows_equal
    from tests.tpch_queries import ORDERED

    assert_rows_equal(
        plain(got), [tuple(r) for r in want],
        ordered=ORDERED.get(name, True), rtol=RTOL,
    )
    say(f"[{tag}] {name}: {len(got)} rows equal the sqlite oracle's")


# ------------------------------------------------------------ bookkeeping


def fallback_total() -> float:
    from trino_tpu.exec.compilesvc import FALLBACKS

    return sum(v for _s, _l, v in FALLBACKS._samples())


def compile_count() -> int:
    """Fragment programs built so far by the process-global compile service
    (every executor compiles through it: the library engine, the
    coordinator's root fragments, each worker task)."""
    from trino_tpu.exec.compilesvc import SERVICE

    return SERVICE.stats()["builds"]


def settle(run) -> int:
    """Re-execute until an execution compiles nothing; returns how many
    programs that took.  A first execution learns each Compact point's true
    surviving count and the executor tightens that capacity for every later
    run (exec/compiler.py, adaptive compaction), so the SECOND execution of
    a query with compaction points compiles one tighter program by design;
    from then on capacities are converged.  More than three rounds would be
    a retrace bug."""
    start = compile_count()
    for _ in range(3):
        before = compile_count()
        run()
        if compile_count() == before:
            return before - start
    raise RuntimeError("executions keep compiling: capacities never converge")


def pallas_ops() -> dict:
    from trino_tpu.ops.kernels import _DISPATCH

    return {
        op: _DISPATCH.value(op, "pallas") for op in KERNEL_FILES
    }


def check_events(events: list[dict], where: str) -> None:
    bad = [e for e in events if e.get("mode") == "fallback" or e.get("lazy")]
    if bad:
        raise RuntimeError(f"{where}: fallback/lazy compile events: {bad}")


def check_on_device(page, platform: str, name: str) -> None:
    for col in page.columns:
        for arr in (col.data, col.valid, col.data2):
            if arr is None:
                continue
            plats = {d.platform for d in arr.devices()}
            if plats != {platform}:
                raise RuntimeError(
                    f"{name}: an output array lives on {plats}, not {platform}"
                )


# ------------------------------------------------------------- the phases


def kernels_phase(scale: float, ran: dict) -> None:
    """The main-path kernels no default query reaches, run on the chip
    through their own entry points over SF-sized lineitem columns and
    compared with numpy.  Prints cold (compile + run) and warm seconds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu.connectors.tpch import tpch_data
    from trino_tpu.ops.pallas import topk
    from trino_tpu.ops.pallas.segreduce import SegRed, fused_segment_reduce

    li = tpch_data("lineitem", scale)
    lnum = np.asarray(li["l_linenumber"]).astype(np.int32)  # 1..7
    price = np.asarray(li["l_extendedprice"]).astype(np.int64)
    ship = np.asarray(li["l_shipdate"]).astype(np.int32)
    n = len(lnum)
    live = np.ones((n,), np.bool_)
    live[::97] = False

    def timed(name, fn, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        warm = time.perf_counter() - t0
        say(f"[kernels] {name}: n {n} cold {cold:.2f}s warm {warm:.4f}s")
        return jax.device_get(out)

    d_live = jnp.asarray(live)

    # segment reduce: exact int64 sum, count, i32 min/max over 7 segments
    seg = np.where(live, lnum - 1, 7).astype(np.int32)
    d_valid = d_live

    def segred(s, p, d, v):
        return fused_segment_reduce(
            s,
            [SegRed("sum", p, v), SegRed("count", None, v),
             SegRed("min", d, v), SegRed("max", d, v)],
            7,
        )

    sums, counts, mins, maxs = timed(
        "segreduce.fused_segment_reduce G 7 sum/count/min/max",
        jax.jit(segred), jnp.asarray(seg), jnp.asarray(price),
        jnp.asarray(ship), d_valid,
    )
    for g in range(7):
        m = live & (lnum == g + 1)
        if (int(sums[g]) != int(price[m].sum()) or int(counts[g]) != int(m.sum())
                or int(mins[g]) != int(ship[m].min())
                or int(maxs[g]) != int(ship[m].max())):
            raise RuntimeError(f"segreduce disagrees with numpy in segment {g}")
    ran.setdefault("segment_reduce", "kernels phase")

    # radix top-k: the exact 10th-largest 32-bit key of the live rows
    u = (price + (1 << 31)).astype(np.uint32)
    thresh = timed(
        "topk.radix_topk_threshold k 10",
        jax.jit(lambda x, lv: topk.radix_topk_threshold(x, lv, 10)),
        jnp.asarray(u), d_live,
    )
    if int(thresh) != int(np.sort(u[live])[-10]):
        raise RuntimeError("topk.radix_topk_threshold disagrees with numpy")
    ran.setdefault("top_n", "kernels phase")


def library_phase(scale, names, platform, selected, session) -> dict:
    """README quick start: Engine().query(sql), cold then warm."""
    import jax

    from tests.tpch_queries import QUERIES
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(scale))
    if platform == "cpu":  # rehearsal: the fused gate reads the session
        eng.session.set("pallas_interpret", "true")
    for prop, value in session.items():
        eng.session.set(prop, value)
    results = {}
    for name in names:
        sql = QUERIES[name]
        ex = eng.executor
        before_ops = pallas_ops()
        n_events = len(ex.compile_events)
        t0 = time.perf_counter()
        rows = eng.query(sql)
        cold = time.perf_counter() - t0
        events = ex.compile_events[n_events:]
        check_events(events, f"library {name}")
        compile_s = sum(e.get("compile_s", 0.0) for e in events)
        cache = [e.get("cache") for e in events]
        picked = sorted(
            op for op, v in pallas_ops().items() if v > before_ops[op]
        )
        for op in picked:
            selected.setdefault(op, f"selected (impl=pallas) by {name}")

        # once capacities have converged a repeat execution compiles
        # nothing, and it runs on the device
        settled = settle(lambda: eng.query(sql))
        misses = compile_count()
        t0 = time.perf_counter()
        page = eng.execute_page(sql)
        jax.block_until_ready([c.data for c in page.columns])
        warm = time.perf_counter() - t0
        if compile_count() != misses:
            raise RuntimeError(f"library {name}: a settled execution compiled")
        check_on_device(page, platform, name)
        rows2 = page.to_pylist()
        if plain(rows2) != plain(rows):
            raise RuntimeError(f"library {name}: second execution differs")
        results[name] = rows
        say(
            f"[library] {name}: cold {cold:.2f}s (compile {compile_s:.2f}s in "
            f"{len(events)} program(s), persistent cache {cache}; {settled} "
            f"more program(s) until capacities settled) warm "
            f"{warm:.3f}s rows {len(rows)} kernels "
            f"{[KERNEL_FILES[o] for o in picked] or 'none (already traced)'}"
        )
    return results


def served_phase(scale: float, names, session) -> dict:
    """The served path: SQL text from a client, coordinator, worker, device,
    and back over /v1/statement + nextUri — all in this one process."""
    from tests.tpch_queries import QUERIES
    from trino_tpu.client.client import StatementClient
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.testing.runner import DistributedQueryRunner

    runner = DistributedQueryRunner(num_workers=1)
    runner.register_catalog("tpch", TpchConnector(scale))
    runner.start()
    results = {}
    try:
        for prop, value in session.items():
            runner.coordinator.session.set(prop, value)
        client = StatementClient(runner.client_url)
        for name in names:
            sql = QUERIES[name]
            misses = compile_count()
            t0 = time.perf_counter()
            _cols, rows = client.execute(sql, timeout=3000.0)
            cold = time.perf_counter() - t0
            compiled = int(compile_count() - misses)
            settled = settle(lambda: client.execute(sql, timeout=3000.0))
            misses = compile_count()
            t0 = time.perf_counter()
            _cols, rows2 = client.execute(sql, timeout=3000.0)
            warm = time.perf_counter() - t0
            if compile_count() != misses:
                raise RuntimeError(f"served {name}: a settled execution compiled")
            if plain(rows2) != plain(rows):
                raise RuntimeError(f"served {name}: second execution differs")
            results[name] = rows
            say(
                f"[served] {name}: cold {cold:.2f}s ({compiled} program(s) "
                f"compiled; {settled} more until capacities settled) warm "
                f"{warm:.3f}s rows {len(rows)}"
            )
    finally:
        runner.stop()
    return results


def distributed_phase(scale: float, names, devices) -> None:
    """--chips 4: the queries through Engine(distributed=True) over every
    device, rows compared with the one-device Engine in this process."""
    from tests.oracle import assert_rows_equal
    from tests.tpch_queries import ORDERED, QUERIES
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    deng = Engine(distributed=True, devices=devices)
    deng.register_catalog("tpch", TpchConnector(scale))
    dist = {}
    for name in names:
        t0 = time.perf_counter()
        dist[name] = deng.query(QUERIES[name])
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        again = deng.query(QUERIES[name])
        warm = time.perf_counter() - t0
        if plain(again) != plain(dist[name]):
            raise RuntimeError(f"distributed {name}: second execution differs")
        say(f"[distributed x{len(devices)}] {name}: cold {cold:.2f}s warm "
            f"{warm:.3f}s rows {len(dist[name])}")
    # where the scan columns live: every input sharded over the mesh, each
    # device holding its own row range and nothing more
    shares = []
    for key, page in deng.executor._sharded_pages.items():
        for col in page.columns:
            per_dev = {}
            for shard in col.data.addressable_shards:
                per_dev[shard.device.id] = shard.data.nbytes
            total = sum(per_dev.values())
            if len(per_dev) != len(devices) or total != col.data.nbytes:
                raise RuntimeError(
                    f"{key[1]}: a scan column is not sharded over the mesh: "
                    f"{col.data.sharding}"
                )
            shares.append(max(per_dev.values()) / total)
    say(f"[distributed] {len(shares)} scan columns sharded over "
        f"{len(devices)} devices; largest per-device share of a column "
        f"{max(shares):.4f}")
    for d in devices:
        stats = d.memory_stats() or {}
        say(f"[distributed] device {d.id}: bytes_in_use "
            f"{stats.get('bytes_in_use')} peak {stats.get('peak_bytes_in_use')}")

    one = Engine()
    one.register_catalog("tpch", TpchConnector(scale))
    for name in names:
        want = one.query(QUERIES[name])
        assert_rows_equal(
            plain(dist[name]), plain(want),
            ordered=ORDERED.get(name, True), rtol=RTOL,
        )
        say(f"[distributed] {name}: {len(want)} rows equal the one-device "
            f"engine's")


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument(
        "--queries", default="",
        help="comma-separated TPC-H queries instead of the default set "
        f"({','.join(QUERY_NAMES)}; with --chips 4 "
        f"{','.join(DIST_QUERY_NAMES)}); heavy: {','.join(HEAVY_QUERY_NAMES)}",
    )
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--oracle-child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.oracle_child:
        return oracle_child(args.scale, args.queries.split(","))

    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    say(f"chip_smoke: jax {jax.__version__}, devices {device}")
    if args.cpu_rehearsal:
        if platform != "cpu":
            say("chip_smoke: --cpu-rehearsal wants JAX_PLATFORMS=cpu")
            return 2
    elif platform != "tpu":
        say(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
            f"this script never carries on on the CPU")
        return 2
    if len(devices) != args.chips and not args.cpu_rehearsal:
        say(f"chip_smoke: --chips {args.chips} but JAX reports "
            f"{len(devices)} device(s)")
        return 2

    sys.path.insert(0, REPO)
    import trino_tpu  # noqa: F401  (x64 on, as every entry point has it)
    from trino_tpu.utils.compilecache import cache_stats, enable_persistent_cache

    enable_persistent_cache()
    say(f"chip_smoke: compile cache {cache_stats()} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r})")
    if args.cpu_rehearsal:
        from trino_tpu.ops.pallas import hashagg, segreduce, topk

        hashagg.INTERPRET = segreduce.INTERPRET = topk.FORCE = True

    picked = tuple(q for q in args.queries.split(",") if q)
    if args.chips == 4:
        distributed_phase(args.scale, picked or DIST_QUERY_NAMES, devices)
        say(f"chip_smoke: distributed phase done in "
            f"{time.perf_counter() - t_start:.1f}s")
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0

    from trino_tpu.native import page_serde

    serde = page_serde()
    say(f"chip_smoke: page serde "
        f"{'native (native/pageserde.cpp, built here)' if serde.native else 'python fallback'}")
    if not serde.native:
        raise RuntimeError("the native page serde did not build")

    names = picked or QUERY_NAMES
    # The engine's default compile_deadline_s (300 s) sends a query whose
    # fragment compiles for longer down the eager fallback — on this backend
    # that is q03, q18 and every other query of --queries.  The smoke's point
    # is the compiled path, so for those it waits for the compile; the
    # default set runs with the defaults a user gets.
    session = {"compile_deadline_s": "0"} if picked else {}
    if session:
        say(f"chip_smoke: --queries: session {session} (the 300 s default "
            f"would time these compiles out into the eager fallback)")
    oracle = start_oracle(args.scale, names)
    try:
        t0 = time.perf_counter()
        from trino_tpu.connectors.tpch import tpch_data
        from trino_tpu.connectors.tpch.generator import TPCH_SCHEMAS

        rows_total = 0
        for table in TPCH_SCHEMAS:
            rows_total += len(next(iter(tpch_data(table, args.scale).values())))
        say(f"chip_smoke: TPC-H scale {args.scale} generated from the fixed "
            f"seed: {rows_total} rows in {time.perf_counter() - t0:.1f}s")

        selected: dict = {}
        lib = library_phase(args.scale, names, platform, selected, session)
        srv = served_phase(args.scale, names, session)
        kernels_phase(args.scale, selected)
        want = finish_oracle(oracle)
    finally:
        if oracle.poll() is None:
            oracle.kill()
            oracle.wait()
    say(f"chip_smoke: oracle loaded in {want['load_s']}s, queries {want['s']}")
    for name in names:
        check_rows("library", name, lib[name], want["rows"][name])
        check_rows("served", name, srv[name], want["rows"][name])

    for op, fname in KERNEL_FILES.items():
        say(f"chip_smoke: kernel {fname} ran on the device: "
            f"{selected.get(op, 'NOT AT ALL')}")
    missing = [f for op, f in KERNEL_FILES.items() if op not in selected]
    n_fallback = fallback_total()
    say(f"chip_smoke: trino_tpu_fallback_executions_total {n_fallback:g}; "
        f"compile cache now {cache_stats()}; peak device bytes "
        f"{(devices[0].memory_stats() or {}).get('peak_bytes_in_use')}")
    if n_fallback:
        raise RuntimeError(f"{n_fallback:g} executions took the eager fallback")
    if missing and not args.cpu_rehearsal and not picked:
        raise RuntimeError(f"these Pallas kernels never ran: {missing}")
    say(f"chip_smoke: all phases passed in {time.perf_counter() - t_start:.1f}s")
    if args.cpu_rehearsal:
        say("chip_smoke: CPU rehearsal only: not a chip run, no ok line")
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
