#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmarks/run.py --cpu-rehearsal --workload <cell> [--seconds 2]

A cell (BENCHMARK.json "workloads") names a configuration and a traffic mix.
This file finds configs/<name>.json, traffic/<name>.json, templates/<name>.json,
reference/<name>.py, layer_metrics/<name>.py and entries/<name>.py by name and
holds no branch on a cell, a query or a scale (README.md beside it).

One run: set-up (generate the tables, start the system, warm every statement
until an execution compiles nothing, settle under the cell's own concurrency),
then the window — closed streams, each repeating whole passes of its mix while
the window is open — then, outside any timing, every answer (or the mix's
seeded sample) against the plain numpy reference.  The last line of standard
output is the result; --trace 1 profiles a steady slice of the window and
prints the per-layer metrics instead of the end-to-end ones.

Without --cpu-rehearsal a run that finds no TPU, or another number of devices
than the cell's `chips`, fails before any work.  The rehearsal walks the same
control flow at the configuration's rehearsal scale with interpreted kernels,
prints no device metric and always exits 3.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import compare as _compare  # noqa: E402
import loader  # noqa: E402
import traffic as _traffic  # noqa: E402
from bytes_model import least_bytes  # noqa: E402

EXIT_NO_CHIP = 2
EXIT_REHEARSAL = 3
MAX_WARM_ROUNDS = 4  # loose compile, tightened compile, a clean run, one spare


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------- bookkeeping


class CompileLog:
    """Seconds JAX spent compiling or loading programs, and persistent-cache
    hits and misses, from JAX's own monitoring events."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.lock = threading.Lock()
        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self.BACKEND:
            with self.lock:
                self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        with self.lock:
            if event == self.HIT:
                self.hits += 1
            elif event == self.MISS:
                self.misses += 1

    def snapshot(self) -> dict:
        with self.lock:
            return {"compile_s": self.seconds, "cache_hits": self.hits,
                    "cache_misses": self.misses}


def seed_capacities() -> str:
    """The program sizes every stateful node loosely on a plan's first
    execution, tightens the capacities it observed and compiles again; it
    keeps what it learned in a file (TRINO_TPU_CAPS_CACHE, else
    <checkout>/.jax_cache/caps_cache.json) so that a restarted process
    compiles one program.  A checkout that has never run has no such file,
    and at SF1 the loose program of q18 alone compiles for 686 s (PERF.md,
    PR 23).  So the entries the program learned at SF1 are kept as data
    under caps/*.json and handed to it here, before it first reads its file,
    wherever that file lacks them.  An entry is keyed by the plan and its
    input shapes: one that no longer fits is never looked up, and the program
    learns anew.  -> the file's path."""
    path = os.environ.setdefault(
        "TRINO_TPU_CAPS_CACHE",
        os.path.join(os.path.dirname(HERE), ".jax_cache", "caps_cache.json"))
    try:
        with open(path) as f:
            have = json.load(f)
    except (OSError, ValueError):
        have = {}
    new = {}
    folder = os.path.join(HERE, "caps")
    for name in sorted(os.listdir(folder)) if os.path.isdir(folder) else []:
        if name.endswith(".json"):
            for key, entry in loader.load_json("caps", name)["entries"].items():
                if key not in have:
                    new[key] = entry
    if new:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".seed.tmp"
        with open(tmp, "w") as f:
            json.dump({**have, **new}, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    say(f"bench: capacities at {path}: {len(have)} entries there, {len(new)} added from caps/")
    return path


def builds() -> int:
    """Fragment programs built so far by the program's process-global
    compile service (every executor compiles through it): the counter that
    SERVICE.stats()["builds"] reports, read without its lock — the streams
    read it around every request."""
    from trino_tpu.exec.compilesvc import SERVICE

    return SERVICE.builds


def fallbacks() -> float:
    from trino_tpu.exec.compilesvc import FALLBACKS

    return sum(v for _s, _l, v in FALLBACKS._samples())


# ------------------------------------------------------------------ window


def drive(entry, streams, seconds: float = 0.0, passes: int = 0) -> tuple[list, list, list]:
    """Start the closed loop: one thread per stream.  A stream starts a pass
    only while the window is open (`seconds`; or, for settling, while it has
    made fewer than `passes`) and finishes the pass it began.  Returns at
    once with (records, [time the window opened], threads) for `finish`;
    clock time.perf_counter."""
    import jax

    records: list = []
    lock = threading.Lock()
    ready = threading.Barrier(len(streams) + 1)
    opened = [0.0]

    def one(stream) -> None:
        request = entry.client(stream.index)
        ready.wait()
        if stream.stagger_s:
            time.sleep(stream.stagger_s)
        made = 0
        while made < passes or time.perf_counter() - opened[0] < seconds:
            made += 1
            for name in stream.order:
                binding = stream.next_binding(name)
                rec = {"stream": stream.index, "template": name,
                       "binding": binding, "rows": None, "error": None}
                built = builds()
                rec["t0"] = time.perf_counter()
                try:
                    with jax.profiler.TraceAnnotation("bench:" + name):
                        rec["rows"], rec["query_id"] = request(name, binding)
                except Exception as e:  # a failed query is a result, not a crash
                    rec["error"] = f"{type(e).__name__}: {e}"[:300]
                rec["t1"] = time.perf_counter()
                rec["built"] = builds() - built  # by anyone, while this one was open
                with lock:
                    records.append(rec)

    threads = [threading.Thread(target=one, args=(s,), name=f"stream{s.index}")
               for s in streams]
    for t in threads:
        t.start()
    opened[0] = time.perf_counter()
    ready.wait()
    return records, opened, threads


def finish(records, opened, threads) -> tuple[list, float, float]:
    """Wait for the streams.  The window closes when the last started query
    completes.  -> (records, opened, closed)."""
    for t in threads:
        t.join()
    closed = max([r["t1"] for r in records], default=time.perf_counter())
    return records, opened[0], closed


# ------------------------------------------------------------- correctness


def check(records, templates, mix, data, limits, seed) -> dict:
    """Every distinct (template, binding) that ran — or, above the mix's
    `check_sample`, a seeded sample of that many per template — against the
    plain reference.  Marks each compared record; returns the numbers."""
    rng = random.Random(f"check/{int(seed)}")
    out = {"compared": 0, "wrong": 0, "exact_mismatches": 0, "decimal_rel_err": 0.0,
           "double_rel_err": 0.0, "decimal_cells_inexact": 0, "reference_s": 0.0,
           "bindings": 0, "first_wrong": {}}
    by_key: dict = {}
    for r in records:
        if r["rows"] is not None:
            by_key.setdefault(r["binding"].key, []).append(r)
    for name, t in templates.items():
        keys = sorted(k for k in by_key if k[0] == name)
        sample = int(mix.get("check_sample", 0))
        if sample and len(keys) > sample:
            keys = sorted(rng.sample(keys, sample))
        ref = loader.load_module("reference", t["reference"]).reference
        for key in keys:
            t0 = time.perf_counter()
            want = ref(data, *key[1])
            out["reference_s"] += time.perf_counter() - t0
            out["bindings"] += 1
            for r in by_key[key]:
                c = _compare.compare(r["rows"], want, t["ordered"])
                out["compared"] += 1
                for k in ("exact_mismatches", "decimal_cells_inexact"):
                    out[k] += c[k]
                for k in ("decimal_rel_err", "double_rel_err"):
                    out[k] = max(out[k], c[k])
                r["wrong"] = any(c[k] > limits[k] for k in limits)
                out["wrong"] += int(r["wrong"])
                if r["wrong"] and name not in out["first_wrong"]:
                    out["first_wrong"][name] = (r["binding"].params, r["rows"][:2], want[:2])
    return out


# ----------------------------------------------------------------- metrics


def end_to_end(records, opened, closed, setup_s) -> tuple[dict, dict]:
    ok = [r for r in records if r["error"] is None]
    by_t: dict = {}
    for r in ok:
        by_t.setdefault(r["template"], []).append((r["t1"] - r["t0"]) * 1e3)
    medians = {k: statistics.median(v) for k, v in by_t.items()}
    values = {"setup_s": setup_s}
    if ok:
        values["query_geomean_ms"] = math.exp(
            sum(math.log(m) for m in medians.values()) / len(medians))
        values["throughput_qps"] = len(ok) / (closed - opened)
    return values, {k: (len(by_t[k]), medians[k]) for k in sorted(by_t)}


def flatten(spans) -> list:
    out = []

    def walk(s, depth):
        out.append({"name": s.name, "t0": s.start_s, "t1": s.end_s,
                    "attrs": dict(s.attributes), "depth": depth})
        for c in s.children:
            walk(c, depth + 1)

    for s in spans:
        walk(s, 0)
    return out


# -------------------------------------------------------------------- main


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--cpu-rehearsal", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = loader.benchmark()
    cell, config, mix, templates = loader.cell(args.workload)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    rehearsal = args.cpu_rehearsal

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    say(f"bench: cell {cell['name']} config {cell['config']} traffic "
        f"{cell['traffic']} seed {args.seed} seconds {seconds:g} trace "
        f"{args.trace}; jax {jax.__version__} devices {device}")
    if rehearsal:
        if device["platform"] != "cpu":
            say("bench: --cpu-rehearsal wants JAX_PLATFORMS=cpu")
            return EXIT_NO_CHIP
    elif device["platform"] != "tpu" or device["count"] != cell["chips"]:
        say(f"bench: the cell needs {cell['chips']} TPU chip(s); JAX reports "
            f"{device}. This benchmark never carries on without them.")
        return EXIT_NO_CHIP
    peaks_table = loader.load_json("peaks.json")
    if not rehearsal and device["kind"] not in peaks_table:
        say(f"bench: no peaks for device kind {device['kind']!r} in peaks.json")
        return EXIT_NO_CHIP

    import trino_tpu  # noqa: F401  (x64 on, as every entry point has it)
    from trino_tpu.connectors.tpch import tpch_data
    from trino_tpu.connectors.tpch.generator import TPCH_SCHEMAS
    from trino_tpu.utils.compilecache import cache_dir, enable_persistent_cache

    enable_persistent_cache()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    say(f"bench: compile cache at {cache_dir()}")
    seed_capacities()
    compile_log = CompileLog()
    scale = float(config["rehearsal_scale_factor" if rehearsal else "scale_factor"])
    if rehearsal:
        from trino_tpu.ops.pallas import hashagg, segreduce, topk

        hashagg.INTERPRET = segreduce.INTERPRET = topk.FORCE = True
        config = dict(config, session=dict(config["session"], pallas_interpret="true"))

    # ---- set-up: data, system, warm, settle
    t0 = time.perf_counter()
    tables = sorted({t for tm in templates.values() for t in tm["columns"]})
    data = {t: tpch_data(t, scale) for t in tables}
    rows = {t: len(next(iter(cols.values()))) for t, cols in data.items()}
    say(f"bench: TPC-H scale {scale:g} from the generator's fixed seed: "
        f"{rows} in {time.perf_counter() - t0:.1f}s")
    entry = loader.load_module("entries", mix["entry"]).Entry(config, templates, scale)
    try:
        fallbacks0 = fallbacks()
        request = entry.client(0)
        for name, t in templates.items():
            warm = (_traffic.warm_bindings(t) if mix["bindings"] == "drawn"
                    else [_traffic.validation(t)])
            for b in warm:
                for round_ in range(MAX_WARM_ROUNDS):
                    before, t0 = builds(), time.perf_counter()
                    request(name, b)
                    dt, built = time.perf_counter() - t0, builds() - before
                    say(f"bench: warm {name} {b.params or ''} round {round_}: "
                        f"{dt:.3f}s, {built} program(s) built")
                    if not built:
                        break
                else:
                    raise RuntimeError(f"{name}: executions keep compiling")
        streams = _traffic.streams(mix, templates, args.seed)
        if mix.get("settle_passes"):
            settle = _traffic.streams(dict(mix, stagger_ms=0), templates, args.seed + 1)
            before, t0 = builds(), time.perf_counter()
            done, _, _ = finish(*drive(entry, settle, passes=int(mix["settle_passes"])))
            say(f"bench: settled with {len(done)} queries by {len(settle)} stream(s) "
                f"in {time.perf_counter() - t0:.1f}s, {builds() - before} program(s) built")
        setup = compile_log.snapshot()
        say(f"bench: set-up compiled or loaded programs for "
            f"{setup['compile_s']:.2f}s; persistent cache hits "
            f"{setup['cache_hits']} misses {setup['cache_misses']}")

        # ---- the window
        builds0 = builds()
        traced = None
        setup_s = time.perf_counter() - T_PROCESS
        running = drive(entry, streams, seconds)
        if args.trace:
            import tracered

            traced = tracered.profile_slice(
                os.path.join(HERE, ".trace", cell["name"]),
                start_after_s=min(1.0, seconds / 4),
                slice_s=min(float(mix["trace_slice_s"]), seconds),
            )
        records, opened, closed = finish(*running)
        builds_in_window = builds() - builds0
        fallbacks_in_run = fallbacks() - fallbacks0
        stats = devices[0].memory_stats() or {}
        device["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
        spans = flatten(entry.spans())
    finally:
        entry.close()
    say(f"bench: window {closed - opened:.3f}s (asked {seconds:g}s), "
        f"{len(records)} queries by {len(streams)} stream(s); "
        f"{builds_in_window} program(s) built in the window; "
        f"{fallbacks_in_run:g} eager fallback(s) in the run; peak device bytes "
        f"{device['memory_peak_bytes']} of {stats.get('bytes_limit')}")

    # ---- correctness, outside any timing
    limits = config["limits"]
    checked = check(records, templates, mix, data, limits, args.seed)
    errors = [r for r in records if r["error"] is not None]
    if builds_in_window:
        for r in sorted((r for r in records if r["built"]), key=lambda r: r["t0"])[:8]:
            say(f"bench: a program was built while {r['template']} "
                f"{r['binding'].params or ''} was open, {r['t0'] - opened:.1f}-"
                f"{r['t1'] - opened:.1f}s into the window")
    for r in errors[:5]:
        say(f"bench: FAILED {r['template']} {r['binding'].params}: {r['error']}")
    failed = len(errors) + checked["wrong"] + int(fallbacks_in_run)
    correct = failed == 0 and checked["compared"] > 0
    say(f"bench: compared {checked['compared']} answers over {checked['bindings']} "
        f"binding(s) with the numpy reference in {checked['reference_s']:.1f}s: "
        f"exact_mismatches {checked['exact_mismatches']} (limit "
        f"{limits['exact_mismatches']}), decimal_rel_err "
        f"{checked['decimal_rel_err']:.3e} (limit {limits['decimal_rel_err']:g}), "
        f"double_rel_err {checked['double_rel_err']:.3e} (limit "
        f"{limits['double_rel_err']:g}), eager_fallbacks {fallbacks_in_run:g} (limit 0), failed_queries "
        f"{len(errors)} (limit 0)")

    for name, (params, got, want) in checked["first_wrong"].items():
        say(f"bench: WRONG {name} {params or ''}: got {got} reference {want}")
    say(f"bench: decimal cells not equal to the exact value: "
        f"{checked['decimal_cells_inexact']} (information; PERF.md section 2)")
    values, per_template = end_to_end(records, opened, closed, setup_s)
    say("bench: per template (n, median ms): " + json.dumps(per_template))
    say(f"bench: {len(records) - len(errors)} latencies in the window; "
        f"setup_s {setup_s:.2f}")

    def wanted(kind: str) -> list:
        return [m for m in bench[kind]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    metrics: dict = {}
    result = {"correct": bool(correct), "attempted": len(records), "failed": failed}
    if args.trace:
        schemas = {t: TPCH_SCHEMAS[t] for t in tables}
        ctx = {
            "records": records, "spans": spans, "trace": traced, "setup": setup, "builds_in_window": builds_in_window,
            "least_bytes": {n: least_bytes(t, schemas, rows, config["column_bytes"])
                            for n, t in templates.items()},
            "peaks": peaks_table.get(device["kind"]), "chips": cell["chips"],
        }
        for m in wanted("per_layer"):
            v = loader.layer_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result["breakdown"] = tracered.breakdown(traced, records, spans)
    else:
        for m in wanted("end_to_end"):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if rehearsal:
        say("bench: CPU rehearsal only: not a chip run, no result line. "
            + json.dumps({"correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"],
                          "metric_names": sorted(metrics)}))
        return EXIT_REHEARSAL
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
