"""From the profiler's trace to numbers: the one reduction every PR shares.

`profile_slice` profiles a steady slice of the window (jax.profiler, host
tracer at its lowest level, python tracer off) and reduces it; `load` and
`reduce` are the reduction itself, checked by selftest.py against the
recorded trace in testdata/.  Device time comes from the device planes
("/device:TPU:<n>", line "XLA Ops"; "XLA Modules" where a trace has no op
line) and from nowhere else: never from a host clock, never from XLA's cost
analysis.

Clocks: the trace counts nanoseconds from its own start, the harness and the
program's spans use time.perf_counter.  `profile_slice` writes a few
`bench_sync` annotations whose perf_counter time it knows; their median
offset maps trace time onto perf_counter time.
"""

from __future__ import annotations

import glob
import os
import shutil
import statistics
import time

SYNC = "bench_sync"
OP_LINES = ("XLA Ops", "XLA Modules")
NAME_CHARS = 96  # the trace names an op by its whole HLO line; keep its head


def load(path: str) -> dict:
    """An .xplane.pb -> {"devices": {plane: [(name, start_ns, dur_ns)]},
    "modules": n, "host": [(name, start_ns, dur_ns)] for bench* annotations}."""
    from jax.profiler import ProfileData

    devices: dict = {}
    modules = 0
    host = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Modules" in lines:
                modules += sum(1 for _ in lines["XLA Modules"].events)
            for want in OP_LINES:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in lines[want].events] if want in lines else []
                if evs:
                    devices[plane.name] = evs
                    break
            else:
                devices[plane.name] = []
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name.startswith("bench")
                )
    return {"devices": devices, "modules": modules, "host": host}


def union(intervals: list) -> list:
    """Sorted, merged [(a, b)]."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(merged: list, a: float, b: float) -> float:
    """Length of [a, b] that the merged intervals cover."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged if y > a and x < b)


def reduce(loaded: dict, s0_ns: float, s1_ns: float) -> dict:
    """Busy time (union of device-op intervals, averaged over the chips),
    per-op sums and the busy intervals, all clipped to [s0_ns, s1_ns] in
    trace time.  Seconds out."""
    busy_each = []
    merged_all = []
    ops: dict = {}
    for _plane, evs in sorted(loaded["devices"].items()):
        clipped = []
        for name, start, dur in evs:
            a, b = max(start, s0_ns), min(start + dur, s1_ns)
            if b > a:
                clipped.append((a, b))
                key = name[:NAME_CHARS]
                ops[key] = ops.get(key, 0.0) + (b - a) / 1e9
        merged = union(clipped)
        busy_each.append(sum(b - a for a, b in merged) / 1e9)
        merged_all.append(merged)
    return {
        "busy_s": sum(busy_each) / max(1, len(busy_each)),
        "window_s": (s1_ns - s0_ns) / 1e9,
        "ops": ops,
        "busy_ns": merged_all[0] if merged_all else [],  # the first chip's
        "chips": len(busy_each),
    }


def profile_slice(out_dir: str, start_after_s: float, slice_s: float) -> dict:
    """Called by the harness while the streams run.  Returns the reduction,
    with busy intervals and the slice mapped onto time.perf_counter."""
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    time.sleep(start_after_s)
    jax.profiler.start_trace(out_dir, profiler_options=options)
    marks = []
    for _ in range(5):
        marks.append(time.perf_counter_ns())
        with jax.profiler.TraceAnnotation(SYNC):
            pass
    s0 = time.perf_counter_ns()
    time.sleep(slice_s)
    s1 = time.perf_counter_ns()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    loaded = load(path)
    syncs = sorted(start for name, start, _d in loaded["host"] if name == SYNC)
    if len(syncs) != len(marks):
        raise RuntimeError(f"trace holds {len(syncs)} of {len(marks)} sync marks")
    offset = statistics.median(s - m for s, m in zip(syncs, marks))
    out = reduce(loaded, s0 + offset, s1 + offset)
    out["path"] = path
    out["slice"] = (s0 / 1e9, s1 / 1e9)
    out["busy"] = [((a - offset) / 1e9, (b - offset) / 1e9) for a, b in out.pop("busy_ns")]
    out["modules"] = loaded["modules"]
    return out


# ----------------------------------------------------- what readers share


def share_in_slice(rec: dict, traced: dict) -> float:
    """The part of a request's interval that lies inside the traced slice."""
    s0, s1 = traced["slice"]
    length = max(rec["t1"] - rec["t0"], 1e-9)
    return max(0.0, min(rec["t1"], s1) - max(rec["t0"], s0)) / length


def host_ms(intervals: list, traced: dict) -> float | None:
    """Median over the intervals that lie inside the slice of their length
    minus the time the device was busy inside them: what the host spent."""
    s0, s1 = traced["slice"]
    inside = [(a, b) for a, b in intervals if a >= s0 and b <= s1]
    if not inside:
        return None
    return statistics.median(
        (b - a - covered(traced["busy"], a, b)) * 1e3 for a, b in inside)


def query_spans(ctx: dict) -> dict:
    """query id -> the coordinator's `query` span, for the window's requests."""
    ids = {r.get("query_id") for r in ctx["records"]} - {None}
    return {s["attrs"]["query_id"]: s for s in ctx["spans"]
            if s["name"] == "query" and s["attrs"].get("query_id") in ids}


def breakdown(traced: dict, records: list, spans: list) -> dict:
    """The device operations that took most time, and the device's idle time
    inside the slice by what the host was doing: the templates whose client
    calls were open (the harness's own annotations) and the innermost
    program span that covered the gap's middle."""
    ops = sorted(traced["ops"].items(), key=lambda kv: -kv[1])[:10]
    s0, s1 = traced["slice"]
    gaps, at = [], s0
    for a, b in traced["busy"]:
        if a > at:
            gaps.append((at, min(a, s1)))
        at = max(at, b)
    if at < s1:
        gaps.append((at, s1))
    idle: dict = {}
    for a, b in gaps:
        mid = (a + b) / 2
        open_calls = sorted({r["template"] for r in records if r["t0"] <= mid <= r["t1"]})
        over = [s for s in spans if s["t0"] <= mid <= s["t1"]]
        span = max(over, key=lambda s: (s["depth"], s["t0"]))["name"] if over else "no span"
        label = ("bench:" + "+".join(open_calls) if open_calls else "no client call") + " / " + span
        idle[label] = idle.get(label, 0.0) + (b - a)
    top = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in top]}
