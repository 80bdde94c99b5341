"""planner (plan/reorder.py, plan/stats.py): how far the join-order cost
model was from what the joins made.  Over the inner equi-joins of the
statements that ran in the traced slice, the median of
max(estimated / actual, actual / estimated): 1 is a planner that knew, 10 a
planner that chose its order from numbers an order of magnitude off.

`estimated` is the `planner` span's `join_estimates` (node -> the rows the
Selinger formula gave that join's output, the numbers the order was costed
with), `actual` the rows the compiled program reported live in that node's
frame (the `device_wait` span's `frames`: node -> [tier, rows]); a run is
paired with the last statement planned before it, and counts when every join
of that plan is among its frames and none overflowed its tier.  A program
that writes no `join_estimates` leaves nothing to read."""

import bisect
import statistics


def errors(runs: list) -> list:
    """`runs`: (join_estimates, frames) pairs -> one factor a join."""
    out = []
    for estimates, frames in runs:
        if not estimates or not set(estimates) <= set(frames):
            continue
        if any(need > cap for cap, need in frames.values()):
            continue
        for node, est in estimates.items():
            est, actual = max(float(est), 1.0), max(float(frames[node][1]), 1.0)
            out.append(max(est / actual, actual / est))
    return out


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    s0, s1 = t["slice"]
    planned = sorted((s for s in ctx["spans"] if s["name"] == "planner"),
                     key=lambda s: s["t1"])
    ends = [p["t1"] for p in planned]
    runs = []
    for s in ctx["spans"]:
        if s["name"] == "device_wait" and "frames" in s["attrs"] \
                and s["t1"] > s0 and s["t0"] < s1:
            last = bisect.bisect_right(ends, s["t0"])  # planned before this run began
            if last:
                runs.append((planned[last - 1]["attrs"].get("join_estimates"),
                             s["attrs"]["frames"]))
    found = errors(runs)
    return statistics.median(found) if found else None
