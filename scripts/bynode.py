#!/usr/bin/env python3
"""Device seconds by plan node of each program in a traced benchmark run
(`benchmarks/run.py --trace 1` leaves the .xplane.pb under
benchmarks/.trace/<cell>/plugins/profile/<time>/): PERF.md section 5's
by-node tables.  An op counts for its innermost plan-node scope (the `tf_op`
of its metadata, read by benchmarks/layer_metrics/device_attributed_share.py's
walk) and is a sort, a fusion (gathers and elementwise passes), a scatter, a
`reduce-window` (running sums and maxima), a `while` (binary searches) or
other; the op line is cut into programs at idle gaps and a program is named
by the scopes it holds (FINGER: the embedded cells' statements).  Host only.

    python scripts/bynode.py <trace.xplane.pb> [gap_ms=2]"""
import collections
import os
import re
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
sys.path[:0] = [BENCH, os.path.join(BENCH, "layer_metrics")]
import loader  # noqa: E402
walk = loader.load_module("layer_metrics", "device_attributed_share")


def label(hlo, tf_op):
    scopes = walk.SCOPE.findall(tf_op)
    name = hlo.lstrip("%").split(" ")[0]
    return (scopes[-1] if scopes else None, name)


walk.label = label
path = sys.argv[1]
gap_ps = float(sys.argv[2]) * 1e9 if len(sys.argv) > 2 else 2e9  # 2 ms
FINGER = [("q21", {"Join#8", "Join#6", "Join#10"}), ("q20", {"Join#13"}), ("q22", {"Filter#18"}),
          ("q16", {"Filter#10"}), ("q18", {"Join#8", "Aggregate#13"}), ("q12", {"Join#3"})]


def kind(name: str) -> str:
    n = re.sub(r"[.\d]+$", "", name)
    if n.startswith("sort"):
        return "sort"
    if "scatter" in n:
        return "scatter"
    if n.startswith(("reduce-window", "reduce_window")):
        return "reduce-window"
    if "fusion" in n or n.startswith("gather"):
        return "fusion"
    return "while" if n.startswith("while") else "other"


for plane, events in walk.device_ops(path):
    events = sorted(events, key=lambda e: e[1])
    programs, cur, last_end = [], [], None
    for lab, a, b in events:
        if last_end is not None and a - last_end > gap_ps and cur:
            programs.append(cur)
            cur = []
        cur.append((lab, a, b))
        last_end = max(last_end or 0, b)
    if cur:
        programs.append(cur)
    print(plane, len(events), "ops in", len(programs), "programs")
    seen = collections.Counter()
    for prog in programs:
        scopes = {lab[0] for lab, _a, _b in prog if lab and lab[0]}
        busy = sum(b - a for _l, a, b in prog) / 1e12
        name = next((n for n, f in FINGER if f <= scopes), None)
        if name is None or busy < 0.01:
            continue
        seen[name] += 1
        by = collections.defaultdict(lambda: collections.Counter())
        for lab, a, b in prog:
            node = (lab[0] if lab and lab[0] else "no scope")
            by[node][kind(lab[1]) if lab else "other"] += (b - a) / 1e12
        span = (prog[-1][2] - prog[0][1]) / 1e12
        rows = sorted(by.items(), key=lambda kv: -sum(kv[1].values()))
        print(f"{name} #{seen[name]}: busy {busy:.3f} s over {span:.3f} s, {len(prog)} ops")
        for node, c in rows:
            tot = sum(c.values())
            if tot >= 0.0005:
                print(f"    {node:14s} {tot:7.3f}  " + "  ".join(f"{k} {v:.3f}" for k, v in c.most_common()))
        big = sorted((((b - a) / 1e12, lab) for lab, a, b in prog), key=lambda x: x[0])[-8:]
        print("    largest ops: " + "; ".join(f"{lab[1] if lab else '?'}@{lab[0] if lab else '?'} {t:.3f}" for t, lab in reversed(big)))
