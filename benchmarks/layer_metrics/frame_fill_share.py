"""capacity sizing (exec/compiler.py `execute`: size, run, grow on overflow,
tighten once; exec/capcache.py): of the lanes the slice's programs ran their
sized plan nodes at, the share that held a live row.  Kernel work scales with
a node's tier, not with its rows — a compaction sorts its whole frame for the
rows that survive — so this is what the tiers cost beyond the data.

Read from the `frames` attribute of the `device_wait` spans that overlap the
traced slice (node -> [tier in lanes, rows the program reported live]), summed
over the nodes of every run that overflowed no tier (an overflowing run is
retried at a larger tier and reports again), so never above 100.  A program
that writes no such attribute leaves nothing to read."""


def share(frames: list) -> float | None:
    """`frames`: one {node: [lanes, live]} per run."""
    lanes = live = 0
    for run in frames:
        if run and all(need <= cap for cap, need in run.values()):
            lanes += sum(cap for cap, _need in run.values())
            live += sum(need for _cap, need in run.values())
    return 100.0 * live / lanes if lanes else None


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    s0, s1 = t["slice"]
    return share([s["attrs"]["frames"] for s in ctx["spans"]
                  if s["name"] == "device_wait" and "frames" in s["attrs"]
                  and s["t1"] > s0 and s["t0"] < s1])
