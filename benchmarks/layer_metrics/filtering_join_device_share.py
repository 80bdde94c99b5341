"""device programs (ops/relops.py `equi_join`): of the time the device was
busy in the traced slice, the share spent in the ops of joins that FILTER or
mark their left page — semi, anti, the null-aware anti of NOT IN, mark —
which is what the planner makes of IN, NOT IN, EXISTS and NOT EXISTS.  Such
a join takes the whole expansion path today (the build side's sort, the
merged rank, a frame of every hash match, the key verification, the residual
over the gathered frame and a scatter-max back to the probe rows): this is
what a filtering join that needs no expansion is to take away.  Read it
beside `device_busy_ms` and `join_device_share`, never alone.

An op's innermost plan-node scope says which join it belongs to (`Join#6`:
the `tf_op` stat of its metadata, by device_attributed_share.py's wire-format
walk through a copy of that module of its own with another `label`, as
join_device_share.py does), and the request it ran in says what kind that
join is: the request's `planner` span carries `join_kinds` (`Join#6` ->
`anti+residual`, runtime/engine.py).  Node ids are a plan's own, so an op is
resolved by the request its midpoint falls in (where streams overlap: only if
every open request gives that id the same kind).  The kind is the program's
word, not a name in the compiled code, so a compile cache shared with a tree
that lacks this PR's spans changes nothing.  The walk's events and
`tracered.load`'s are the same op line in the same order: zipped, they give a
scope its time on the trace's clock, which the slice's start maps onto the
spans' perf_counter.  Prints the seconds by kind and by statement and join.
None where no request in the trace says `join_kinds` (a program without the
attribute) or no op belongs to a filtering join."""

import json

import loader
import meshred
from tracered import union

FILTERING = ("semi", "anti", "null_anti", "mark", "mark_in")


def shares(planes: list, requests: list) -> tuple[float | None, dict]:
    """`planes`: [[(`Join#<id>` or None, start, end)]] a device plane;
    `requests`: [(start, end, template, {`Join#<id>`: kind})] on the same
    clock -> (the share in percent, {"<template> <join> <kind>": time});
    each a union of intervals, summed over the planes."""
    mine = busy = 0.0
    by_join: dict = {}

    def length(intervals) -> float:
        return sum(b - a for a, b in union(intervals))

    def resolve(join: str, a: float, b: float):
        mid = (a + b) / 2.0
        found = [(template, kinds.get(join)) for s, e, template, kinds in requests
                 if s <= mid <= e]
        if len({kind for _t, kind in found}) != 1:
            return None  # no open request, or two that disagree
        template, kind = found[0]
        if kind is None or kind.split("+")[0] not in FILTERING:
            return None
        return f"{template} {join} {kind}"

    for events in planes:
        told: dict = {}
        for join, a, b in events:
            what = resolve(join, a, b) if join else None
            if what:
                told.setdefault(what, []).append((a, b))
        mine += length([s for spans in told.values() for s in spans])
        busy += length([(a, b) for _j, a, b in events])
        for what, spans in told.items():
            by_join[what] = by_join.get(what, 0.0) + length(spans)
    return (100.0 * mine / busy if mine and busy else None), by_join


def requests_of(ctx: dict, offset_s: float) -> list:
    """The window's requests with the `join_kinds` their planner span said,
    on the trace's clock in nanoseconds."""
    planners = [s for s in ctx["spans"]
                if s["name"] == "planner" and "join_kinds" in s["attrs"]]
    out = []
    for r in ctx["records"]:
        kinds = next((s["attrs"]["join_kinds"] for s in planners
                      if r["t0"] <= s["t0"] <= r["t1"]), None)
        if kinds is not None and r["error"] is None:
            out.append(((r["t0"] + offset_s) * 1e9, (r["t1"] + offset_s) * 1e9,
                        r["template"], kinds))
    return out


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.get("path"):
        return None
    window = meshred.slice_ns(t)
    if window is None:
        return None
    requests = requests_of(ctx, window[0] / 1e9 - t["slice"][0])
    if not requests:
        return None
    walk = loader.load_module("layer_metrics", "device_attributed_share")
    scope = walk.SCOPE

    def innermost_join(_hlo: str, tf_op: str):
        scopes = scope.findall(tf_op)
        return scopes[-1] if scopes and scopes[-1].startswith("Join#") else None

    walk.label = innermost_join
    timed = meshred.loaded(t["path"])["devices"]
    planes = []
    for plane, events in walk.device_ops(t["path"]):
        clock = timed.get(plane, [])
        if len(clock) != len(events):  # not the same op line: nothing to zip
            return None
        planes.append([(join, start, start + dur)
                       for (join, _a, _b), (_name, start, dur) in zip(events, clock)])
    share, by_join = shares(planes, requests)
    if share is not None:
        by_kind: dict = {}
        for what, ns in by_join.items():
            kind = what.rsplit(" ", 1)[1]
            by_kind[kind] = by_kind.get(kind, 0.0) + ns
        print("bench: filtering joins' device seconds by kind: " + json.dumps(
            {k: round(ns / 1e9, 6) for k, ns in sorted(by_kind.items())})
            + "; by statement and join: " + json.dumps(
            {k: round(ns / 1e9, 6) for k, ns in sorted(by_join.items())}), flush=True)
    return share
