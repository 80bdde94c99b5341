"""A served request's host path, cut into pieces that add up to it.

`tracered.breakdown` names a whole idle gap by the one span over its middle;
the span readers take one median over every template's requests.  This
reduction partitions instead of sampling: for every request of the window that
lies inside the traced slice, the client's interval [t0, t1] (the harness's
record) is cut so that the pieces sum to it exactly.  At each instant the
piece is

* the innermost span of that query id's `query` tree that is open (a span
  with children keeps its own time as `<name> (self)`; of two siblings that
  overlap — `compile` begins at the miss, inside `program_lookup` — the one
  that began first keeps the overlap), up to the terminal transition: from
  there the answer's way to the client is the handler thread's, whatever the
  query's thread still does;
* else the `http.post` / `http.get` root of that id — `http.get` cut into
  `(hold)` (waiting for the query), `(wake)` (terminal transition to the
  handler running again), `(self)`, `encode` (`json.dumps`) and `write`
  (headers and body onto the socket) by the span's own attributes;
* else `client`: connection, the handler thread's start, the client's decode —
  no program span can lie there;
* and inside `device_wait`, where the mapped device trace says the device was
  busy, `device`.

Beside each piece's wall time its share of the span's `cpu_ms`, the opening
thread's CPU clock (trino_tpu/utils/tracing.py): a span's own CPU time is its
`cpu_ms` less its children's, spread over its pieces by their length.  Wall
minus CPU is time a thread did not run.  `client` and `queued` have no CPU
clock, and on a host whose thread CPU clock is not fit to be read (the chip
tool's machines) no span has: the column then reads null.

What the CPU clock cannot say there, the spans of all threads on one clock
still can: `contended` is the part of a request's working pieces (not the
device's time, the wait for it, the hold, the wake-up or `queued`) during
which another thread of the process was at work too — in a `query` tree
outside its `device_wait`, a `finalize`, an `http.post`, an `http.get` after
its hold, or a client's own stretch.  One interpreter lock serves them all,
so that time was shared, not owned.

A program without the spans of PR 37 (an older checkout: no `commit` under any
`query`) leaves nothing to read: `report` returns None, prints nothing, and
every reader over it returns None.  Otherwise the first reader of a traced run
prints

    bench: host path by template (median ms a request, wall | cpu): {...}
    bench: device idle by span (s of the slice): {...}

the second being the slice's idle gaps cut at the pieces' boundaries, each
part going to the piece it lies under (shared equally where several requests
are open), where `breakdown.idle_gaps` gives a gap whole to one span.
"""

from __future__ import annotations

import bisect
import json
import statistics

from tracered import covered, union

CLIENT = "client"
DEVICE = "device"
NO_CALL = "no client call"
HOLD, WAKE = "http.get (hold)", "http.get (wake)"
ENCODE, WRITE = "http.get encode", "http.get write"
# cuts in which the request's thread waits and does not work
PASSIVE = ("device_wait", HOLD, WAKE, "queued")
UNNAMED = ("query (self)", "root_fragment (self)", "execute (self)",
           "http.post (self)", "http.get (self)")
IDLE_ROWS = 16


# ------------------------------------------------------------ one request


def _tree_cuts(tree: list) -> list:
    """[(a, b, label, cpu per second or None)] for one pre-order tree
    [(span, path of ancestor indices)]: at each instant the deepest open
    span, of equals the one that began first."""
    spans = [s for s, _up in tree]
    kids: dict = {}
    for i, (_s, up) in enumerate(tree):
        if up:
            kids.setdefault(up[-1], []).append(i)
    bounds = sorted({t for s in spans for t in (s["t0"], s["t1"])})
    rates: dict = {}

    def rate(i):
        """CPU seconds a second of span i's own time: its `cpu_ms` less its
        children's, over its length outside them (an interval a sibling
        keeps is still this span's own: its CPU ran there too)."""
        if i not in rates:
            s, cpu = spans[i], spans[i]["attrs"].get("cpu_ms")
            if cpu is not None:
                below = [spans[k] for k in kids.get(i, ())]
                own = s["t1"] - s["t0"] - covered(
                    union([(c["t0"], c["t1"]) for c in below]), s["t0"], s["t1"])
                cpu -= sum(c["attrs"].get("cpu_ms") or 0.0 for c in below)
                cpu = min(1.0, max(0.0, cpu) / 1e3 / own) if own > 0 else 0.0
            rates[i] = cpu
        return rates[i]

    out = []
    for a, b in zip(bounds, bounds[1:]):
        over = [i for i, s in enumerate(spans) if s["t0"] <= a and s["t1"] >= b]
        if over:
            i = max(over, key=lambda i: (spans[i]["depth"], -spans[i]["t0"]))
            out.append((a, b, spans[i]["name"] + (" (self)" if i in kids else ""), rate(i)))
    return out


def _get_cuts(get: dict, t_fin: float | None) -> list:
    """An `http.get` root by its own attributes; the CPU is the time after
    the hold's (a held thread does not run)."""
    at = get["attrs"]
    t_answer = min(get["t1"], get["t0"] + (at.get("held_ms") or 0.0) / 1e3)
    t_write = max(t_answer, get["t1"] - (at.get("write_ms") or 0.0) / 1e3)
    t_encode = max(t_answer, t_write - (at.get("encode_ms") or 0.0) / 1e3)
    t_wake = min(max(get["t0"], t_answer if t_fin is None else t_fin), t_answer)
    rate = still = None
    if at.get("cpu_ms") is not None and get["t1"] > t_answer:
        # the span's CPU clock runs from the handler's entry: the little it
        # used before the hold would read as more than a second a second
        rate, still = min(1.0, at["cpu_ms"] / 1e3 / (get["t1"] - t_answer)), 0.0
    return [(a, b, label, r) for a, b, label, r in (
        (get["t0"], t_wake, HOLD, still), (t_wake, t_answer, WAKE, still),
        (t_answer, t_encode, "http.get (self)", rate),
        (t_encode, t_write, ENCODE, rate), (t_write, get["t1"], WRITE, rate)) if b > a]


def _post_cuts(post: dict) -> list:
    cpu = post["attrs"].get("cpu_ms")
    if cpu is not None and post["t1"] > post["t0"]:
        cpu = min(1.0, cpu / 1e3 / (post["t1"] - post["t0"]))
    return [(post["t0"], post["t1"], "http.post (self)", cpu)]


def _fill(layers: list, t0: float, t1: float) -> list:
    """[t0, t1] cut by the first layer that covers each instant; `client`
    where none does.  A layer is a list of cuts that do not overlap."""
    bounds = sorted({t0, t1} | {t for layer in layers for a, b, _l, _c in layer
                                for t in (a, b) if t0 < t < t1})
    out = []
    for a, b in zip(bounds, bounds[1:]):
        for layer in layers:
            hit = next((c for c in layer if c[0] <= a and c[1] >= b), None)
            if hit is not None:
                out.append((a, b, hit[2], hit[3]))
                break
        else:
            out.append((a, b, CLIENT, None))
    return out


def timeline(rec: dict, tree: list, posts: list, gets: list) -> list:
    """The request's interval as cuts [(a, b, label, cpu per second)] that
    add up to it."""
    fins = [g["t0"] + (g["attrs"]["held_ms"] - g["attrs"]["since_finished_ms"]) / 1e3
            for g in gets if g["attrs"].get("since_finished_ms") is not None]
    t_fin = min(fins) if fins else None
    query = _tree_cuts(tree)
    if t_fin is not None:
        query = [(a, min(b, t_fin), label, c) for a, b, label, c in query if a < t_fin]
    http = [c for g in gets for c in _get_cuts(g, t_fin)]
    http += [c for p in posts for c in _post_cuts(p)]
    # the POST and the polls of one id follow one another; should two
    # overlap, the one that began first keeps the overlap
    flat, at = [], None
    for a, b, label, c in sorted(http, key=lambda c: c[:3]):
        a = a if at is None else max(a, at)
        if b > a:
            flat.append((a, b, label, c))
            at = b
    return _fill([query, flat], rec["t0"], rec["t1"])


def pieces(cuts: list, busy: list, starts: list) -> dict:
    """label -> [wall s, cpu s or None]; `device_wait` gives the part of it
    in which the device was busy to `device`."""
    out: dict = {}

    def add(label, wall, cpu):
        have = out.setdefault(label, [0.0, None])
        have[0] += wall
        if cpu is not None:
            have[1] = (have[1] or 0.0) + cpu

    for a, b, label, rate in cuts:
        wall = b - a
        if label == "device_wait":
            on = _covered(busy, starts, a, b)
            add(DEVICE, on, None if rate is None else rate * on)
            wall -= on
        add(label, wall, None if rate is None else rate * wall)
    return out


def _covered(busy: list, starts: list, a: float, b: float) -> float:
    """`tracered.covered` for the slice's thousands of busy intervals: found
    by bisection on their starts."""
    total = 0.0
    for x, y in busy[max(0, bisect.bisect_right(starts, a) - 1):]:
        if x >= b:
            break
        total += max(0.0, min(b, y) - max(a, x))
    return total


# ------------------------------------------------------------ the slice


def _trees_by_query(spans: list) -> tuple[dict, dict, dict]:
    """query id -> its `query` tree as [(span, ancestors' indices)], its
    `http.post` roots and its `http.get` roots."""
    trees, posts, gets = {}, {}, {}
    tree, path = None, []
    for s in spans:
        if s["depth"] == 0:
            tree = None
            qid = s["attrs"].get("query_id")
            if s["name"] == "query":
                tree = trees[qid] = []
            elif s["name"] in ("http.post", "http.get"):
                (posts if s["name"] == "http.post" else gets).setdefault(qid, []).append(s)
        if tree is not None:
            del path[s["depth"]:]
            tree.append((s, tuple(path)))
            path.append(len(tree) - 1)
    return trees, posts, gets


# attributes that time or count a part of a piece: (span, attribute)
INSIDE = (("to_rows", "fetch_ms"), ("to_rows", "d2h_arrays"), ("http.post", "admit_ms"))


def requests(ctx: dict) -> list:
    """[(record, cuts, {"<span>.<attribute>": value} of INSIDE)] for the
    window's requests that ran, left a `query` tree and touch the slice."""
    s0, s1 = ctx["trace"]["slice"]
    trees, posts, gets = _trees_by_query(ctx["spans"])
    out = []
    for r in ctx["records"]:
        qid = r.get("query_id")
        if r["error"] is None and qid in trees and r["t1"] > s0 and r["t0"] < s1:
            mine = [s for s, _up in trees[qid]] + posts.get(qid, [])
            inside = {f"{name}.{key}": s["attrs"][key] for name, key in INSIDE
                      for s in mine if s["name"] == name and key in s["attrs"]}
            out.append((r, timeline(r, trees[qid], posts.get(qid, []),
                                    gets.get(qid, [])), inside))
    return out


def company(spans: list, reqs: list):
    """-> shared(a, b): seconds of [a, b] in which two or more threads of the
    process were at work, as far as spans can tell: a `query` tree outside
    its `device_wait`s, a `finalize`, an `http.post`, an `http.get` after its
    hold, a request's `client` cuts."""
    events = []

    def work(a, b, sign=1):
        if b > a:
            events.extend(((a, sign), (b, -sign)))

    tree = False
    for s in spans:
        if s["depth"] == 0:
            tree = s["name"] == "query"
            if s["name"] == "http.get":
                work(min(s["t1"], s["t0"] + (s["attrs"].get("held_ms") or 0.0) / 1e3), s["t1"])
            elif s["name"] in ("query", "finalize", "http.post"):
                work(s["t0"], s["t1"])
        elif tree and s["name"] == "device_wait":
            work(s["t0"], s["t1"], -1)
    for _r, cuts, _inside in reqs:
        for a, b, label, _c in cuts:
            if label == CLIENT:
                work(a, b)
    events.sort()
    times, levels, shared_to = [], [], []  # shared_to[i]: shared seconds before times[i]
    level = 0
    for t, sign in events:
        shared_to.append(
            shared_to[-1] + (t - times[-1] if level >= 2 else 0.0) if times else 0.0)
        level += sign
        times.append(t)
        levels.append(level)

    def before(x):
        i = bisect.bisect_right(times, x) - 1
        if i < 0:
            return 0.0
        return shared_to[i] + (x - times[i] if levels[i] >= 2 else 0.0)

    return lambda a, b: before(b) - before(a)


def idle_by_span(reqs: list, busy: list, slice_: tuple) -> dict:
    """Seconds of the slice with the device idle, by `<template> / <piece>`:
    every gap cut at the pieces' boundaries."""
    s0, s1 = slice_
    gaps, at = [], s0
    for a, b in busy:
        if a > at:
            gaps.append((at, min(a, s1)))
        at = max(at, b)
    if at < s1:
        gaps.append((at, s1))
    cuts = sorted((a, b, f"{r['template']} / {label}")
                  for r, cs, _in in reqs for a, b, label, _c in cs)
    starts = [c[0] for c in cuts]
    longest = max((b - a for a, b, _l in cuts), default=0.0)
    idle: dict = {}
    for ga, gb in gaps:
        lo = bisect.bisect_left(starts, ga - longest)
        near = [c for c in cuts[lo:bisect.bisect_left(starts, gb)] if c[1] > ga]
        bounds = sorted({ga, gb} | {t for a, b, _l in near for t in (a, b) if ga < t < gb})
        for x, y in zip(bounds, bounds[1:]):
            over = [label for a, b, label in near if a <= x and b >= y] or [NO_CALL]
            for label in over:
                idle[label] = idle.get(label, 0.0) + (y - x) / len(over)
    return idle


_last: tuple = ()  # (spans, records, trace, report): the run's one reduction


def report(ctx: dict) -> dict | None:
    """{"requests": [(record, {label: [wall s, cpu s]}, INSIDE's values and
    `contended_ms`)] inside the slice, "idle": {label: s}}; computed and
    printed once a run."""
    global _last
    if _last and all(x is y for x, y in zip(_last, (ctx["spans"], ctx["records"], ctx["trace"]))):
        return _last[3]
    out = None
    if ctx["trace"] is not None and any(s["name"] == "commit" for s in ctx["spans"]):
        busy = ctx["trace"]["busy"]
        starts = [a for a, _b in busy]
        s0, s1 = ctx["trace"]["slice"]
        reqs = requests(ctx)
        shared = company(ctx["spans"], reqs)
        inside = [
            (r, pieces(cuts, busy, starts), dict(extra, contended_ms=1e3 * sum(
                shared(a, b) for a, b, label, _c in cuts if label not in PASSIVE)))
            for r, cuts, extra in reqs if r["t0"] >= s0 and r["t1"] <= s1]
        if inside:
            out = {"requests": inside, "idle": idle_by_span(reqs, busy, (s0, s1))}
            print("bench: host path by template (median ms a request, wall | cpu): "
                  + json.dumps(table(out)), flush=True)
            top = sorted(out["idle"].items(), key=lambda kv: -kv[1])[:IDLE_ROWS]
            print("bench: device idle by span (s of the slice): "
                  + json.dumps({k: round(v, 4) for k, v in top}), flush=True)
    _last = (ctx["spans"], ctx["records"], ctx["trace"], out)
    return out


def table(rep: dict) -> dict:
    """template -> {"n", "client_ms", "pieces": {label: [wall ms, cpu ms]}
    by wall, "inside": medians of INSIDE's attributes and `contended_ms`}."""
    by_t: dict = {}
    for row in rep["requests"]:
        by_t.setdefault(row[0]["template"], []).append(row)
    out = {}
    for t, rows in sorted(by_t.items()):
        med = {}
        for label in {label for _r, p, _in in rows for label in p}:
            wall = statistics.median(p.get(label, [0.0])[0] for _r, p, _in in rows)
            cpus = [p[label][1] for _r, p, _in in rows
                    if label in p and p[label][1] is not None]
            med[label] = [round(wall * 1e3, 3),
                          round(statistics.median(cpus) * 1e3, 3) if cpus else None]
        keys = sorted({k for _r, _p, extra in rows for k in extra})
        out[t] = {
            "n": len(rows),
            "client_ms": round(statistics.median(
                (r["t1"] - r["t0"]) * 1e3 for r, _p, _in in rows), 3),
            "pieces": dict(sorted(med.items(), key=lambda kv: -kv[1][0])),
            "inside": {k: round(statistics.median(
                extra[k] for _r, _p, extra in rows if k in extra), 3) for k in keys},
        }
    return out


# ------------------------------------------------------ what readers share


def per_template(ctx: dict, value) -> float | None:
    """Mean over the cell's templates of the per-template median of
    `value(record, pieces, inside)`: one number a request, each template
    weighing the same, so a bimodal mix reads as neither mode's midpoint.
    None without the spans."""
    rep = report(ctx)
    if rep is None:
        return None
    by_t: dict = {}
    for r, p, inside in rep["requests"]:
        by_t.setdefault(r["template"], []).append(value(r, p, inside))
    return statistics.fmean(statistics.median(v) for v in by_t.values())


def wall_ms(ctx: dict, *labels: str) -> float | None:
    """The pieces called `labels`, summed a request, in ms."""
    return per_template(
        ctx, lambda _r, p, _in: sum(p[k][0] for k in labels if k in p) * 1e3)
