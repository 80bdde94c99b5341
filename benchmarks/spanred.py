"""From the program's spans to numbers: what the span readers share.

run.py's `flatten` hands every reader the exporter's root spans in pre-order
(`name`, `t0`, `t1`, `attrs`, `depth`; time.perf_counter, the clock the traced
slice is mapped onto): a root and the deeper spans that follow it are one
tree.  A served request leaves four kinds of tree — `http.post` and `http.get`
on handler threads, `query` on the query's own thread, `finalize` after it —
tied by `attrs["query_id"]`; the library entry leaves `planner` and `execute`.
A program without these spans (an older checkout) leaves nothing to read:
every reader then returns None and the line leaves the metric out.
"""

from __future__ import annotations

import statistics

from tracered import covered, union


def trees(ctx: dict) -> list:
    """[(root, [its descendants])] for the roots that lie inside the traced
    slice; [] without a trace."""
    t = ctx["trace"]
    if t is None:
        return []
    s0, s1 = t["slice"]
    out: list = []
    keep = False
    for s in ctx["spans"]:
        if s["depth"] == 0:
            keep = s["t0"] >= s0 and s["t1"] <= s1
            if keep:
                out.append((s, []))
        elif keep:
            out[-1][1].append(s)
    return out


def ms(span: dict) -> float:
    return (span["t1"] - span["t0"]) * 1e3


def named(spans: list, *names: str) -> list:
    return [s for s in spans if s["name"] in names]


def median(values: list) -> float | None:
    return statistics.median(values) if values else None


def per_tree(ctx: dict, name: str, value=ms) -> float | None:
    """Median over the trees that hold spans called `name` of the sum of
    `value` over a tree's such spans: one number per request."""
    found = [named([root] + below, name) for root, below in trees(ctx)]
    return median([sum(value(s) for s in spans) for spans in found if spans])


def queries(ctx: dict) -> list:
    """The `query` trees inside the slice that belong to the window's
    requests (the settling passes' and the warm-up's do not)."""
    ids = {r.get("query_id") for r in ctx["records"]} - {None}
    return [(root, below) for root, below in trees(ctx)
            if root["name"] == "query" and root["attrs"].get("query_id") in ids]


def roots_by_query(ctx: dict, name: str) -> dict:
    """query id -> the root spans called `name` that carry it, in any part
    of the run (a poll may end after the slice does)."""
    out: dict = {}
    for s in ctx["spans"]:
        if s["depth"] == 0 and s["name"] == name:
            out.setdefault(s["attrs"].get("query_id"), []).append(s)
    return out


def self_s(span: dict, children: list) -> float:
    """Seconds of a span that none of the given children covers."""
    return span["t1"] - span["t0"] - covered(
        union([(c["t0"], c["t1"]) for c in children]), span["t0"], span["t1"])


def host_s(span: dict, minus: list, busy: list) -> float:
    """Seconds of a span outside the `minus` spans in which the device was
    not busy either: what the host spent there."""
    cuts = union([(c["t0"], c["t1"]) for c in minus])
    at, left = span["t0"], []
    for a, b in cuts:
        if a > at:
            left.append((at, min(a, span["t1"])))
        at = max(at, b)
    if at < span["t1"]:
        left.append((at, span["t1"]))
    return sum(b - a - covered(busy, a, b) for a, b in left)
