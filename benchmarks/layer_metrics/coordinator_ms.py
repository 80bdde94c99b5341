"""coordinator (runtime/coordinator.py: plan, schedule, collect, and the root
fragment it executes itself): per query, the `query` span minus its worker
`task` spans, less the time the device was busy in what remains; median over
the queries inside the traced slice."""

import statistics

from tracered import covered, query_spans


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    s0, s1 = t["slice"]

    def host(s):
        return s["t1"] - s["t0"] - covered(t["busy"], s["t0"], s["t1"])

    tasks: dict = {}
    for s in ctx["spans"]:
        if s["name"] == "task":
            tasks.setdefault(s["attrs"].get("query_id"), []).append(s)
    values = [
        (host(q) - sum(host(k) for k in tasks.get(qid, []))) * 1e3
        for qid, q in query_spans(ctx).items() if q["t0"] >= s0 and q["t1"] <= s1
    ]
    return statistics.median(values) if values else None
