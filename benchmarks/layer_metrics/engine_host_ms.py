"""single-process engine (runtime/engine.py, runtime/fastpath.py,
exec/compiler.py dispatch): the engine's interval — the coordinator's `query`
span where the request has one (the prepared fast path), else the request
itself (the library entry) — minus the time the device was busy inside it;
median over the requests inside the traced slice."""

from tracered import host_ms, query_spans


def read(ctx):
    if ctx["trace"] is None:
        return None
    spans = query_spans(ctx)
    intervals = []
    for r in ctx["records"]:
        if r["error"] is None:
            s = spans.get(r.get("query_id"))
            intervals.append((s["t0"], s["t1"]) if s else (r["t0"], r["t1"]))
    return host_ms(intervals, ctx["trace"])
