"""client protocol (client/client.py, the HTTP handlers): the client's
latency minus the coordinator's `query` span of the same query id, median
over the window.  Host spans only; needs no device trace."""

import statistics

from tracered import query_spans


def read(ctx):
    spans = query_spans(ctx)
    gaps = [
        (r["t1"] - r["t0"] - (spans[r["query_id"]]["t1"] - spans[r["query_id"]]["t0"])) * 1e3
        for r in ctx["records"]
        if r["error"] is None and r.get("query_id") in spans
    ]
    return statistics.median(gaps) if gaps else None
