"""coordinator: what a query costs after its rows exist — `query_info`
(QueryInfo, operator stats, the anomaly sentinel; inside `query`) plus
`finalize` (metrics, journal, events, history, flight recorder, post-mortem;
after `query`, while the client may already hold the answer).  It is process
time the next query waits for, so it moves throughput; median over the
queries inside the traced slice."""

from spanred import median, ms, named, queries, roots_by_query


def read(ctx):
    final = roots_by_query(ctx, "finalize")
    return median([
        sum(ms(s) for s in named(below, "query_info")
            + final.get(q["attrs"]["query_id"], []))
        for q, below in queries(ctx)
    ]) if final else None
