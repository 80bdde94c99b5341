"""client protocol: from the POST's first byte read to the moment the query's
own thread opens its `query` span — body read, load shedding, admission
through the resource group, the thread's start.  `query`.t0 minus
`http.post`.t0 of the same query id; median over the queries inside the
traced slice."""

from spanred import median, queries, roots_by_query


def read(ctx):
    posts = roots_by_query(ctx, "http.post")
    return median([
        (q["t0"] - posts[q["attrs"]["query_id"]][0]["t0"]) * 1e3
        for q, _below in queries(ctx) if q["attrs"]["query_id"] in posts
    ])
