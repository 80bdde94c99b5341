"""client protocol: how long a finished answer lay in the coordinator before
the client's poll took it — `since_finished_ms` of the `http.get` span that
carried the data (`served`).  The client sleeps 50 ms between polls
(client/client.py), so a uniform finish lands ~25 ms before the next poll;
median over the window's requests whose serving poll lies inside the slice."""

from spanred import median, trees


def read(ctx):
    ids = {r.get("query_id") for r in ctx["records"]} - {None}
    return median([
        root["attrs"]["since_finished_ms"] for root, _below in trees(ctx)
        if root["name"] == "http.get" and root["attrs"].get("served")
        and root["attrs"].get("query_id") in ids
        and root["attrs"].get("since_finished_ms") is not None
    ])
