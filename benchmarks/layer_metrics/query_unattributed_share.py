"""coordinator: the share of a `query` span that none of its child spans
covers (its self time over its duration) — what the breakdown still cannot
name; median over the queries inside the traced slice.  None where `query`
has no children (a program without the spans)."""

from spanred import median, queries, self_s


def read(ctx):
    values = []
    for q, below in queries(ctx):
        children = [s for s in below if s["depth"] == 1]
        if children and q["t1"] > q["t0"]:
            values.append(100.0 * self_s(q, children) / (q["t1"] - q["t0"]))
    return median(values)
