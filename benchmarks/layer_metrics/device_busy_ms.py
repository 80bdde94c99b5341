"""device programs (ops/relops.py, ops/pallas/*): device-busy time in the
traced slice — the union of the device-op intervals of the profiler's trace —
per query, a query counting by the part of it that lies inside the slice."""

from tracered import share_in_slice


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    queries = sum(share_in_slice(r, t) for r in ctx["records"] if r["error"] is None)
    return t["busy_s"] * 1e3 / queries if queries else None
