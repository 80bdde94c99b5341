"""exchange: per query, the part of `collective_ms` during which no other op
ran on that chip — the collective time the program waits for, not hides;
mean over the chips, from the profiler's trace (meshred.py)."""

import meshred


def read(ctx):
    ns = meshred.collective_ns(ctx, exposed=True)
    n = meshred.queries(ctx) if ns is not None else 0
    return ns / 1e6 / n if n else None
