"""exchange (parallel/exchange.py under exec/spmd.py): per query, the time a
chip spent in collective ops — HLO names that begin all-to-all, all-gather,
all-reduce or collective-permute, -start and -done included — mean over the
chips, from the profiler's trace (meshred.py)."""

import meshred


def read(ctx):
    ns = meshred.collective_ns(ctx)
    n = meshred.queries(ctx) if ns is not None else 0
    return ns / 1e6 / n if n else None
