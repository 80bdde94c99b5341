"""coordinator root fragment (runtime/coordinator.py `_run_once`: the result
fragment the coordinator executes itself, with a new LocalExecutor): the
`root_fragment` span outside its `scan_load` spans, less the time the device
was busy there — capacities, cache keys, the compile service's lookup,
dispatch, the wait's host side, operator stats; median over the queries
inside the traced slice."""

from spanred import host_s, median, named, queries


def read(ctx):
    values = []
    for _q, below in queries(ctx):
        for rf in named(below, "root_fragment"):
            inside = [s for s in named(below, "scan_load")
                      if s["t0"] >= rf["t0"] and s["t1"] <= rf["t1"]]
            values.append(host_s(rf, inside, ctx["trace"]["busy"]) * 1e3)
    return median(values)
