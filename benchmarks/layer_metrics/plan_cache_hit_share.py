"""prepared fast path (runtime/fastpath.py): of the requests inside the traced
slice, the share whose `planner` span says `plan_cache` `hit` — the template
parsed once, the plan found under (statement, binding types), nothing
planned.  A `miss` plans anew, a `bypass` plans and keeps nothing; both count
against it, as does a request with no such span (the legacy
substitute-and-replan path).  It reads what
`trino_tpu_plan_cache_events_total` counts.  None where no `planner` span
says how its plan was come by (a program without the attribute)."""

from spanred import named, queries


def read(ctx):
    found = [[s["attrs"]["plan_cache"] for s in named(below, "planner")
              if "plan_cache" in s["attrs"]] for _q, below in queries(ctx)]
    if not any(found):
        return None
    return 100.0 * sum(1 for f in found if f and all(c == "hit" for c in f)) / len(found)
