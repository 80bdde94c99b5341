"""planner (sql/, plan/): a request's `planner` spans — parse, plan, optimize
and, in the coordinator, distribute and fragment (runtime/coordinator.py
`_run_once`, runtime/engine.py `execute_page`); median over the requests
inside the traced slice."""

from spanred import per_tree


def read(ctx):
    return per_tree(ctx, "planner")
