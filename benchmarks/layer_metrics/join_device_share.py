"""device programs (ops/relops.py `equi_join`): of the time the device was
busy in the traced slice, the share spent in ops whose innermost plan-node
scope is a `Join#<id>` — the build side's sort, the probe's searches, the
expansion's gathers, the key verification; the scans, compactions and
aggregations around the joins are other nodes' scopes.  What a better join
order, a cheaper build side or a partitioned join is to take away; read it
beside `device_busy_ms`, never alone.

The scope reaches the trace as the `tf_op` stat of an op's metadata
(device_attributed_share.py, whose wire-format walk this reader uses through
a copy of that module of its own with another `label`, so the accepted
reader is left as it is).  None where no op carries such a scope."""

import loader
from tracered import union


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.get("path"):
        return None
    walk = loader.load_module("layer_metrics", "device_attributed_share")
    scope = walk.SCOPE

    def innermost_is_join(_hlo: str, tf_op: str):
        scopes = scope.findall(tf_op)
        return "join" if scopes and scopes[-1].startswith("Join#") else None

    walk.label = innermost_is_join
    joins = busy = 0.0
    for _plane, events in walk.device_ops(t["path"]):
        joins += sum(b - a for a, b in union([(a, b) for w, a, b in events if w]))
        busy += sum(b - a for a, b in union([(a, b) for _w, a, b in events]))
    return 100.0 * joins / busy if joins and busy else None
