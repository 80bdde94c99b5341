"""kernels (ops/pallas/fused.py `run`): of the time the device was busy in
the trace, the share spent preparing the fused scan kernel's input — the
casts to f64, the hi/lo f32 splits and the pads, which the program puts
under `jax.named_scope("fused_scan_prep")`; the scope reaches
the trace as part of an op's `tf_op` stat.  ROADMAP S5's number: work that
reads every column and writes every plane before the kernel reads them.

The wire-format walk is device_attributed_share.py's; this reader loads a
copy of that module of its own and gives it another `label` (prep or not),
so the accepted reader is left as it is.  None where no op carries the
scope (a program without it, or a compiler that kept no metadata on the
fusions it made): the line then leaves the metric out."""

import loader
from tracered import union

SCOPE = "fused_scan_prep"


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.get("path"):
        return None
    walk = loader.load_module("layer_metrics", "device_attributed_share")
    walk.label = lambda hlo, tf_op: SCOPE if SCOPE in tf_op else None
    prep = busy = 0.0
    for _plane, events in walk.device_ops(t["path"]):
        prep += sum(b - a for a, b in union([(a, b) for w, a, b in events if w]))
        busy += sum(b - a for a, b in union([(a, b) for _w, a, b in events]))
    return 100.0 * prep / busy if prep and busy else None
