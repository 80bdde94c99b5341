"""exchange: of the time the chips were busy in the trace, the share in ops
that carry an Exchange node's scope innermost (`Exchange#<id>`,
exec/compiler.py `_trace_plan`): the bucket sort, the scatter into the
[D, B] send buffers, the collective and the flattening after it.  Summed
over the chips; the wire format is read by device_attributed_share.py's
reader (an op's scope is a stat of its metadata)."""

import re

import loader

EXCHANGE = re.compile(r"(^|:)Exchange#\d+(/|$)")


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.get("path"):
        return None
    per, _told, busy = loader.load_module(
        "layer_metrics", "device_attributed_share").by_operator(t["path"])
    mine = sum(s for what, s in per.items() if EXCHANGE.search(what))
    return 100.0 * mine / busy if busy and mine else None
