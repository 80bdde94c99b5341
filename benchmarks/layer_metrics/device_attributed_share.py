"""device programs (ops/relops.py, ops/pallas/*): of the time the device was
busy in the trace, the share spent in operations that say where they come
from — a plan node's scope (`Aggregate#3`, exec/compiler.py `_trace_plan`;
it reaches the trace as the `tf_op` stat of the op's metadata, e.g.
`jit(call)/Project#0/Aggregate#1/sub`) or a Pallas kernel's name (the HLO
instruction is then called `%fused_scan.1`, not `%call.1`).  Also prints,
before the result line, the device's time by operator, and which programs
were built while the slice ran and why (the `compile` spans).

jax.profiler.ProfileData hands out an event's own stats but not those of its
metadata, where `tf_op` lives, so this file reads the .xplane.pb's wire
format itself (tsl/profiler/protobuf/xplane.proto; field numbers below)."""

import json
import re

from tracered import OP_LINES, union

SCOPE = re.compile(r"\b([A-Z][A-Za-z]*#\d+)\b")
KERNEL = re.compile(r"^%?(fused_scan|hash_agg|hash_join_probe|seg_reduce)\b")


def fields(buf) -> list:
    """One protobuf message -> [(field number, value)]: varints as int,
    length-delimited fields as memoryview, fixed fields skipped over."""
    out, i, n = [], 0, len(buf)

    def varint() -> int:
        nonlocal i
        v = shift = 0
        while True:
            b = buf[i]
            i += 1
            v |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return v

    while i < n:
        key = varint()
        no, wire = key >> 3, key & 7
        if wire == 0:
            out.append((no, varint()))
        elif wire == 2:
            ln = varint()
            out.append((no, buf[i:i + ln]))
            i += ln
        else:
            i += 8 if wire == 1 else 4
    return out


def text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def label(hlo: str, tf_op: str) -> str | None:
    """`<the fragment's root node>:<the innermost node>` of an op's scope
    path (node ids are per plan, the root tells the statements of a mix
    apart), then the kernel's name where the instruction is a named Pallas
    call; None when it carries neither."""
    scopes = SCOPE.findall(tf_op)
    kernel = KERNEL.match(hlo)
    node = [":".join(dict.fromkeys((scopes[0], scopes[-1])))] if scopes else []
    return "/".join(node + ([kernel.group(1)] if kernel else [])) or None


def device_ops(path: str) -> list:
    """[(plane name, [(label or None, start_ps, end_ps)])] for the op line of
    every device plane.  XSpace.planes=1; XPlane: name=2 lines=3
    event_metadata=4 stat_metadata=5; XLine: name=2 events=4; XEvent:
    metadata_id=1 offset_ps=2 duration_ps=3; XEventMetadata: id=1 name=2
    stats=5; XStatMetadata: id=1 name=2; XStat: metadata_id=1 str_value=5
    ref_value=7; a map entry: key=1 value=2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for no, plane in fields(space):
        if no != 1:
            continue
        pf = fields(plane)
        name = next((text(v) for k, v in pf if k == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for k, v in pf:
            if k == 5:
                meta = dict(fields(dict(fields(v))[2]))
                stat_names[meta.get(1, 0)] = text(meta.get(2, b""))
        labels = {}
        for k, v in pf:
            if k == 4:
                mf = fields(dict(fields(v))[2])
                hlo = next((text(x) for j, x in mf if j == 2), "")
                tf_op = ""
                for j, x in mf:
                    if j == 5:
                        st = dict(fields(x))
                        if stat_names.get(st.get(1)) == "tf_op":
                            tf_op = (text(st[5]) if 5 in st
                                     else stat_names.get(st.get(7), ""))
                labels[next((x for j, x in mf if j == 1), 0)] = label(hlo, tf_op)
        lines = {}
        for k, v in pf:
            if k == 3:
                lf = fields(v)
                lines[next((text(x) for j, x in lf if j == 2), "")] = lf
        events = []
        for want in OP_LINES:
            for j, x in lines.get(want, ()):
                if j == 4:
                    e = dict(fields(x))
                    start = e.get(2, 0)
                    events.append((labels.get(e.get(1, 0)), start, start + e.get(3, 0)))
            if events:
                break
        out.append((name, events))
    return out


def by_operator(path: str) -> tuple[dict, float, float]:
    """-> ({label: seconds}, seconds in labelled ops, seconds busy), each the
    length of a union of intervals, summed over the device planes."""
    per: dict = {}
    told = busy = 0.0

    def seconds(intervals):
        return sum(b - a for a, b in union(intervals)) / 1e12

    for _plane, events in device_ops(path):
        mine: dict = {}
        for what, a, b in events:
            if what is not None:
                mine.setdefault(what, []).append((a, b))
        for what, spans in mine.items():
            per[what] = per.get(what, 0.0) + seconds(spans)
        told += seconds([s for spans in mine.values() for s in spans])
        busy += seconds([(a, b) for _w, a, b in events])
    return per, told, busy


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.get("path"):
        return None
    s0, s1 = t["slice"]
    for s in ctx["spans"]:
        if s["name"] == "compile" and s["attrs"].get("cause") != "joined" \
                and s0 <= s["t0"] <= s1:
            was_open = sorted({r["template"] for r in ctx["records"]
                               if r["t0"] <= s["t0"] <= r["t1"]})
            print(f"bench: a program was built in the slice: {s['attrs']} "
                  f"while {was_open} were open", flush=True)
    per, told, busy = by_operator(t["path"])
    top = dict(sorted(per.items(), key=lambda kv: -kv[1])[:24])
    print("bench: device time by operator: " + json.dumps(top), flush=True)
    return 100.0 * told / busy if busy else None
