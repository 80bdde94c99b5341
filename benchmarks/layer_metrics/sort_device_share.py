"""device programs (ops/relops.py): of the time the device was busy in the
traced slice, the share spent in ops whose HLO opcode is `sort` — the sort
joins' build and probe orders, the grouped aggregation's sort, the compaction
sorts and the top-n's candidates: what a radix partition (ROADMAP S1) is to
take away.  Told by the opcode, as collective_ms.py tells collectives
(meshred.py: the trace names an op by its whole HLO line, and run.py's
`trace["ops"]` keeps only its head, which a sort of several operands fills
with its result tuple), so the .xplane.pb is loaded again; mean over the
chips.  A trace without a sort leaves nothing to read."""

import re

import meshred
from tracered import union

SORT = re.compile(r"%?sort[.\d]*$")


def is_sort(hlo_line: str) -> bool:
    """By the opcode; by the instruction's name where the line is no more."""
    name, _, rest = hlo_line.partition(" = ")
    opcode = meshred.OPCODE.search(rest)
    if opcode is not None:
        return opcode.group(1) == "sort"
    return SORT.match(name.strip()) is not None


def share(devices: dict, s0: float, s1: float) -> float | None:
    """`devices`: plane -> [(HLO line, start_ns, dur_ns)], clipped here to
    [s0, s1]; sorts' time over the busy time, each a union of intervals."""
    sorts = busy = 0.0
    for _plane, events in sorted(devices.items()):
        mine, every = [], []
        for name, start, dur in events:
            a, b = max(start, s0), min(start + dur, s1)
            if b > a:
                every.append((a, b))
                if is_sort(name):
                    mine.append((a, b))
        sorts += sum(b - a for a, b in union(mine))
        busy += sum(b - a for a, b in union(every))
    return 100.0 * sorts / busy if sorts else None


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.get("path"):
        return None
    window = meshred.slice_ns(t)
    if window is None:
        return None
    return share(meshred.loaded(t["path"])["devices"], *window)
