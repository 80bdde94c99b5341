"""From the trace of a run over several chips to numbers: what the readers
of the exchange layer share (layer_metrics/collective_ms.py,
collective_exposed_ms.py, exchange_device_share.py, ici_roofline_share.py).

run.py hands a reader `ctx["trace"]`, whose `ops` are summed over the chips
and whose `busy` is the first chip's alone; these readers want each chip by
itself, so they load the .xplane.pb again (`tracered.load`) and clip it to
the traced slice themselves.  The slice in trace time: it opens as the last
`bench_sync` annotation closes (tracered.profile_slice takes its clock
reading right after writing them) and lasts `window_s`.

A collective is an op of the device's op line whose HLO opcode is
`all-to-all`, `all-gather`, `all-reduce` or `collective-permute` (`-start`
and `-done` halves included).  The trace names an op by its whole HLO line,
`%all_to_all.49 = u32[4,1,65536]{...} all-to-all(...)`: the opcode stands
after the result's shape, and the instruction's own name does not decide —
JAX names the reshape behind an all_to_all `%all_to_all.50` too, and the
TPU compiler makes `%all-reduce`s of dynamic-update-slices out of the larger
all-gathers.  Those of an Exchange node carry its `Exchange#<id>` scope,
except the compiler's all-reduces, which carry none; the one-scalar
all-gathers by which the chips agree on an overflow counter
(parallel/exchange.py pmax_count) reach the chip as a few small all-reduces
without a scope.  A program without exchanges, or a trace of one chip,
leaves nothing to read: every reader then returns None.
"""

from __future__ import annotations

import functools
import re

import tracered
from tracered import covered, share_in_slice, union

KINDS = r"(all-to-all|all-gather|all-reduce|collective-permute)(-start|-done)?"
OPCODE = re.compile(r"[\])}] ([a-z][a-z0-9-]*)\(")  # the first `<shape> <opcode>(`
NAMED = re.compile("%?" + KINDS)


def is_collective(hlo_line: str) -> bool:
    """By the opcode; by the instruction's name where the line holds no
    more than that."""
    name, _, rest = hlo_line.partition(" = ")
    opcode = OPCODE.search(rest)
    if opcode is not None:
        return re.fullmatch(KINDS, opcode.group(1)) is not None
    return NAMED.match(name.replace("_", "-")) is not None


@functools.lru_cache(maxsize=2)
def loaded(path: str) -> dict:
    return tracered.load(path)


def slice_ns(trace: dict) -> tuple[float, float] | None:
    """The traced slice in the trace's own nanoseconds."""
    syncs = [start + dur for name, start, dur in loaded(trace["path"])["host"]
             if name == tracered.SYNC]
    if not syncs:
        return None
    return max(syncs), max(syncs) + trace["window_s"] * 1e9


def chips(ctx: dict) -> list | None:
    """Per chip of the trace: (merged intervals of its collectives, merged
    intervals of every other op), clipped to the slice, in nanoseconds; None
    without a trace, or where no chip ran a collective."""
    t = ctx["trace"]
    if t is None or not t.get("path"):
        return None
    window = slice_ns(t)
    if window is None:
        return None
    s0, s1 = window
    out = []
    for _plane, events in sorted(loaded(t["path"])["devices"].items()):
        mine, others = [], []
        for name, start, dur in events:
            a, b = max(start, s0), min(start + dur, s1)
            if b > a:
                (mine if is_collective(name) else others).append((a, b))
        out.append((union(mine), union(others)))
    return out if any(mine for mine, _others in out) else None


def queries(ctx: dict) -> float:
    """The window's requests, each by the part of it inside the slice."""
    return sum(share_in_slice(r, ctx["trace"]) for r in ctx["records"]
               if r["error"] is None)


def collective_ns(ctx: dict, exposed: bool = False) -> float | None:
    """Nanoseconds a chip spent in collectives inside the slice, mean over
    the chips; `exposed`: only while no other op ran on that chip."""
    per_chip = chips(ctx)
    if per_chip is None:
        return None
    total = 0.0
    for mine, others in per_chip:
        for a, b in mine:
            total += (b - a) - (covered(others, a, b) if exposed else 0.0)
    return total / len(per_chip)


def dispatches(ctx: dict) -> list:
    """The `dispatch` spans that began inside the slice and say what their
    program moves (`exchange_bytes`, `devices`; exec/spmd.py)."""
    s0, s1 = ctx["trace"]["slice"]
    return [s for s in ctx["spans"]
            if s["name"] == "dispatch" and "exchange_bytes" in s["attrs"]
            and s0 <= s["t0"] < s1]
