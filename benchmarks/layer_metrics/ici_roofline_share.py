"""exchange: the least time the chip-to-chip links could take for what the
slice's exchanges have to move, over the time its collectives took.  What
has to move: the `exchange_bytes` of the `dispatch` spans that began inside
the slice — the lanes a chip hands to its exchanges, before any padding of
the [D, B] send buffers (parallel/exchange.py) — times (D - 1) / D, the part
a uniform hash sends to other chips.  Over the chip's ICI peak
(peaks_ici.json, by the device kind whose peaks run.py handed over; a kind
that file lacks is an error), over the collective time of `collective_ms`.
Padding, skew and latency lower the share; nothing lifts it past 100%: an
all_gather receives more than this reckons, never less."""

import loader
import meshred


def read(ctx):
    ns = meshred.collective_ns(ctx)
    moved = meshred.dispatches(ctx) if ns else []
    if not moved:
        return None
    kinds = [k for k, v in loader.load_json("peaks.json").items() if v == ctx["peaks"]]
    ici = loader.load_json("peaks_ici.json")
    if not kinds or kinds[0] not in ici:
        raise KeyError(f"no ICI peak for device kind {kinds} in peaks_ici.json")
    leaves = sum(s["attrs"]["exchange_bytes"] * (s["attrs"]["devices"] - 1)
                 / s["attrs"]["devices"] for s in moved)
    return 100.0 * (leaves / ici[kinds[0]]["ici_bytes_per_s"]) / (ns / 1e9)
