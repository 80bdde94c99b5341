"""kernels: the least bytes the slice's queries have to read (bytes_model.py:
every row of each referenced column once, from shapes) over the chip's HBM
bandwidth (peaks.json), over the device-busy time of the slice.  Bounded by
HBM bytes, not by operations: these statements are scans, joins and sorts."""

from tracered import share_in_slice


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["busy_s"]:
        return None
    needed = sum(share_in_slice(r, t) * ctx["least_bytes"][r["template"]]
                 for r in ctx["records"] if r["error"] is None)
    least_s = needed / ctx["peaks"]["hbm_bytes_per_s"] / ctx["chips"]
    return 100.0 * least_s / t["busy_s"]
