"""kernels (ops/pallas/fused.py): the fused scan kernel's own share of its
roofline.  The least bytes the slice's queries have to read (bytes_model.py:
every row of each referenced column once, at the width it is resident —
the same work whatever implements it) over the chip's HBM bandwidth
(peaks.json), over the time of the device ops named `fused_scan` (the
`pallas_call`'s `name=`; the HLO instruction is `%fused_scan.<n>`) inside
the slice.  Bounded by HBM bytes: the kernel reads each plane once and does
the rest in VMEM.  What prepares its planes is `scan_prep_device_share`'s;
`hbm_roofline_share` holds the same bytes against all device-busy time.
None where the slice holds no such op (a program that declines the kernel)."""

import re

from tracered import share_in_slice

KERNEL = re.compile(r"^%?fused_scan\b")


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    kernel_s = sum(s for name, s in t["ops"].items() if KERNEL.match(name))
    if not kernel_s:
        return None
    needed = sum(share_in_slice(r, t) * ctx["least_bytes"][r["template"]]
                 for r in ctx["records"] if r["error"] is None)
    least_s = needed / ctx["peaks"]["hbm_bytes_per_s"] / ctx["chips"]
    return 100.0 * least_s / (kernel_s / t["chips"])
