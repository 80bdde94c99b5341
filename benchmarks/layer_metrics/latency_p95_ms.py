"""client protocol: the nearest-rank 95th percentile of all latencies of the
window, on the client's clock.  Not an end-to-end metric: the client sleeps
50 ms between polls, latencies cluster at ~60, ~115 and ~165 ms, and the 95th
percentile sits on the edge between two clusters and flips between them from
run to run (PERF.md, PR 23)."""

import math


def read(ctx):
    v = sorted(r["t1"] - r["t0"] for r in ctx["records"] if r["error"] is None)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)] * 1e3 if v else None
