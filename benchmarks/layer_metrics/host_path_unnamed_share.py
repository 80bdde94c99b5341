"""coordinator: the share of the client's latency that no span names — the
`(self)` pieces of `query`, `root_fragment`, `execute`, `http.post` and
`http.get` in a request's host path (hostpath.py); mean over the cell's
templates of each template's median.  None on a program without the spans."""

from hostpath import UNNAMED, per_template


def read(ctx):
    return per_template(ctx, lambda r, pieces, _in: 100.0 * sum(
        pieces[k][0] for k in UNNAMED if k in pieces) / max(r["t1"] - r["t0"], 1e-9))
