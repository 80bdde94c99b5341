"""executor table IO: bytes a request's `scan_load` spans newly put on the
device (`h2d_bytes`: the arrays `table_page` made; 0 when every column was
resident); median over the requests inside the traced slice."""

from spanred import per_tree


def read(ctx):
    return per_tree(ctx, "scan_load", lambda s: float(s["attrs"].get("h2d_bytes", 0)))
