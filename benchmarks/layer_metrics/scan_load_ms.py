"""executor table IO (exec/compiler.py `table_page`): the sum of a request's
`scan_load` spans — reading the connector's columns, narrowing, padding,
dictionary coding and putting them on the device, or finding them there;
median over the requests inside the traced slice."""

from spanred import per_tree


def read(ctx):
    return per_tree(ctx, "scan_load")
