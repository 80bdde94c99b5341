"""client protocol: the part of the client's latency under no program span
of its query id — the connections, the handler threads' start, the client's
decode; the `client` piece of a request's host path (hostpath.py), mean over
the cell's templates of each template's median.  None on a program without the
spans."""

from hostpath import CLIENT, wall_ms


def read(ctx):
    return wall_ms(ctx, CLIENT)
