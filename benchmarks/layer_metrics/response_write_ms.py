"""client protocol: the answer's way out of the handler — `http.get` after
the hold, by its `encode_ms` (`json.dumps`) and `write_ms` (headers and body
onto the socket); the two pieces of a request's host path (hostpath.py), mean
over the cell's templates of each template's median.  None on a program without
the spans."""

from hostpath import ENCODE, WRITE, wall_ms


def read(ctx):
    return wall_ms(ctx, ENCODE, WRITE)
