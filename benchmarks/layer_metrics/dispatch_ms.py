"""coordinator root fragment: the `dispatch` piece of a request's host path
(hostpath.py) — the compiled program's call until it returns, the device's
work enqueued; mean over the cell's templates of each template's median.  None
on a program without the spans."""

from hostpath import wall_ms


def read(ctx):
    return wall_ms(ctx, "dispatch")
