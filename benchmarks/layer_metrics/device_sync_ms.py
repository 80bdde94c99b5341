"""coordinator root fragment: `device_wait` outside the time the device was
busy under it (hostpath.py cuts that out as `device`) — the host's side of
the wait: the blocked thread's wake-up and the device->host read of the
program's flags; mean over the cell's templates of each template's median.
Span + device trace, as `coordinator_ms`.  None on a program without the spans."""

from hostpath import wall_ms


def read(ctx):
    return wall_ms(ctx, "device_wait")
