"""process (GIL): how long a request's threads worked in company — the part
of its working pieces (hostpath.py: not the device's time, the wait for it,
the poll's hold and wake-up, `queued`) during which another thread of the
process was at work too (a `query` tree outside its `device_wait`, a
`finalize`, an `http.post`, an `http.get` after its hold, a client's own
stretch).  One interpreter lock serves them all, so that time was shared.
Read from the spans of all threads on one clock: the thread CPU clock beside
each span (`cpu_ms`) would say it directly, and is not fit to be read on the
benchmark's machines (trino_tpu/utils/tracing.py `_cpu_clock`).  Mean over the
cell's templates of each template's median.  None on a program without the
spans."""

from hostpath import per_template


def read(ctx):
    return per_template(ctx, lambda _r, _pieces, inside: inside["contended_ms"])
