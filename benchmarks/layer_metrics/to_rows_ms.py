"""coordinator result path (`exec/compiler.py` `page_rows`: the result page
fetched from the device and turned into Python rows): the `to_rows` piece of
a request's host path (hostpath.py); mean over the cell's templates of each
template's median.  None on a program without the spans."""

from hostpath import wall_ms


def read(ctx):
    return wall_ms(ctx, "to_rows")
