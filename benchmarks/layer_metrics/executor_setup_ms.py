"""coordinator root fragment: what an execution costs the host before its
program is enqueued and after its flags are back — `root_fragment`'s own time
(the executor made, `_node_ids`), `size` (capacities from the executor, the
capacity cache or statistics), `program_lookup` (the cache key, the executor's
own cache, on a miss the signature and the traced call), `compile` (the
compile service's answer) and `settle` (tighten, store); the pieces of a
request's host path (hostpath.py), mean over the cell's templates of each
template's median.  None on a program without the spans."""

from hostpath import wall_ms


def read(ctx):
    return wall_ms(ctx, "root_fragment (self)", "size", "program_lookup",
                   "compile", "settle")
