"""The interpret switch `benchmarks/run.py`'s CPU rehearsal sets by name and
`ops/pallas/fused.py` reads.  The hash-table kernel this module held went in
PR 45; the file goes when a `benchmark` PR points `run.py` elsewhere."""

# run the fused scan kernel interpreted (no TPU): read at trace time
INTERPRET = False
