"""kernels (ops/pallas/fused.py under exec/compiler.py's dispatch): of the
requests inside the traced slice, the share whose `dispatch` span names the
fused scan kernel with at least one parameter riding as a kernel scalar
(`kernels`: `pallas fused_pipeline (... params <n>)`, the text of EXPLAIN
ANALYZE's `-- kernel:` line).  100, or a prepared statement is off its
kernel: its bindings baked into the plan, or the scan run operator at a
time.  None where no `dispatch` span says which kernels its program holds
(a program without the attribute)."""

import re

from spanred import named, queries

FUSED = re.compile(r"\bfused_pipeline \([^)]*\bparams (\d+)")


def read(ctx):
    found = [[s["attrs"]["kernels"] for s in named(below, "dispatch")
              if "kernels" in s["attrs"]] for _q, below in queries(ctx)]
    if not any(found):
        return None
    return 100.0 * sum(
        1 for f in found
        if any(int(n) >= 1 for text in f for n in FUSED.findall(text))
    ) / len(found)
