"""executor table IO (exec/compiler.py `_load_inputs`, connectors/tpch
column files): seconds of set-up inside `scan_load` spans that read a table
from outside the device — `source` `file` (generated columns found as
files, read memory-mapped) or `generated` (made and written first), never
`resident`: reading, dictionary coding, narrowing and the upload.  Set-up is
whatever ended before the window's first request.  None where no span says
where its columns came from (a program without the attribute)."""


def read(ctx):
    opened = min((r["t0"] for r in ctx["records"]), default=None)
    loads = [s for s in ctx["spans"] if s["name"] == "scan_load"
             and s["attrs"].get("source") not in (None, "resident")
             and (opened is None or s["t1"] <= opened)]
    if not loads:
        return None
    return sum(s["t1"] - s["t0"] for s in loads)
