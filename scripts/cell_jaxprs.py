#!/usr/bin/env python3
"""Each embedded cell's statements traced (not compiled, not run) at the cell's
row counts and shipped tiers: sha256 of `jax.make_jaxpr`'s text and the
program's join events, as JSON.  Run it from the root of two trees (this one
and a `git archive` of the parent) and diff the files: a change that is to
leave a cell's programs alone leaves its lines byte-identical.

    JAX_PLATFORMS=cpu python scripts/cell_jaxprs.py out.json

Plans and scan capacities come from the statistics recorded for
tests/test_benchmark_caps.py, dtypes and dictionaries from SF0.01 pages:
nothing is generated at scale (ten statements in under a minute)."""
import hashlib
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402

from tests.test_compact_rows import (  # noqa: E402
    CASES, _load, _scan_stand_ins, _shipped_keys, planned_engine)
from trino_tpu.connectors.tpch import TpchConnector  # noqa: E402
from trino_tpu.exec.capcache import _key  # noqa: E402
from trino_tpu.exec.compiler import _make_call, _node_ids  # noqa: E402
from trino_tpu.ops import kernels  # noqa: E402
from trino_tpu.plan.nodes import TableScan  # noqa: E402
from trino_tpu.runtime.engine import Engine  # noqa: E402


def main(out_path: str) -> None:
    tiny = Engine()
    tiny.register_catalog("tpch", TpchConnector(0.01))
    out = {}
    for config, name in CASES:
        with planned_engine(config) as engine:
            plan = engine.plan("\n".join(_load("templates", f"{name}.json")["text"]))
            stand_ins = _scan_stand_ins(engine, plan)
        key = _key(plan, stand_ins)
        caps = {int(i): c for i, c in _load("caps", _shipped_keys()[key])["entries"][key].items()}
        pages = {
            str(i): jax.tree_util.tree_map(
                lambda a, rows=stand_ins[str(i)].capacity: jax.ShapeDtypeStruct((rows,), a.dtype),
                tiny.executor._scan_page(i, node))
            for i, node in _node_ids(plan).items() if isinstance(node, TableScan)
        }
        call, _holder = _make_call(plan, caps, False)
        text = str(jax.make_jaxpr(call)(pages))
        out[f"{config}/{name}"] = {
            "sha256": hashlib.sha256(text.encode()).hexdigest(),
            "lines": text.count("\n"),
            "joins": [k for k in kernels.describe(plan) if "join" in k],
        }
        print(config, name, out[f"{config}/{name}"]["sha256"][:16], flush=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    main(sys.argv[1])
