#!/usr/bin/env python3
"""The precision control: the reference put in the program's place, computed
in float32 — the nearest precision below the exact integer and float64
arithmetic the configurations state.  It has to come out NOT correct.

    python3 benchmarks/control.py [--workloads a,b] [--seeds 1,2,3] [--scale 1.0]

For each cell and seed it takes the bindings the cell's streams would send
(the validation binding of a text mix; the first `check_sample` draws per
stream of a drawn mix), computes each answer twice — exactly, and lowered —
and holds the lowered one against the exact one with the comparison the
benchmark uses.  The benchmark's own runs never run this; PERF.md records its
readings beside the limits.  Host-only: it needs no accelerator.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import compare as _compare  # noqa: E402
import loader  # noqa: E402
import traffic as _traffic  # noqa: E402


def control_cell(cell_name: str, seed: int, data: dict) -> dict:
    _cell, config, mix, templates = loader.cell(cell_name)
    bindings: dict = {}
    for s in _traffic.streams(mix, templates, seed):
        for _ in range(int(mix.get("check_sample", 1))):
            for name in s.order:
                b = s.next_binding(name)
                bindings[b.key] = b
    out = {"cell": cell_name, "seed": seed, "answers": 0, "exact_mismatches": 0,
           "decimal_rel_err": None, "double_rel_err": None, "by_template": {}}
    for key, b in sorted(bindings.items()):
        t = templates[b.template]
        ref = loader.load_module("reference", t["reference"]).reference
        c = _compare.compare(ref(data, *b.args, lowered=True), ref(data, *b.args),
                             t["ordered"])
        out["answers"] += 1
        out["exact_mismatches"] += c["exact_mismatches"]
        per = out["by_template"].setdefault(
            b.template, {"exact_mismatches": 0, "decimal_rel_err": [], "double_rel_err": []})
        per["exact_mismatches"] += c["exact_mismatches"]
        for k in ("decimal_rel_err", "double_rel_err"):
            per[k].append(c[k])
    limits = config["limits"]
    caught = out["exact_mismatches"] > limits["exact_mismatches"]
    for per in out["by_template"].values():
        for k in ("decimal_rel_err", "double_rel_err"):
            # widest and smallest gap over this template's answers
            per[k] = [max(per[k]), min(per[k])]
            caught = caught or per[k][0] > limits[k]
            out[k] = max(out[k] or 0.0, per[k][0])
    out["correct"] = not caught
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", default="")
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--scale", type=float, default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # the generator's import chain
    from trino_tpu.connectors.tpch import tpch_data

    names = [w for w in args.workloads.split(",") if w] or [
        w["name"] for w in loader.benchmark()["workloads"]]
    data: dict = {}
    passed = []
    for name in names:
        _cell, config, _mix, templates = loader.cell(name)
        scale = args.scale if args.scale is not None else float(config["scale_factor"])
        for t in templates.values():
            for table in t["columns"]:
                data.setdefault(table, tpch_data(table, scale))
        for seed in (int(s) for s in args.seeds.split(",")):
            out = control_cell(name, seed, data)
            print("control: " + json.dumps(out), flush=True)
            if out["correct"]:
                passed.append((name, seed))
    if passed:
        print(f"control: NOT CAUGHT in {passed}", flush=True)
        return 1
    print("control: the lowered precision came out not correct everywhere", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
