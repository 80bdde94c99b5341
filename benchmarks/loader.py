"""Finds the benchmark's data files and small modules by name.

Everything that belongs to one configuration, one traffic mix, one template,
one reference, one layer metric or one way in sits in a file of its own; the
harness holds no branch on any of their names.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module (names may hold dots)."""
    d = os.path.join(HERE, kind)
    if d not in sys.path:
        sys.path.insert(0, d)  # references import their `common`
    path = os.path.join(d, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path
    )
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(metric: str):
    """The reader of a per-layer metric: layer_metrics/<metric>.py, or — for
    a variant `<metric>.<suffix>`, the same quantity listed again for cells
    that report another end-to-end metric — layer_metrics/<metric>.py."""
    try:
        return load_module("layer_metrics", metric).read
    except FileNotFoundError:
        if "." not in metric:
            raise
        return load_module("layer_metrics", metric.rsplit(".", 1)[0]).read


def benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str) -> tuple[dict, dict, dict, dict]:
    """-> (the cell, its configuration file, its traffic file, its templates)."""
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no such workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(REPO, cfg_entry["file"])) as f:
        config = json.load(f)
    return (w, config) + mix(w["traffic"])


def mix(name: str) -> tuple[dict, dict]:
    """-> (a traffic file, the templates of its pass)."""
    m = load_json("traffic", name + ".json")
    return m, {n: load_json("templates", n + ".json") for n in m["pass"]}


def sql_text(template: dict, key: str = "text") -> str:
    return "\n".join(template[key])
