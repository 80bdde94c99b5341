"""The capacities the benchmark ships are the ones its plans look up.

`benchmarks/run.py` `seed_capacities` hands the program the entries of
`benchmarks/caps/*.json` so that a first run in a checkout compiles ONE tight
program a statement.  An entry is keyed by sha1(plan json + input capacities)
(`exec/capcache.py` `_key`): a change to `trino_tpu/plan/` that moves a plan
orphans its entry, and nothing says so — the program sizes the plan from
statistics again, and q18's loose program alone compiles for ~700 s on the
chip (PR 23: 686 s; PR 38's first run in the driver's checkout: 1,003.5 s of
set-up against 261.6 s in the builder's, 197 s from the driver's 1,200 s cut).
This test says so, here on the CPU: it plans the embedded cell's statements as
the cell does and asks for their keys among the shipped entries.

Planning at SF1 asks the connector for column statistics only (the first time
that generates a few columns into the connector's column directory, seconds);
no table is loaded and nothing runs.
"""

import glob
import json
import os
from types import SimpleNamespace

import pytest

from trino_tpu.exec.capcache import _key
from trino_tpu.exec.compiler import _node_ids
from trino_tpu.plan.nodes import TableScan, format_plan

_BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")


def _load(*parts):
    with open(os.path.join(_BENCH, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def embedded_engine():
    """`benchmarks/entries/embedded.py` `Entry.__init__`, less the device."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    config = _load("configs", "tpch_sf1_embedded.json")
    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(float(config["scale_factor"])))
    for prop, value in config["session"].items():
        engine.session.set(prop, str(value))
    return engine


def _shipped_keys() -> dict:
    """key -> the caps file that holds it."""
    keys = {}
    for path in sorted(glob.glob(os.path.join(_BENCH, "caps", "*.json"))):
        for key in _load(path)["entries"]:
            keys[key] = os.path.basename(path)
    return keys


def _scan_stand_ins(engine, plan) -> dict:
    """What `LocalExecutor._load_inputs` hands `load_caps` for a one-device
    `Engine()`: a page per TableScan by preorder node id, whose capacity is
    the table's row count (no split, no pad, no dynamic filter)."""
    return {
        str(i): SimpleNamespace(
            capacity=engine.catalogs.get(n.catalog).estimated_row_count(n.table)
        )
        for i, n in _node_ids(plan).items()
        if isinstance(n, TableScan)
    }


@pytest.mark.parametrize("name", _load("traffic", "joins_text_1stream.json")["pass"])
def test_embedded_statement_finds_its_shipped_capacities(embedded_engine, name):
    text = "\n".join(_load("templates", f"{name}.json")["text"])  # loader.sql_text
    plan = embedded_engine.plan(text)
    key = _key(plan, _scan_stand_ins(embedded_engine, plan))
    shipped = _shipped_keys()
    assert key in shipped, (
        f"{name}'s plan at SF1 has the capacities' key {key}; benchmarks/caps/"
        f" holds {shipped}.  The plan moved: learn its tiers anew (the"
        " statement through Engine() at SF1 until an execution builds"
        " nothing) and add them as a new file under benchmarks/caps/.\n"
        + format_plan(plan)
    )
    # the entry sizes nodes of THIS plan: every id it names is a node's
    entry = _load("caps", shipped[key])["entries"][key]
    assert {int(i) for i in entry} <= set(_node_ids(plan))
