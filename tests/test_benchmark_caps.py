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

The SF10 configuration (`tpch_sf10_embedded`, PR 40) is planned from
statistics too, but no test may generate SF10's columns to have them (three
tables' first passes: ~90 s and 6 GB in the sandbox).  So the case plans from
the statistics the connector computed from those columns once, recorded in
`tests/data/tpch_sf10_planned_stats.json` (the columns the cells' statements
read and the tables' row counts):
milliseconds, on any machine.  A statistic planning asks for that is not
recorded fails the case — on a fresh machine that column would be generated,
which is the other thing PR 40 mended.  Where SF10's column files are on the
machine (whoever learns the tiers anew has them) the recording is held to
them; elsewhere that one case skips.
"""

import contextlib
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


# the configurations whose cells go through Engine(), and the mix each is sent
EMBEDDED = {
    "tpch_sf1_embedded": "joins_text_1stream",
    "tpch_sf10_embedded": "joins_text_1stream",
    "tpch_sf10_embedded_multiway": "multiway_text_1stream",
    "tpch_sf10_embedded_subquery": "subquery_text_1stream",
}
CASES = [(config, name) for config, mix in EMBEDDED.items()
         for name in _load("traffic", f"{mix}.json")["pass"]]
# from this scale on a test does not generate columns: it plans from a recording
_BIG = 2.0
_RECORDED = os.path.join(os.path.dirname(__file__), "data", "tpch_sf10_planned_stats.json")


def _recorded_stats(scale: float) -> dict:
    """table -> TableStats from the recording; a column it does not hold is
    one planning must not ask of (the connector would generate it)."""
    from trino_tpu.connectors.spi import ColumnStats, LazyStats, TableStats
    from trino_tpu.connectors.tpch.generator import TPCH_SCHEMAS

    recorded = _load(_RECORDED)
    assert recorded["scale_factor"] == scale

    def table(name: str, t: dict) -> TableStats:
        def column(c: str) -> ColumnStats:
            assert c in t["columns"], (
                f"planning asked a statistic of {name}.{c}, which {_RECORDED} does not"
                " hold: with an empty column directory the connector generates that"
                " column (at SF10 a pass over the table).  If a statement names it,"
                " record it; if none does, something walks the relation's statistics whole")
            return ColumnStats(**t["columns"][c])

        return TableStats(float(t["rows"]), LazyStats([c for c, _t in TPCH_SCHEMAS[name]], column))

    return {name: table(name, t) for name, t in recorded["tables"].items()}


def _columns_on_this_machine(scale: float) -> bool:
    from trino_tpu.connectors.tpch import columns

    return all(
        os.path.exists(os.path.join(columns._folder(table, scale), c + ".npy"))
        for table, t in _load(_RECORDED)["tables"].items() for c in t["columns"])


@contextlib.contextmanager
def planned_engine(config_name: str):
    """`benchmarks/entries/embedded.py` `Entry.__init__`, less the device."""
    from trino_tpu.connectors import tpch
    from trino_tpu.runtime.engine import Engine

    config = _load("configs", f"{config_name}.json")
    scale = float(config["scale_factor"])
    mp = pytest.MonkeyPatch()
    if scale >= _BIG:
        # the connector's statistics and row counts as it keeps them once the
        # columns have been read: nothing is generated, nothing is opened
        stats = _recorded_stats(scale)
        tables = {}
        for name, s in stats.items():
            tables[(name, scale)] = t = tpch._Table(name, scale)
            t._rows = int(s.row_count)
        mp.setattr(tpch, "_STATS", {(name, scale): s for name, s in stats.items()})
        mp.setattr(tpch, "_TABLES", tables)
    engine = Engine()
    engine.register_catalog("tpch", tpch.TpchConnector(scale))
    for prop, value in config["session"].items():
        engine.session.set(prop, str(value))
    try:
        yield engine
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def embedded_engine(request):
    with planned_engine(request.param) as engine:
        yield engine


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


@pytest.mark.parametrize("embedded_engine,name", CASES, indirect=["embedded_engine"])
def test_embedded_statement_finds_its_shipped_capacities(embedded_engine, name):
    text = "\n".join(_load("templates", f"{name}.json")["text"])  # loader.sql_text
    plan = embedded_engine.plan(text)
    key = _key(plan, _scan_stand_ins(embedded_engine, plan))
    shipped = _shipped_keys()
    assert key in shipped, (
        f"{name}'s plan at scale {embedded_engine.catalogs.get('tpch').scale:g} has the"
        f" capacities' key {key}; benchmarks/caps/"
        f" holds {shipped}.  The plan moved: learn its tiers anew (the"
        " statement through Engine() at that scale until an execution builds"
        " nothing) and add them as a new file under benchmarks/caps/.\n"
        + format_plan(plan)
    )
    # the entry sizes nodes of THIS plan: every id it names is a node's
    entry = _load("caps", shipped[key])["entries"][key]
    assert {int(i) for i in entry} <= set(_node_ids(plan))


def test_recorded_sf10_statistics_are_the_columns_own():
    """The recording the SF10 case plans from, against the connector's own
    statistics — where SF10's column files are on the machine."""
    import dataclasses

    from trino_tpu.connectors.tpch import TpchConnector

    recorded = _load(_RECORDED)
    scale = recorded["scale_factor"]
    if not _columns_on_this_machine(scale):
        pytest.skip(f"the TPC-H columns of scale {scale:g} are not on this machine,"
                    " and reading their statistics would generate them for minutes")
    connector = TpchConnector(scale)
    for name, t in recorded["tables"].items():
        stats = connector.table_stats(name)
        assert int(stats.row_count) == t["rows"]
        for c, want in t["columns"].items():
            assert dataclasses.asdict(stats.columns[c]) == pytest.approx(want), (name, c)
