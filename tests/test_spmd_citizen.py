"""The SPMD executor on the normal path: programs from the compile service,
capacities from the capacity cache, the one-chip executor's spans, and what
its exchanges move on every dispatch — on a 4-device virtual mesh, at the
rehearsal scale of the benchmark cell that measures it (`spmd_q12_q01`),
through that cell's own way in and against its own plain reference.
"""

import os
import re
import sys

import jax
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
sys.path.insert(0, BENCH)

import compare  # noqa: E402  (benchmarks/compare.py)
import loader  # noqa: E402  (benchmarks/loader.py)
import traffic  # noqa: E402  (benchmarks/traffic.py)

from trino_tpu.exec.compilesvc import SERVICE, CompileService  # noqa: E402

STATEMENTS = ["q12", "q01"]
SCALE = 0.01


def _flat(spans) -> list:
    out = []
    for s in spans:
        out.append(s)
        out.extend(_flat(s.children))
    return out


class _Cell:
    """The cell's way in over four virtual devices, every statement run
    twice: at its first sizes, then at the tiers that run tightened."""

    def __init__(self):
        from trino_tpu.connectors.tpch import tpch_data

        _cell, self.config, self.mix, self.templates = loader.cell("spmd_q12_q01")
        self.entry = loader.load_module("entries", self.mix["entry"]).Entry(
            self.config, self.templates, SCALE)
        self.engine = self.entry.engine
        self.request = self.entry.client(0)
        self.data = {t: tpch_data(t, SCALE)
                     for tm in self.templates.values() for t in tm["columns"]}
        self.first, self.tightened = {}, {}
        for name in STATEMENTS:
            self.first[name] = self.run(name)
            self.tightened[name] = self.run(name)

    def sql(self, name: str) -> str:
        return loader.sql_text(self.templates[name])

    def run(self, name: str) -> dict:
        """One request -> its rows, the programs built meanwhile and the
        spans of its `execute` tree."""
        seen, built = len(self.entry.spans()), SERVICE.builds
        rows, _ = self.request(name, None)
        spans = _flat([s for s in self.entry.spans()[seen:] if s.name == "execute"])
        return {"rows": rows, "builds": SERVICE.builds - built, "spans": spans,
                "names": [s.name for s in spans]}


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    from trino_tpu.exec import capcache

    assert len(jax.devices()) >= 4, "conftest must provide the virtual devices"
    mp = pytest.MonkeyPatch()
    mp.setenv("TRINO_TPU_CAPS_CACHE",
              str(tmp_path_factory.mktemp("caps") / "caps_cache.json"))
    mp.setattr(capcache, "_mem", None)  # a capacity file of this module's own
    try:
        yield _Cell()
    finally:
        mp.undo()


def _plan_and_inputs(cell, name):
    from trino_tpu.exec.compiler import _node_ids

    plan = cell.engine.plan(cell.sql(name))
    return plan, cell.engine.executor._load_inputs(_node_ids(plan), None)


@pytest.mark.parametrize("name", STATEMENTS)
def test_first_execution_builds_through_the_service(cell, name):
    first = cell.first[name]
    compiles = [s for s in first["spans"] if s.name == "compile"]
    # SERVICE.builds moves when an SPMD program is built, once per `compile`
    # span that was not handed a program: the benchmark's counter can see it
    assert first["builds"] >= 1
    assert first["builds"] == len(compiles)
    assert compiles[0].attributes["cause"] == "new_plan"
    assert {s.attributes["cause"] for s in compiles[1:]} <= {"caps_tier"}
    assert all(s.attributes["status"] == "ready" for s in compiles)
    assert first["names"][:2] == ["execute", "scan_load"]


def _against_reference(cell, name: str, rows) -> dict:
    """compare.py's verdict on `rows` against the cell's plain reference."""
    t = cell.templates[name]
    want = loader.load_module("reference", t["reference"]).reference(
        cell.data, *traffic.validation(t).args)
    assert want
    return compare.compare(rows, want, t["ordered"])


@pytest.mark.parametrize("name", STATEMENTS)
def test_answer_equals_the_cells_plain_reference(cell, name):
    c = _against_reference(cell, name, cell.first[name]["rows"])
    assert c["exact_mismatches"] == 0, c
    assert c["decimal_rel_err"] <= cell.config["limits"]["decimal_rel_err"], c
    # the AVGs: float64 here (the chip's limit is the configuration's)
    assert c["double_rel_err"] < 1e-12, c


@pytest.mark.parametrize("name", STATEMENTS)
def test_second_execution_builds_the_tightened_program(cell, name):
    """The first run sized every Aggregate at its child and the bucket at
    half of it; what it observed is a few groups, so the second run builds
    the plan once more at tight tiers, and says so."""
    second = cell.tightened[name]
    assert second["rows"] == cell.first[name]["rows"]
    assert second["builds"] == 1
    causes = [s.attributes["cause"] for s in second["spans"] if s.name == "compile"]
    assert causes == ["caps_tightened"]


@pytest.mark.parametrize("name", STATEMENTS)
def test_third_execution_builds_nothing_and_says_what_it_moves(cell, name):
    again = cell.run(name)
    assert again["rows"] == cell.first[name]["rows"]
    assert again["builds"] == 0
    assert again["names"] == ["execute", "scan_load", "size", "program_lookup",
                              "dispatch", "device_wait"]
    assert again["spans"][2].attributes["source"] == "learned"
    assert again["spans"][3].attributes["jit_cache"] == "hit"
    by_name = {s.name: s.attributes for s in again["spans"]}
    scan = by_name["scan_load"]
    assert scan["h2d_bytes"] == 0 and scan["columns_cached"] == scan["columns"] > 0
    moved = by_name["dispatch"]
    assert moved["devices"] == 4 and moved["exchange_bytes"] > 0
    assert by_name["device_wait"]["d2h_bytes"] > 0

    # the collectives the traced program holds, counted in its lowered text
    plan, inputs = _plan_and_inputs(cell, name)
    ex = cell.engine.executor
    call, holder = ex._make_call(plan, dict(ex._learned_caps[plan]), False)
    lowered = jax.jit(call).lower(inputs, ())
    traced = dict(holder["dispatch"]["exchanges"])
    holder["lowered"](lowered)  # as the build does: what survived lowering
    text = lowered.as_text()
    in_text = {op: len(re.findall(rf"\bstablehlo\.{op}\b", text))
               for op in ("all_to_all", "all_gather")}
    said = moved["exchanges"]
    assert said == holder["dispatch"]["exchanges"]
    assert said.get("all_to_all", 0) == in_text["all_to_all"]
    assert said.get("all_gather", 0) + said.get("pmax_count", 0) == in_text["all_gather"]
    # q12 repartitions, q01 ends in a gather (and every sized node agrees on
    # its overflow counter through one small all_gather)
    assert said[{"q12": "all_to_all", "q01": "all_gather"}[name]] > 0
    assert said["pmax_count"] >= 1
    # q12 gathers three filter columns nothing reads after the exchange:
    # traced, dropped by the lowering, and not counted
    assert all(traced[k] >= said[k] for k in said)
    assert (traced != said) == (name == "q12")


@pytest.mark.parametrize("name", STATEMENTS)
def test_new_executor_finds_capacities_and_program(cell, name):
    """After store_caps a new executor over the same mesh starts at the
    learned tiers: one program per statement from an empty service, and
    none at all from the process's own (`joined`)."""
    from trino_tpu.exec.spmd import SpmdExecutor
    from trino_tpu.utils.tracing import InMemorySpanExporter, Tracer

    plan = cell.engine.plan(cell.sql(name))
    old = cell.engine.executor
    want = cell.engine.execute_page(cell.sql(name)).to_pylist()

    cold = SpmdExecutor(cell.engine.catalogs, "tpch", old.devices)
    cold.compile_service = CompileService()
    assert cold.execute(plan).to_pylist() == want
    assert cold.compile_service.builds == 1
    assert cold._learned_caps[plan] == old._learned_caps[plan]

    warm = SpmdExecutor(cell.engine.catalogs, "tpch", old.devices)
    warm.tracer, exporter = Tracer(), InMemorySpanExporter()
    warm.tracer.add_exporter(exporter)
    built = SERVICE.builds
    with warm.tracer.span("execute"):
        assert warm.execute(plan).to_pylist() == want
    assert SERVICE.builds == built
    causes = [s.attributes["cause"] for s in _flat(exporter.snapshot())
              if s.name == "compile"]
    assert causes == ["joined"]


def _own_executor(cell, monkeypatch, tmp_path):
    """A new executor on the cell's mesh that knows nothing: a service and
    a capacity file of its own, its `compile` spans and a count of its
    calls of `_trace_eager`."""
    from trino_tpu.exec import capcache
    from trino_tpu.exec.spmd import SpmdExecutor
    from trino_tpu.utils.tracing import InMemorySpanExporter, Tracer

    monkeypatch.setenv("TRINO_TPU_CAPS_CACHE", str(tmp_path / "caps_cache.json"))
    monkeypatch.setattr(capcache, "_mem", None)
    eager = []
    real = SpmdExecutor._trace_eager

    def counted(self, plan, *args, **kwargs):
        eager.append(plan)
        return real(self, plan, *args, **kwargs)

    monkeypatch.setattr(SpmdExecutor, "_trace_eager", counted)
    ex = SpmdExecutor(cell.engine.catalogs, "tpch", cell.engine.executor.devices)
    ex.compile_service = CompileService()
    ex.tracer, exporter = Tracer(), InMemorySpanExporter()
    ex.tracer.add_exporter(exporter)

    def compiles():
        return [s.attributes for s in _flat(exporter.snapshot()) if s.name == "compile"]

    return ex, eager, compiles


def test_cold_statement_is_one_build_and_no_eager_pass(cell, monkeypatch, tmp_path, oracle):
    """Nothing learned, nothing cached, inputs as small as they come: the
    stats-sized capacities go straight into ONE compiled program."""
    from tests.oracle import assert_rows_equal
    from tests.tpch_queries import ORDERED, QUERIES

    ex, eager, compiles = _own_executor(cell, monkeypatch, tmp_path)
    plan = cell.engine.plan(QUERIES["q03"])
    with ex.tracer.span("execute"):
        rows = ex.execute(plan).to_pylist()
    assert_rows_equal(rows, oracle.query(QUERIES["q03"]), ordered=ORDERED["q03"])
    assert eager == []
    assert ex.compile_service.builds == 1
    assert [c["cause"] for c in compiles()] == ["new_plan"]
    assert not ex.fallback_events


def test_undersized_capacities_converge_through_the_compiled_loop(
        cell, monkeypatch, tmp_path):
    """First capacities far too small: every overflow costs one compiled
    tier (never an eager pass), and the answer is the reference's."""
    from trino_tpu.exec.spmd import SpmdExecutor

    ex, eager, compiles = _own_executor(cell, monkeypatch, tmp_path)
    sized = SpmdExecutor._initial_caps
    monkeypatch.setattr(
        SpmdExecutor, "_initial_caps",
        lambda self, nodes, inputs: {n: 2 for n in sized(self, nodes, inputs)})
    plan = cell.engine.plan(cell.sql("q12"))
    with ex.tracer.span("execute"):
        rows = ex.execute(plan).to_pylist()
    assert _against_reference(cell, "q12", rows)["exact_mismatches"] == 0
    assert eager == []
    built = compiles()
    assert len(built) == ex.compile_service.builds >= 2
    assert [c["cause"] for c in built] == ["new_plan"] + ["caps_tier"] * (len(built) - 1)
    assert len({c["signature"] for c in built}) == len(built)  # a tier each
    # every tier but the last overflowed somewhere; the last holds, and is
    # what the loop learned
    grown = ex._learned_caps[plan]
    assert all(c >= 2 for c in grown.values()) and max(grown.values()) > 2


def test_capacities_are_keyed_by_device_count(cell):
    from trino_tpu.exec import capcache

    plan, inputs = _plan_and_inputs(cell, "q12")
    ex = cell.engine.executor
    assert ex._caps_scope == "|spmd4"
    # found, and settled: this process stored them, they only grow from here
    assert capcache.load_caps(plan, inputs, ex._caps_scope) == (ex._learned_caps[plan], True)
    assert capcache.load_caps(plan, inputs) == (None, False)  # one device: another key
    assert capcache.load_caps(plan, inputs, "|spmd8") == (None, False)


def test_scan_pages_lie_in_equal_shards_and_follow_scan_version(cell, monkeypatch):
    ex = cell.engine.executor
    pages = list(ex._sharded_pages.values())
    assert pages
    for page in pages:
        for a in jax.tree_util.tree_leaves(page):
            shards = a.addressable_shards
            assert len({s.device.id for s in shards}) == len(shards) == 4
            assert {s.data.shape[0] for s in shards} == {a.shape[0] // 4}
    # nothing of a table was staged whole on the first device
    assert not ex._table_cols and not ex._table_pages
    cell.entry.check_shards()

    # the pages are good for the version the connector vouches for, no longer
    conn = cell.engine.catalogs.get("tpch")
    real = conn.scan_version
    monkeypatch.setattr(conn, "scan_version", lambda table: ("later", real(table)))
    reread = cell.run("q01")
    scan = next(s for s in reread["spans"] if s.name == "scan_load").attributes
    assert scan["h2d_bytes"] > 0 and scan["columns_cached"] == 0
    assert reread["builds"] == 0 and reread["rows"] == cell.first["q01"]["rows"]
    assert len(ex._sharded_pages) == len(pages)  # the old version's page went


def test_entry_fails_a_page_that_one_device_holds_whole(cell):
    from trino_tpu.data.page import Column, Page
    from trino_tpu.data.types import BIGINT

    ex = cell.engine.executor
    whole = jax.device_put(np.arange(64), ex.devices[0])
    ex._sharded_pages["staged"] = Page((Column(BIGINT, whole),), whole < 64)
    try:
        with pytest.raises(RuntimeError, match="lies in shards"):
            cell.entry.check_shards()
    finally:
        del ex._sharded_pages["staged"]


def test_planned_exchanges_tally_is_per_trace():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from trino_tpu.ops.expr import ColumnVal
    from trino_tpu.data.types import BIGINT
    from trino_tpu.parallel import exchange

    mesh = Mesh(np.array(jax.devices()[:4]), (exchange.AXIS,))

    def step(x):
        cols, live = exchange.gather_all(
            [ColumnVal(x, None, None, BIGINT)], x >= 0)
        n = exchange.pmax_count(live.sum(), exchange.AXIS)
        return cols[0].data, n

    fn = shard_map(step, mesh=mesh, in_specs=(P(exchange.AXIS),), out_specs=P(),
                   check_vma=False)
    with exchange.planned_exchanges() as outer:
        with exchange.planned_exchanges() as inner:
            lowered = jax.jit(fn).lower(np.arange(32, dtype=np.int64))
        assert outer == []
    # per device: 8 int64 lanes and their 8 live lanes enter the gather
    assert exchange.reckon(inner) == {
        "exchanges": {"all_gather": 2, "pmax_count": 1}, "exchange_bytes": 8 * 8 + 8}
    # the gathered live mask only feeds the count; the data is an output
    assert exchange.reckon(inner, lowered.as_text(debug_info=True)) == exchange.reckon(inner)
    with exchange.planned_exchanges() as tally:  # the gathered data is read by nothing
        dead = jax.jit(lambda x: fn(x)[1]).lower(np.arange(32, dtype=np.int64))
    assert exchange.reckon(tally) == exchange.reckon(inner)
    assert exchange.reckon(tally, dead.as_text(debug_info=True)) == {
        "exchanges": {"all_gather": 1, "pmax_count": 1}, "exchange_bytes": 8}
    jax.jit(fn).lower(np.arange(32, dtype=np.int64))  # no tally open: no error


# ------------------------------- the exchange layer's readers, recorded

READERS = ["collective_ms", "collective_exposed_ms", "exchange_device_share",
           "ici_roofline_share"]


@pytest.fixture(scope="module")
def recorded():
    ctx = loader.load_json("testdata", "spmd_q12_q01_exchange.json")
    ctx["trace"]["path"] = os.path.join(BENCH, "testdata", ctx["trace"]["path"])
    ctx["trace"]["slice"] = tuple(ctx["trace"]["slice"])
    ctx["peaks"] = loader.load_json("peaks.json")[ctx["device_kind"]]
    return ctx, loader.load_json("testdata", "spmd_q12_q01_exchange.expected.json")


@pytest.mark.parametrize("metric", READERS)
def test_exchange_reader_against_the_recorded_slice(recorded, metric):
    ctx, expected = recorded
    read = loader.layer_reader(metric)
    # the trace's times pass through float32 nanoseconds in ProfileData
    assert read(ctx) == pytest.approx(expected[metric], rel=1e-4)
    assert 0 < read(ctx) < (100 if metric.endswith("_share") else 10)
    # a program without the exchange tally (the parent commit) moves no
    # bytes that a reader could see; the trace's collectives are still there
    parent = dict(ctx, spans=[dict(s, attrs={"signature": s["attrs"]["signature"]})
                              for s in ctx["spans"]])
    if metric == "ici_roofline_share":
        assert read(parent) is None
    else:
        assert read(parent) == read(ctx)
    # one chip, no collective (PR 24's recording of served_q06): nothing to read
    one_chip = dict(ctx, trace=dict(ctx["trace"], path=os.path.join(
        BENCH, "testdata", "served_q06_named.xplane.pb")))
    assert read(one_chip) is None
    assert read(dict(ctx, trace=None)) is None


def test_a_collective_is_told_by_its_opcode():
    import meshred  # benchmarks/meshred.py

    yes = ["%all_to_all.49 = u32[4,1,65536]{2,1,0:T(1,128)S(1)} all-to-all(u32[4,1,65536]{2,1,0} %fusion.1), channel_id=3",
           "%all-reduce.29 = (s32[6002368]{0:T(1024)S(1)}, s32[6002368]{0:T(1024)}) all-reduce(s32[6002368]{0} %dus.1, s32[6002368]{0} %dus.2)",
           "%all-gather-done.1 = f32[8]{0} all-gather-done((f32[2]{0}, f32[8]{0}) %all-gather-start.1)",
           "%collective-permute.3 = pred[] collective-permute(pred[] %p)",
           "%all-gather-start.3"]
    no = ["%all_to_all.50 = pred[4,1,1048576]{2,1,0:T(4,128)(4,1)S(1)} reshape(pred[4194304]{0} %x)",
          "%fusion.7 = u32[11724,1,128]{2,1,0:T(1,128)S(1)} fusion(u32[11724,1,128]{2,1,0} %all-reduce.7), kind=kLoop",
          "%sort.1 = (u32[8]{0}, u32[8]{0}) sort(u32[8]{0} %a, u32[8]{0} %b)",
          "%fusion.2"]
    assert all(meshred.is_collective(line) for line in yes)
    assert not any(meshred.is_collective(line) for line in no)
