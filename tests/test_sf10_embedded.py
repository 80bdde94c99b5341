"""What PR 40 (`tpch_sf10_embedded`, cell `embedded_sf10_joins`) added, at
sizes a test run can hold on the CPU:

(a) planning a join statement makes the TPC-H columns the statement names
    (and what the generator's same pass yields beside them), not every column
    of every relation it sizes;
(b) the configuration's deployment — `benchmarks/entries/embedded.py` set up
    from `configs/tpch_sf10_embedded.json` — answers q12 and q18 as the plain
    numpy reference does, at SF0.1 (the smallest round scale at which q18
    returns rows), and a dropped row is caught;
(c) a run says how full its frames were: `frames` on `device_wait`, the two
    counters, and they agree with the learned tiers;
(d) the two device-memory gauges;
(e) the three readers against fixtures under benchmarks/testdata/.
"""

import json
import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
for p in (BENCH, os.path.join(BENCH, "layer_metrics")):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402  (benchmarks/compare.py)
import loader  # noqa: E402  (benchmarks/loader.py)

from trino_tpu.exec import capcache  # noqa: E402
from trino_tpu.exec import compiler as _compiler  # noqa: E402

CONFIG = "tpch_sf10_embedded"
STATEMENTS = loader.load_json("traffic", "joins_text_1stream.json")["pass"]


def _text(name: str) -> str:
    return loader.sql_text(loader.load_json("templates", f"{name}.json"))


# ------------------------------------------- (a) planning reads what is named

# the columns q12 and q18 name (ISSUE 40) ...
NAMED = {
    "lineitem": {"l_orderkey", "l_quantity", "l_shipdate", "l_commitdate",
                 "l_receiptdate", "l_shipmode"},
    "orders": {"o_orderkey", "o_custkey", "o_totalprice", "o_orderdate",
               "o_orderpriority"},
    "customer": {"c_custkey", "c_name"},
}
# ... and the strings the generator builds in a pass of their own
# (connectors/tpch/generator.py: only when asked)
OWN_PASS = {"l_comment", "o_comment", "o_clerk", "c_comment", "c_phone",
            "c_address", "c_name"}


def _column_files(folder) -> dict:
    out: dict = {}
    for root, _dirs, files in os.walk(folder):
        cols = {f[:-4] for f in files if f.endswith(".npy")}
        if cols:
            out[os.path.basename(root)] = cols
    return out


def test_planning_a_join_statement_generates_the_named_columns_only(tmp_path, monkeypatch):
    from trino_tpu.connectors import tpch
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    monkeypatch.setenv("TRINO_TPU_TPCH_CACHE", str(tmp_path))
    monkeypatch.setattr(tpch, "_TABLES", {})  # nothing loaded, no statistics kept
    monkeypatch.setattr(tpch, "_STATS", {})
    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(0.01))
    made = {}
    for name in STATEMENTS:  # q12, then q18, as the cell warms them
        engine.plan(_text(name))
        made[name] = _column_files(tmp_path)
    # q12 names lineitem and orders: nothing of customer
    assert set(made["q12"]) == {"lineitem", "orders"}, made["q12"]
    assert set(made["q18"]) == set(NAMED), made["q18"]
    for table, cols in made["q18"].items():
        # of the strings that cost a pass of their own, none the statements
        # do not name (q18 names c_name, and planning needs no statistic of it)
        assert cols & OWN_PASS <= NAMED[table], (table, sorted(cols))
        assert not any(c.endswith("_comment") for c in cols)
    # the columns planning asks a statistic of are there
    asked = {c for cols in made["q18"].values() for c in cols}
    assert {"l_orderkey", "l_shipmode", "l_shipdate", "l_commitdate",
            "l_receiptdate", "o_orderkey", "o_custkey", "c_custkey"} <= asked


def test_lineitems_second_stream_is_drawn_without_the_lines(monkeypatch):
    """`l_shipmode` asked for alone (q12's planning, once the numbers are
    files) draws the second stream and the orders' line counts: no pass over
    the lines, and the values `generate_table` gives."""
    from trino_tpu.connectors.tpch import generator

    whole = generator.generate_columns("lineitem", 0.01)

    def no_lines(scale):
        raise AssertionError("the orders-and-lines pass ran")

    monkeypatch.setattr(generator, "_order_lines", no_lines)
    before = generator.PASSES.value("second_stream")
    for asked in (["l_shipmode"], ["l_comment", "l_shipinstruct"]):
        made = generator.generate_columns("lineitem", 0.01, asked)
        assert set(asked) <= set(made) <= set(generator._LINE_STREAM_2)
        for c, column in made.items():
            assert column.values == whole[c].values
            assert (column.codes == whole[c].codes).all()
    assert generator.PASSES.value("second_stream") == before + 2


# ------------------------------------- (b) the deployment against the reference


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """The cell's way in, set up from the configuration's file, at SF0.1, with
    a capacity file of its own; and the tables the reference reads."""
    from trino_tpu.connectors.tpch import tpch_data

    caps = tmp_path_factory.mktemp("caps") / "caps_cache.json"
    mp = pytest.MonkeyPatch()
    mp.setenv("TRINO_TPU_CAPS_CACHE", str(caps))
    mp.setattr(capcache, "_mem", None)
    config = loader.load_json("configs", f"{CONFIG}.json")
    _mix, templates = loader.mix("joins_text_1stream")
    entry = loader.load_module("entries", "embedded").Entry(config, templates, 0.1)
    data = {t: tpch_data(t, 0.1) for t in ("lineitem", "orders", "customer")}
    yield entry, templates, data, config
    entry.close()
    mp.undo()


def _against_reference(entry, templates, data, name) -> dict:
    rows, _ = entry.client(0)(name, None)
    t = templates[name]
    want = loader.load_module("reference", t["reference"]).reference(data)
    assert len(want) > 0
    return compare.compare(rows, want, t["ordered"])


@pytest.mark.parametrize("name", STATEMENTS)
def test_the_deployment_answers_as_the_reference_does(deployment, name):
    entry, templates, data, config = deployment
    assert config["scale_factor"] == 10.0 and config["session"] == {"compile_deadline_s": 0}
    c = _against_reference(entry, templates, data, name)
    limits = config["limits"]
    assert all(c[k] <= limits[k] for k in limits), c


@pytest.mark.parametrize("name", STATEMENTS)
def test_a_dropped_row_is_caught(deployment, name, monkeypatch):
    from trino_tpu.data.page import Page

    real = Page.to_pylist
    monkeypatch.setattr(Page, "to_pylist", lambda self: real(self)[:-1])
    entry, templates, data, config = deployment
    c = _against_reference(entry, templates, data, name)
    assert c["exact_mismatches"] > config["limits"]["exact_mismatches"]


# ------------------------------------------------- (c) frames: lanes and rows


def _flat(spans):
    for s in spans:
        yield s
        yield from _flat(s.children)


def test_q18_reports_lanes_and_live_rows_of_every_sized_node(deployment):
    entry, _templates, _data, _config = deployment
    engine = entry.engine
    plan = engine.plan(_text("q18"))
    nodes = _compiler._node_ids(plan)
    request = entry.client(0)
    for _ in range(3):  # loose, tightened, settled (one more if another test ran it)
        request("q18", None)
    seen = len(entry.spans())
    kinds = ("Aggregate", "Join", "Compact", "TopN")
    lanes0 = {k: _compiler.FRAME_LANES.value(k) for k in kinds}
    live0 = {k: _compiler.FRAME_LIVE_ROWS.value(k) for k in kinds}
    request("q18", None)
    learned = engine.executor._learned_caps[plan]
    waits = [s for s in _flat(entry.spans()[seen:]) if s.name == "device_wait"]
    assert len(waits) == 1  # settled: one program ran, once
    frames = waits[0].attributes["frames"]
    # every sized node that builds a frame, under its kind and pre-order id, at
    # its learned tier; the semi join of the subquery's orderkeys answers off
    # its rank (PR 48): it has a tier and no frame
    (semi,) = [nid for nid, n in nodes.items() if getattr(n, "kind", None) == "semi"]
    assert semi in learned
    assert set(frames) == {
        f"{type(nodes[nid]).__name__}#{nid}" for nid in learned if nid != semi}
    lanes = dict.fromkeys(kinds, 0)
    live = dict.fromkeys(kinds, 0)
    for name, (cap, need) in frames.items():
        kind, nid = name.split("#")
        assert cap == learned[int(nid)]
        # a TopN's need is its radix threshold's ties: none where K holds every row
        assert (kind == "TopN" or need > 0) and need <= cap, frames
        lanes[kind] += cap
        live[kind] += need
    assert {type(nodes[nid]).__name__ for nid in learned} == set(kinds)
    assert {k: _compiler.FRAME_LANES.value(k) - lanes0[k] for k in kinds} == lanes
    assert {k: _compiler.FRAME_LIVE_ROWS.value(k) - live0[k] for k in kinds} == live
    # the subquery's aggregation holds a group an order: 150,000 at SF0.1
    assert frames["Aggregate#13"] == [262144, 150000]
    # and the reader over this very span
    import frame_fill_share

    assert frame_fill_share.share([frames]) == pytest.approx(
        100.0 * sum(live.values()) / sum(lanes.values()))


# --------------------------------------------------------- (d) the two gauges


class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_device_memory_gauges_follow_the_devices_counters(monkeypatch):
    import device_memory_peak_share as reader

    peak, limit = _compiler.DEVICE_MEMORY_PEAK, _compiler.DEVICE_MEMORY_LIMIT
    before = (peak.value(), limit.value())
    try:
        peak.set(0), limit.set(0)
        # the CPU keeps no such counters: nothing moves, nothing to read
        monkeypatch.setattr(_compiler.jax, "local_devices", lambda: [_Device(None)])
        _compiler._note_device_memory()
        assert (peak.value(), limit.value()) == (0.0, 0.0)
        assert reader.read({}) is None
        # the fullest device's peak, beside its own limit
        monkeypatch.setattr(_compiler.jax, "local_devices", lambda: [
            _Device({"peak_bytes_in_use": 3 << 30, "bytes_limit": 16 << 30}),
            _Device({"peak_bytes_in_use": 5 << 30, "bytes_limit": 15 << 30})])
        _compiler._note_device_memory()
        assert (peak.value(), limit.value()) == (5 << 30, 15 << 30)
        assert reader.read({}) == pytest.approx(100.0 * 5 / 15)
    finally:
        peak.set(before[0]), limit.set(before[1])


def test_a_statement_reads_the_devices_memory_when_its_program_has_run(monkeypatch):
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    calls = []
    monkeypatch.setattr(_compiler, "_note_device_memory", lambda: calls.append(1))
    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(0.01))
    engine.execute_page("select count(*) from nation").to_pylist()
    assert calls == [1]


# ------------------------------------------------ (e) the readers, on fixtures


@pytest.fixture(scope="module")
def recorded_frames():
    ctx = loader.load_json("testdata", "embedded_frames.json")
    ctx["trace"]["slice"] = tuple(ctx["trace"]["slice"])
    return ctx, loader.load_json("testdata", "embedded_frames.expected.json")


def test_frame_fill_share_against_the_fixture(recorded_frames):
    ctx, expected = recorded_frames
    read = loader.layer_reader("frame_fill_share")
    assert read(ctx) == pytest.approx(expected["frame_fill_share"], rel=1e-9)
    assert 0 < read(ctx) <= 100
    # a program without the attribute (the parent commit), and no trace
    parent = dict(ctx, spans=[dict(s, attrs={k: v for k, v in s["attrs"].items()
                                              if k != "frames"}) for s in ctx["spans"]])
    assert read(parent) is None
    assert read(dict(ctx, trace=None)) is None
    # only runs that overflowed: nothing converged, nothing to read
    only = dict(ctx, spans=[s for s in ctx["spans"] if s["attrs"].get("overflowed")])
    assert only["spans"] and read(only) is None


def test_device_memory_peak_share_without_the_gauges(monkeypatch):
    from trino_tpu.utils import metrics

    read = loader.layer_reader("device_memory_peak_share")
    monkeypatch.setattr(metrics, "GLOBAL", metrics.MetricsRegistry())  # the parent commit
    assert read({}) is None


def test_device_memory_peak_share_against_the_fixture():
    fixture = loader.load_json("testdata", "device_memory_gauges.json")
    read = loader.layer_reader("device_memory_peak_share")
    peak, limit = _compiler.DEVICE_MEMORY_PEAK, _compiler.DEVICE_MEMORY_LIMIT
    before = (peak.value(), limit.value())
    try:
        for case in fixture["cases"]:
            peak.set(case["peak_bytes"]), limit.set(case["limit_bytes"])
            got = read({})
            if case["share"] is None:
                assert got is None, case
            else:
                assert got == pytest.approx(case["share"], rel=1e-9) and got <= 100
    finally:
        peak.set(before[0]), limit.set(before[1])


def test_sort_device_share_against_the_recorded_slice():
    """The four-chip cell's recording (PR 27) holds sharded q12's sorts."""
    ctx = loader.load_json("testdata", "spmd_q12_q01_exchange.json")
    ctx["trace"]["path"] = os.path.join(BENCH, "testdata", ctx["trace"]["path"])
    ctx["trace"]["slice"] = tuple(ctx["trace"]["slice"])
    expected = loader.load_json("testdata", "spmd_q12_q01_sorts.expected.json")
    read = loader.layer_reader("sort_device_share")
    # the trace's times pass through float32 nanoseconds in ProfileData
    assert read(ctx) == pytest.approx(expected["sort_device_share"], rel=1e-4)
    assert 0 < read(ctx) < 100
    # a trace without a sort (PR 24's recording of served_q06), and none
    no_sort = dict(ctx, trace=dict(ctx["trace"], path=os.path.join(
        BENCH, "testdata", "served_q06_named.xplane.pb")))
    assert read(no_sort) is None
    assert read(dict(ctx, trace=None)) is None


def test_a_sort_is_told_by_its_opcode():
    import sort_device_share as reader

    yes = ["%sort.39 = (s32[6]{0}, s32[6]{0}) sort(s32[6]{0} %a, s32[6]{0} %b), dimensions={0}",
           "%sort.1 = (u32[76800000]{0:T(1024)}, u32[76800000]{0:T(1024)}, s32[76800000]{0:T(1024)})"
           " sort(u32[76800000]{0} %x, u32[76800000]{0} %y, s32[76800000]{0} %z)",
           # as the chip's trace names them at SF10 (PR 40, embedded_sf10_joins)
           "%sort.395 = (s32[76777682]{0:T(1024)}, u32[76777682]{0:T(1024)}, u32[76777682]{0:T(1024)},"
           " s32[76777682]{0:T(1024)}) sort(s32[76777682]{0:T(1024)} %get-tuple-element.1122,"
           " u32[76777682]{0:T(1024)} %iota.2, u32[76777682]{0:T(1024)} %broadcast_in_dim.9,"
           " s32[76777682]{0:T(1024)} %iota.30), dimensions={0}, is_stable=true, to_apply=%region_5.11.clone.clone",
           "%sort.83 = (s8[33554432]{0:T(1024)(128)(4,1)}, s32[33554432]{0:T(1024)})"
           " sort(s8[33554432]{0:T(1024)(128)(4,1)S(1)} %get-tuple-element.452, s32[33554432]{0:T(1024)} %iota.35),"
           " dimensions={0}, is_stable=true, to_apply=%region_4.7",
           "%sort.7 = u32[8]{0} sort(u32[8]{0} %a)",
           "%sort.12"]
    no = ["%fusion.45 = s32[2097152]{0:T(1024)} fusion(s32[6002367]{0} %sort.3), kind=kLoop",
          "%sorted_gather.1 = s32[8]{0} gather(s32[8]{0} %a, s32[8]{0} %sort.2)",
          "%hash_join_probe.1 = s32[11776,128]{1,0} custom-call(s32[11776,128]{1,0} %x)",
          "%fusion.2"]
    assert all(reader.is_sort(line) for line in yes)
    assert not any(reader.is_sort(line) for line in no)
    events = {"/device:TPU:0": [(yes[0], 0.0, 40.0), (no[0], 30.0, 30.0), (yes[3], 80.0, 40.0)]}
    # clipped to [0, 100]: sorts 40 + 20 of a busy 60 + 20
    assert reader.share(events, 0.0, 100.0) == pytest.approx(75.0)
    assert reader.share({"/device:TPU:0": [(no[0], 0.0, 10.0)]}, 0.0, 100.0) is None
