"""What PR 42 (`tpch_sf10_embedded_multiway`, cell `embedded_sf10_multiway`)
added, at sizes a test run can hold on the CPU:

(a) the configuration's deployment — `benchmarks/entries/embedded.py` set up
    from `configs/tpch_sf10_embedded_multiway.json` — answers Q5 and Q9 as the
    plain numpy references `reference/q05.py` and `q09.py` do, to the cent, at
    SF0.01; a dropped row is caught; the float32 control of each comes out
    not correct under the configuration's limits at that same scale (the
    smallest round one: Q5 reads 1.0e-7 there, Q9 2.0e-7, against 1e-8);
(b) the `planner` span says what the join order was chosen by (`join_order`,
    `join_estimates`), the run's `frames` hold the rows the same joins made,
    and `trino_tpu_join_rows_total` counts both;
(c) planned from the recorded SF10 statistics, neither statement holds a join
    whose estimated output exceeds ten times its larger input — the order
    SF0.01's statistics give Q5 (suppliers x customers through nation:
    1.2 billion rows at SF10) does;
(d) the two readers, `join_estimate_error` and `join_device_share`.
"""

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

from tests.test_benchmark_caps import planned_engine  # noqa: E402
from trino_tpu.exec import capcache  # noqa: E402
from trino_tpu.exec import compiler as _compiler  # noqa: E402
from trino_tpu.ops.kernels import JOIN_ROWS  # noqa: E402
from trino_tpu.plan import reorder  # noqa: E402
from trino_tpu.plan.nodes import Join, TableScan  # noqa: E402
from trino_tpu.plan.stats import estimate  # noqa: E402

CONFIG = "tpch_sf10_embedded_multiway"
MIX = "multiway_text_1stream"
STATEMENTS = loader.load_json("traffic", f"{MIX}.json")["pass"]
SCALE = 0.01


def _text(name: str) -> str:
    return loader.sql_text(loader.load_json("templates", f"{name}.json"))


# ------------------------------------- (a) the deployment against the reference


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """The cell's way in, set up from the configuration's file, at SF0.01,
    with a capacity file of its own; and the tables the references read."""
    from trino_tpu.connectors.tpch import tpch_data

    caps = tmp_path_factory.mktemp("caps") / "caps_cache.json"
    mp = pytest.MonkeyPatch()
    mp.setenv("TRINO_TPU_CAPS_CACHE", str(caps))
    mp.setattr(capcache, "_mem", None)
    config = loader.load_json("configs", f"{CONFIG}.json")
    _mix, templates = loader.mix(MIX)
    entry = loader.load_module("entries", "embedded").Entry(config, templates, SCALE)
    data = {t: tpch_data(t, SCALE) for tm in templates.values() for t in tm["columns"]}
    yield entry, templates, data, config
    entry.close()
    mp.undo()


def _reference(templates, name):
    return loader.load_module("reference", templates[name]["reference"]).reference


def _against_reference(entry, templates, data, name) -> dict:
    rows, _ = entry.client(0)(name, None)
    want = _reference(templates, name)(data)
    assert len(want) > 0
    return compare.compare(rows, want, templates[name]["ordered"])


def test_the_configuration_is_the_one_the_issue_names(deployment):
    _entry, templates, _data, config = deployment
    assert config["scale_factor"] == 10.0 and config["rehearsal_scale_factor"] == SCALE
    assert config["session"] == {"compile_deadline_s": 0}
    assert config["limits"] == {"exact_mismatches": 0, "decimal_rel_err": 1e-08,
                                "double_rel_err": 3e-08}
    assert sorted(templates) == ["q05", "q09"]
    assert all(t["ordered"] for t in templates.values())
    assert {t for tm in templates.values() for t in tm["columns"]} == {
        "customer", "orders", "lineitem", "supplier", "nation", "region", "part", "partsupp"}


@pytest.mark.parametrize("name", STATEMENTS)
def test_the_deployment_answers_to_the_cent(deployment, name):
    entry, templates, data, _config = deployment
    c = _against_reference(entry, templates, data, name)
    assert c["exact_mismatches"] == 0 and c["decimal_rel_err"] == 0.0, c
    assert c["decimal_cells_inexact"] == 0 and c["rows"] == {"q05": 5, "q09": 175}[name]


@pytest.mark.parametrize("name", STATEMENTS)
def test_a_dropped_row_is_caught(deployment, name, monkeypatch):
    from trino_tpu.data.page import Page

    real = Page.to_pylist
    monkeypatch.setattr(Page, "to_pylist", lambda self: real(self)[:-1])
    entry, templates, data, config = deployment
    c = _against_reference(entry, templates, data, name)
    assert c["exact_mismatches"] > config["limits"]["exact_mismatches"]


@pytest.mark.parametrize("name", STATEMENTS)
def test_the_float32_control_is_not_correct(deployment, name):
    _entry, templates, data, config = deployment
    ref = _reference(templates, name)
    c = compare.compare(ref(data, lowered=True), ref(data), templates[name]["ordered"])
    limits = config["limits"]
    # by the decimal limit, not by each: float32 moves no name, year or row count
    assert c["decimal_rel_err"] > limits["decimal_rel_err"], c
    assert c["exact_mismatches"] == 0 and c["double_rel_err"] == 0.0, c


# ------------------------- (b) what the order was chosen by, and what came out


def _flat(spans):
    for s in spans:
        yield s
        yield from _flat(s.children)


@pytest.mark.parametrize("name", STATEMENTS)
def test_planner_span_holds_the_order_and_its_estimates(deployment, name):
    entry, _templates, _data, _config = deployment
    request = entry.client(0)
    for _ in range(3):  # loose, tightened, settled (fewer if another test ran it)
        request(name, None)
    seen = len(entry.spans())
    before = {w: JOIN_ROWS.value(w) for w in ("estimated", "actual")}
    request(name, None)
    spans = list(_flat(entry.spans()[seen:]))
    planner = [s for s in spans if s.name == "planner"]
    waits = [s for s in spans if s.name == "device_wait"]
    assert len(planner) == 1 and len(waits) == 1
    order = planner[0].attributes["join_order"]
    estimates = planner[0].attributes["join_estimates"]
    frames = waits[0].attributes["frames"]
    # one region of six relations, each a table, five joins with a number each
    assert len(order) == 1 and len(order[0]) == 6
    assert set(order[0]) == set(loader.load_json("templates", f"{name}.json")["columns"])
    plan = entry.engine.plan(_text(name))
    joins = {f"Join#{i}" for i, n in _compiler._node_ids(plan).items() if isinstance(n, Join)}
    assert set(estimates) == joins and len(joins) == 5
    assert all(rows >= 1.0 for rows in estimates.values())
    # the same nodes report the rows they made
    assert joins <= set(frames)
    assert JOIN_ROWS.value("estimated") - before["estimated"] == pytest.approx(
        sum(estimates.values()))
    assert JOIN_ROWS.value("actual") - before["actual"] == sum(
        frames[j][1] for j in joins)


def test_a_statement_without_a_join_says_nothing(deployment):
    entry, _templates, _data, _config = deployment
    seen = len(entry.spans())
    entry.engine.execute_page("select count(*) from nation")
    planner = [s for s in _flat(entry.spans()[seen:]) if s.name == "planner"]
    assert len(planner) == 1
    assert "join_order" not in planner[0].attributes
    assert "join_estimates" not in planner[0].attributes


# ------------------------------------ (c) the plans at SF10's recorded statistics


def _inputs_and_outputs(engine, plan) -> list:
    """[(join id, estimated output, estimated larger input)]: a joined input
    at the cost model's number, a leaf at plan/stats.py's."""
    _order, estimates = reorder.join_estimates(plan, engine.catalogs)
    nodes = _compiler._node_ids(plan)
    out = []
    for nid, rows in estimates.items():
        inputs = [estimates[c] if c in estimates else estimate(nodes[c], engine.catalogs).rows
                  for c in _compiler._child_ids(nodes, nid)]
        out.append((nid, rows, max(inputs)))
    return out


@pytest.fixture(scope="module")
def sf10_engine():
    with planned_engine(CONFIG) as engine:
        yield engine


@pytest.mark.parametrize("name", STATEMENTS)
def test_no_join_is_estimated_past_ten_times_its_larger_input(sf10_engine, name):
    plan = sf10_engine.plan(_text(name))
    checked = _inputs_and_outputs(sf10_engine, plan)
    assert len(checked) == 5
    for nid, rows, larger in checked:
        assert rows <= 10 * larger, (nid, rows, larger)


def test_the_order_of_sf001_would_fail_that_at_sf10(sf10_engine):
    """Q5 as SF0.01's statistics order it — nation, region, supplier, then
    customer through the nation key — costed with SF10's."""
    plan = sf10_engine.plan(_text("q05"))
    root = next(n for n in _compiler._node_ids(plan).values() if reorder.is_ordered_join(n))
    region = reorder._Region(root, lambda n: n, sf10_engine.catalogs)
    at = {next(n.table for n in reorder.walk(r) if isinstance(n, TableScan)): i
          for i, r in enumerate(region.rels)}
    rows, members = region.rel_rows[at["nation"]], frozenset([at["nation"]])
    for table in ("region", "supplier"):
        rows = region.join_rows(rows, members, at[table])
        members |= {at[table]}
    customers = region.rel_rows[at["customer"]]
    blown = region.join_rows(rows, members, at["customer"])
    assert customers == 1_500_000 and blown > 1e9  # 20k suppliers x 60k customers a nation
    assert blown > 10 * max(rows, customers)


# ------------------------------------------------------------ (d) the readers


def test_join_estimate_error_reader():
    reader = loader.load_module("layer_metrics", "join_estimate_error")
    frames = {"Join#5": [4096, 100], "Join#6": [4096, 2000], "Aggregate#2": [1024, 5]}
    # 10x under, 4x over -> factors 10 and 4
    assert sorted(reader.errors([({"Join#5": 10.0, "Join#6": 8000.0}, frames)])) == [4.0, 10.0]
    # a run that overflowed a tier, a plan whose joins the frames lack, no estimates
    assert reader.errors([({"Join#5": 10.0}, {"Join#5": [64, 100]})]) == []
    assert reader.errors([({"Join#9": 10.0}, frames), (None, frames)]) == []

    def span(name, t0, t1, **attrs):
        return {"name": name, "t0": t0, "t1": t1, "attrs": attrs, "depth": 0}

    spans = [
        span("planner", 0.0, 0.1, join_estimates={"Join#5": 10.0, "Join#6": 8000.0}),
        span("device_wait", 0.2, 5.0, frames=frames),
        span("planner", 5.1, 5.2, join_estimates={"Join#3": 50.0}),
        span("device_wait", 5.3, 6.0, frames={"Join#3": [128, 100]}),
        span("planner", 20.0, 20.1, join_estimates={"Join#3": 1.0}),
        span("device_wait", 20.2, 21.0, frames={"Join#3": [128, 100]}),  # after the slice
    ]
    ctx = {"trace": {"slice": (1.0, 15.0)}, "spans": spans}
    assert reader.read(ctx) == 4.0  # median of 10, 4, 2
    assert reader.read({"trace": None, "spans": spans}) is None
    assert reader.read({"trace": {"slice": (1.0, 15.0)}, "spans": [
        span("planner", 0.0, 0.1), span("device_wait", 0.2, 5.0, frames=frames)]}) is None


def test_join_device_share_reader():
    """Against the recorded four-chip slice, whose q12 runs under `Join#7`:
    the share is that of the ops whose innermost scope is the join's, as
    device_attributed_share.py's own walk labels them."""
    reader = loader.load_module("layer_metrics", "join_device_share")
    walk = loader.load_module("layer_metrics", "device_attributed_share")
    path = os.path.join(BENCH, "testdata", "spmd_q12_q01_slice.xplane.pb")
    share = reader.read({"trace": {"path": path}})
    per, _told, busy = walk.by_operator(path)
    joins = sum(s for label, s in per.items() if label.split("/")[0].split(":")[-1].startswith("Join#"))
    assert joins > 0
    assert share == pytest.approx(100.0 * joins / busy, rel=1e-3)
    assert 0.0 < share < 100.0
    # a trace without a join leaves nothing to read
    none = os.path.join(BENCH, "testdata", "served_q06_slice.xplane.pb")
    assert reader.read({"trace": {"path": none}}) is None
    assert reader.read({"trace": None}) is None
