"""What PR 47 (`tpch_sf10_embedded_subquery`, cell `embedded_sf10_subquery`)
added, at sizes a test run can hold on the CPU:

(a) the configuration's deployment — `benchmarks/entries/embedded.py` set up
    from `configs/tpch_sf10_embedded_subquery.json` — answers Q16, Q20, Q21
    and Q22 as the plain numpy references `reference/q16.py` ... `q22.py` do,
    to the cell, at SF0.01, AND each reference equals the sqlite oracle: the
    reference is checked too; a dropped row is caught; the float32 control of
    the cell comes out not correct, by Q22's decimal sum alone;
(b) SQL's three-valued NOT IN, the one guarantee the configuration adds: a
    NULL key on the build side and on the probe side, program and
    `reference/q16.py` `not_in` against the standard's rule;
(c) the `planner` span says what the subqueries became (`subqueries`,
    `join_kinds`), a statement without a join says nothing, the dispatch text
    names a join's kind and its residual, and
    `trino_tpu_plan_subqueries_total` counts the forms;
(d) planned from the recorded SF10 statistics, every filtering join stands
    where its estimated expansion stays under 2^27 lanes;
(e) the reader `filtering_join_device_share`.
"""

import dataclasses
import os
import re
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
from trino_tpu.plan.nodes import Join, TableScan, walk  # noqa: E402
from trino_tpu.plan.planner import SUBQUERIES, join_kinds  # noqa: E402
from trino_tpu.plan.stats import estimate  # noqa: E402

CONFIG = "tpch_sf10_embedded_subquery"
MIX = "subquery_text_1stream"
STATEMENTS = loader.load_json("traffic", f"{MIX}.json")["pass"]
SCALE = 0.01
FILTERING = ("semi", "anti", "null_anti", "mark", "mark_in")


def _text(name: str) -> str:
    return loader.sql_text(loader.load_json("templates", f"{name}.json"))


def _flat(spans):
    for s in spans:
        yield s
        yield from _flat(s.children)


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
    sibling = loader.load_json("configs", "tpch_sf10_embedded_multiway.json")
    assert config["scale_factor"] == 10.0 and config["rehearsal_scale_factor"] == SCALE
    for key in ("layout", "session", "limits"):
        assert config[key] == sibling[key]
    assert config["limits"] == {"exact_mismatches": 0, "decimal_rel_err": 1e-08,
                                "double_rel_err": 3e-08}
    # the sibling's guarantees, the references named anew, and one more
    assert config["guarantees"][:5] == sibling["guarantees"][:5]
    assert sum("three-valued NOT IN" in g for g in config["guarantees"]) == 1
    assert len(config["guarantees"]) == len(sibling["guarantees"]) + 1
    assert STATEMENTS == ["q16", "q20", "q21", "q22"]
    assert all(t["ordered"] and t["order_note"] for t in templates.values())
    assert {t for tm in templates.values() for t in tm["columns"]} == {
        "customer", "orders", "lineitem", "supplier", "nation", "part", "partsupp"}
    cell = next(w for w in loader.benchmark()["workloads"]
                if w["name"] == "embedded_sf10_subquery")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)


ROWS = {"q16": 307, "q20": 3, "q21": 4, "q22": 7}


@pytest.mark.parametrize("name", STATEMENTS)
def test_the_deployment_answers_to_the_cell(deployment, name):
    entry, templates, data, _config = deployment
    c = _against_reference(entry, templates, data, name)
    assert c["exact_mismatches"] == 0 and c["decimal_rel_err"] == 0.0, c
    assert c["decimal_cells_inexact"] == 0 and c["rows"] == ROWS[name]


@pytest.fixture(scope="module")
def oracle(deployment):
    from tests.oracle import SqliteOracle

    return SqliteOracle(deployment[2])


@pytest.mark.parametrize("name", STATEMENTS)
def test_the_reference_equals_the_sqlite_oracle(deployment, oracle, name):
    """The reference is independent of the program, so it is checked too:
    against sqlite over the same tables, which reads the statement's text."""
    _entry, templates, data, _config = deployment
    want = _reference(templates, name)(data)
    c = compare.compare(oracle.query(_text(name)), want, templates[name]["ordered"])
    assert c["exact_mismatches"] == 0 and c["rows"] == ROWS[name], c
    assert c["decimal_rel_err"] < 1e-12, c  # sqlite sums money in doubles


@pytest.mark.parametrize("name", STATEMENTS)
def test_a_dropped_row_is_caught(deployment, name, monkeypatch):
    from trino_tpu.data.page import Page

    real = Page.to_pylist
    monkeypatch.setattr(Page, "to_pylist", lambda self: real(self)[:-1])
    entry, templates, data, config = deployment
    c = _against_reference(entry, templates, data, name)
    assert c["exact_mismatches"] > config["limits"]["exact_mismatches"]


def test_the_float32_control_is_not_correct_by_q22(deployment):
    """Q22's `sum(c_acctbal)` is the cell's one decimal: float32 moves it past
    the limit and moves nothing else — no count, no name, no row.  At SF0.1,
    the smallest round scale at which a code's balances no longer sum exactly
    in 24 bits (at SF0.01 six or eight balances a code do: 4.7M hundredths)."""
    from trino_tpu.connectors.tpch import tpch_data

    _entry, templates, data, config = deployment
    limits = config["limits"]
    for name in STATEMENTS:
        ref = _reference(templates, name)
        tables = data if name != "q22" else {t: tpch_data(t, 0.1) for t in templates[name]["columns"]}
        c = compare.compare(ref(tables, lowered=True), ref(tables), templates[name]["ordered"])
        assert c["exact_mismatches"] == 0 and c["double_rel_err"] == 0.0, (name, c)
        if name == "q22":  # by the decimal limit, not by each
            assert c["decimal_rel_err"] > limits["decimal_rel_err"], c
        else:
            assert c["decimal_rel_err"] == 0.0, (name, c)


# ------------------------------------------------ (b) the three-valued NOT IN


@pytest.fixture()
def memory_engine():
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.runtime.engine import Engine

    eng = Engine(default_catalog="memory")
    eng.register_catalog("memory", MemoryConnector())
    return eng


@pytest.mark.parametrize("probe,build,what", [
    ([1, 2, 3, None], [2, 5], "a NULL probe key is not kept"),
    ([1, 2, 3], [2, None], "a NULL among the members keeps no row"),
    ([1, None, 3], [None], "both"),
    ([1, None, 3], [], "over no member every row is kept, the NULL one too"),
    ([1, 2, 3], [2, 5], "no NULL: the plain anti join"),
])
def test_not_in_with_a_null_key_on_either_side(memory_engine, probe, build, what):
    """Program and reference against the standard's rule, spelled out here:
    `x NOT IN (S)` is TRUE iff S is empty, or x is not NULL, no member of S
    is NULL and none equals x."""
    import numpy as np

    def sql_values(vals):
        return ", ".join(f"({'null' if v is None else v})" for v in vals)

    eng = memory_engine
    eng.execute("create table probe (x bigint)")
    eng.execute("create table build (y bigint)")
    eng.execute(f"insert into probe values {sql_values(probe)}")
    if build:
        eng.execute(f"insert into build values {sql_values(build)}")
    rule = [x for x in probe
            if not build or (x is not None and None not in build and x not in build)]
    got = eng.execute("select x from probe where x not in (select y from build)")
    assert sorted(got, key=repr) == sorted([(x,) for x in rule], key=repr), what
    assert list(join_kinds(eng.plan(
        "select x from probe where x not in (select y from build)")).values()) == ["null_anti"]

    not_in = loader.load_module("reference", "q16").not_in

    def column(vals):
        return (np.asarray([0 if v is None else v for v in vals], np.int64),
                np.asarray([v is None for v in vals], np.bool_))

    (keys, keys_null), (members, members_null) = column(probe), column(build)
    kept = not_in(keys, members, keys_null, members_null)
    assert [x for x, k in zip(probe, kept) if k] == rule, what


# ------------------------------ (c) what the subqueries became, span and text

SUBQUERY_FORMS = {
    "q16": ["not_in"],
    "q20": ["in", "in", "scalar_correlated"],
    "q21": ["exists", "not_exists"],
    "q22": ["scalar_uncorrelated", "not_exists"],
}
KINDS = {  # the filtering, marking and cross joins of each plan at SF0.01
    "q16": ["null_anti"],
    "q20": ["semi", "semi"],
    "q21": ["anti+residual", "semi+residual"],
    "q22": ["anti", "cross"],
}


FILTER_FORMS = {  # `join_filter` events of each program (ops/kernels.py), sorted
    "q16": ["rank"],
    "q20": ["rank", "rank"],
    "q21": ["minmax", "minmax"],
    "q22": ["rank"],
}


@pytest.mark.parametrize("name", STATEMENTS)
def test_planner_span_holds_the_subqueries_and_the_join_kinds(deployment, name):
    entry, _templates, _data, _config = deployment
    request = entry.client(0)
    before = {f: SUBQUERIES.value(f) for f in set(SUBQUERY_FORMS[name])}
    seen = len(entry.spans())
    request(name, None)
    spans = list(_flat(entry.spans()[seen:]))
    planner = [s for s in spans if s.name == "planner"]
    assert len(planner) == 1
    attrs = planner[0].attributes
    assert attrs["subqueries"] == SUBQUERY_FORMS[name]
    for form in before:
        assert SUBQUERIES.value(form) - before[form] == SUBQUERY_FORMS[name].count(form)
    plan = entry.engine.plan(_text(name))
    joins = {f"Join#{i}": n for i, n in _compiler._node_ids(plan).items()
             if isinstance(n, Join)}
    kinds = attrs["join_kinds"]
    assert set(kinds) == set(joins)
    for nid, said in kinds.items():
        n = joins[nid]
        assert said.split("+")[0] == n.kind
        assert said.endswith("+residual") == (n.residual is not None)
    assert sorted(k for k in kinds.values() if k != "inner") == KINDS[name]
    # the dispatch text tells the same joins apart, and sizes them
    dispatch = [s for s in spans if s.name == "dispatch"]
    assert len(dispatch) == 1
    text = dispatch[0].attributes["kernels"]
    for said in kinds.values():
        if said != "cross":  # a cross join broadcasts one row: no equi_join
            assert f"sort join ({said} build " in text, (said, text)
    assert text.count("sort join (") == sum(k != "cross" for k in kinds.values())
    # how each filtering join found its answer (PR 48): off the merged rank
    # (`rank`), off its key run's min and max (`minmax`: q21's `ne`), never
    # through a frame here
    # ... but where SF0.01's tiers leave a join so few probes that `rank_form`
    # searches (q20's Join#2 at its first, loose tiers: 128 probes, 32,768
    # build lanes): their frame is as small as they are, and stays
    taken = re.findall(r"(\w+) join_rank \([^)]*\); (\w+) join_filter \(", text)
    assert len(taken) == len(FILTER_FORMS[name]), text
    assert sorted(form for rank, form in taken if rank == "merged") == \
        FILTER_FORMS[name][:sum(rank == "merged" for rank, _ in taken)], text
    assert all(form == "frame" for rank, form in taken if rank == "scan"), text
    for said in kinds.values():
        if said.split("+")[0] in FILTERING:
            assert f" join_filter ({said} " in text, (said, text)
    # a join that builds an expansion frame is among the run's frames; one that
    # built none has no need to report and is not
    frames = next(s for s in spans if s.name == "device_wait").attributes["frames"]
    framed = {nid for nid, said in kinds.items() if said.split("+")[0] not in FILTERING + ("cross",)}
    assert framed <= set(frames)
    unframed = sum(form != "frame" for _rank, form in taken)
    assert len(set(kinds) - set(frames)) == unframed + sum(k == "cross" for k in kinds.values())


def test_a_statement_without_a_join_says_nothing(deployment):
    entry, _templates, _data, _config = deployment
    seen = len(entry.spans())
    entry.engine.execute_page(_text("q06"))
    planner = [s for s in _flat(entry.spans()[seen:]) if s.name == "planner"]
    assert len(planner) == 1
    assert "subqueries" not in planner[0].attributes
    assert "join_kinds" not in planner[0].attributes


def test_explain_analyze_names_kind_and_residual(deployment):
    entry, _templates, _data, _config = deployment
    text = "\n".join(str(r[0]) for r in entry.engine.execute(
        "explain analyze " + _text("q21")))
    kernel = [line for line in text.splitlines() if line.startswith("-- kernel:")]
    assert any("sort join (semi+residual build " in line for line in kernel), text
    assert any("sort join (anti+residual build " in line for line in kernel), text
    assert any("sort join (inner build " in line for line in kernel), text
    # and how each of the two answered: `ne` asked of the run's min and max
    assert any("minmax join_filter (semi+residual " in line and "ne of the run" in line
               for line in kernel), text
    assert any("minmax join_filter (anti+residual " in line for line in kernel), text
    assert not any(" join_filter (inner" in line for line in kernel), text


# ------------------------------------ (d) the plans at SF10's recorded statistics

# name -> {join: (kind, the tables of its probe side, of its build side)}: where
# `push_filters` leaves each filtering join at SF10's statistics (PERF.md section 4)
SF10_FILTERING = {
    "q16": {"Join#4": ("null_anti", {"partsupp", "part"}, {"supplier"})},
    "q20": {"Join#2": ("semi", {"supplier", "nation"}, {"partsupp", "part", "lineitem"}),
            "Join#13": ("semi", {"partsupp"}, {"part"})},
    "q21": {"Join#6": ("anti+residual", {"supplier", "nation", "lineitem"}, {"lineitem"}),
            "Join#8": ("semi+residual", {"supplier", "nation", "lineitem"}, {"lineitem"})},
    "q22": {"Join#5": ("anti", {"customer"}, {"orders"})},
}


@pytest.fixture(scope="module")
def sf10_engine():
    with planned_engine(CONFIG) as engine:
        yield engine


def _tables(node) -> set:
    return {n.table for n in walk(node) if isinstance(n, TableScan)}


@pytest.mark.parametrize("name", STATEMENTS)
def test_filtering_joins_stay_under_2_27_lanes_at_sf10(sf10_engine, name):
    """A filtering join's frame holds every hash match of its probe rows: as
    many lanes as the inner join of the same sides would make.  Estimated as
    `plan/stats.py` sizes that inner join, from the recorded statistics."""
    plan = sf10_engine.plan(_text(name))
    kinds = join_kinds(plan)
    found = {}
    for i, n in _compiler._node_ids(plan).items():
        if isinstance(n, Join) and n.kind in FILTERING:
            as_inner = dataclasses.replace(n, kind="inner", residual=None)
            lanes = estimate(as_inner, sf10_engine.catalogs).rows
            found[f"Join#{i}"] = (kinds[f"Join#{i}"], _tables(n.left), _tables(n.right))
            assert lanes < 2 ** 27, (name, i, lanes)
    assert found == SF10_FILTERING[name]


# ------------------------------------------------------------ (e) the reader


def test_filtering_join_device_share_reader():
    """Against the recorded ops of a traced chip run of the cell (a pass's
    joins among their neighbours, each op by its innermost `Join#<id>` scope
    and its time, and the requests with the `join_kinds` their planner spans
    said): an op is a filtering join's when the request it ran in says so."""
    reader = loader.load_module("layer_metrics", "filtering_join_device_share")
    fixture = loader.load_json("testdata", "embedded_sf10_subquery_ops.json")
    expected = loader.load_json("testdata", "embedded_sf10_subquery_ops.expected.json")
    planes = [[tuple(e) for e in plane] for plane in fixture["planes"]]
    requests = [tuple(r) for r in fixture["requests"]]
    share, by_join = reader.shares(planes, requests)
    assert share == pytest.approx(expected["filtering_join_device_share"], rel=1e-9)
    assert {k: v / 1e9 for k, v in by_join.items()} == pytest.approx(expected["seconds_by_join"])
    assert 0.0 < share < 100.0
    # the same ids under another statement's kinds: an inner join is not counted
    kinds = {"Join#4": "null_anti", "Join#5": "inner", "Join#6": "anti+residual"}
    ops = [("Join#4", 0, 10), ("Join#5", 10, 30), (None, 30, 40), ("Join#6", 50, 60),
           ("Join#6", 95, 99), ("Join#7", 60, 70)]
    share, by_join = reader.shares([ops], [(0, 80, "qa", kinds)])
    assert share == pytest.approx(100.0 * 20 / 64)  # the op at 95 ran in no request
    assert by_join == {"qa Join#4 null_anti": 10, "qa Join#6 anti+residual": 10}
    # two open requests that give the id different kinds: not resolved
    both = [(0, 80, "qa", kinds), (0, 80, "qb", {"Join#4": "inner", "Join#6": "anti+residual"})]
    assert reader.shares([ops], both)[1] == {"qa Join#6 anti+residual": 10}
    # requests that say nothing (the parent's spans), traces without spans, no trace
    assert reader.shares([ops], []) == (None, {})
    older = os.path.join(BENCH, "testdata", "spmd_q12_q01_slice.xplane.pb")
    window = {"path": older, "window_s": 1.0, "slice": (0.0, 1.0)}
    assert reader.read({"trace": window, "spans": [], "records": []}) is None
    record = {"t0": 0.0, "t1": 1e9, "template": "q12", "error": None}
    assert reader.read({"trace": window, "records": [record], "spans": [
        {"name": "planner", "t0": 0.5, "t1": 0.6, "attrs": {"join_estimates": {}}}]}) is None
    assert reader.read({"trace": None}) is None


# ---------------------------------------- (c') the served paths say the same


def test_coordinator_and_fast_path_say_what_they_planned():
    """A text statement through client -> coordinator, and a prepared one
    through the fast path: the `planner` span of whoever planned carries the
    subqueries and the join kinds; a plan taken from the cache says nothing
    (nobody planned)."""
    import time

    from trino_tpu.client.client import StatementClient
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.testing.runner import DistributedQueryRunner
    from trino_tpu.utils.tracing import InMemorySpanExporter

    runner = DistributedQueryRunner(num_workers=1)
    runner.register_catalog("tpch", TpchConnector(SCALE))
    runner.start()
    try:
        coord = runner.coordinator
        coord.session.set("result_cache_enabled", "false")
        exporter = InMemorySpanExporter()
        coord.tracer.add_exporter(exporter)
        client = StatementClient(runner.client_url)
        client.prepared["rich"] = (
            "select count(*) from orders where o_totalprice > ? and o_custkey not in "
            "(select c_custkey from customer where c_acctbal < 0)")
        planners = []
        for sql in (_text("q22"), "EXECUTE rich USING 1000.00", "EXECUTE rich USING 2000.00"):
            _cols, rows = client.execute(sql)
            assert rows
            qid = client.last_query_id
            deadline = time.time() + 5.0
            while time.time() < deadline:
                query = [s for s in exporter.snapshot()
                         if s.name == "query" and s.attributes.get("query_id") == qid]
                if query and query[0].find("commit") is not None:
                    break
                time.sleep(0.01)
            planners.append(query[0].find("planner").attributes)
    finally:
        runner.stop()
    text, miss, hit = planners
    assert text["subqueries"] == ["scalar_uncorrelated", "not_exists"]
    assert sorted(text["join_kinds"].values()) == ["anti", "cross"]
    assert miss["plan_cache"] == "miss" and miss["subqueries"] == ["not_in"]
    assert list(miss["join_kinds"].values()) == ["null_anti"]
    assert hit["plan_cache"] == "hit"
    assert "subqueries" not in hit and "join_kinds" not in hit
