"""Filtering joins sink like the predicates they are (plan/optimizer.py
`push_filters` -> `_sink_filtering_joins`).

A semi / anti / null_anti join (IN, EXISTS, NOT EXISTS, NOT IN) keeps or
drops each row of its left input by that row's key alone, so it commutes
with an inner join over that input.  The planner lays it over the whole FROM
clause; the optimizer moves it, subquery plan and all, to the input that
makes its key — where plan/stats.py estimates that input no larger than the
one it stood on.  Each case asserts where the node stands in the plan, what
`trino_tpu_plan_semi_join_sunk_total` counted, AND that the rows are the
sqlite oracle's.

Reference behaviour being matched: PredicatePushDown.java pushing a
SemiJoinNode's filtering source predicate to the side that produces it.
"""

import json
import os

import pytest

from tests.oracle import assert_rows_equal
from tests.tpch_queries import QUERIES
from trino_tpu.plan.ir import FieldRef, field_refs
from trino_tpu.plan.nodes import (
    Aggregate, Filter, Join, Limit, Project, TableScan, TopN, Window,
    format_plan, walk,
)
from trino_tpu.plan.optimizer import (
    SEMI_JOINS_SUNK, _sink_filtering_joins, optimize,
)
from trino_tpu.plan.serde import plan_to_json

_FILTERING = ("semi", "anti", "null_anti")
_TEMPLATES = os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "benchmarks", "templates"
)


def _benchmark_text(name: str) -> str:
    with open(os.path.join(_TEMPLATES, f"{name}.json")) as f:
        return "\n".join(json.load(f)["text"])  # benchmarks/loader.py sql_text


@pytest.fixture(scope="module")
def engine(tpch_tiny):
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    return eng


def _sunk() -> dict:
    """What /metrics reads: joins moved so far, by kind."""
    return {k: SEMI_JOINS_SUNK.labels(k).value for k in _FILTERING}


def _plan(engine, sql):
    """-> (the optimized plan, joins this planning moved by kind)."""
    before = _sunk()
    plan = engine.plan(sql)
    after = _sunk()
    return plan, {k: after[k] - before[k] for k in after if after[k] != before[k]}


def _joins(plan, kinds):
    return [n for n in walk(plan) if isinstance(n, Join) and n.kind in kinds]


def _under_projects(node):
    while isinstance(node, (Project, Filter)):
        node = node.child
    return node


def _parents_of(plan, target):
    return [n for n in walk(plan) if any(c is target for c in n.children)]


# ------------------------------------------------- (a) the benchmark's q18
def test_benchmark_q18_semi_join_stands_on_orders(engine, oracle):
    sql = _benchmark_text("q18")
    plan, moved = _plan(engine, sql)
    assert moved == {"semi": 1}
    (semi,) = _joins(plan, _FILTERING)
    assert semi.kind == "semi"
    assert isinstance(semi.left, TableScan) and semi.left.table == "orders", (
        format_plan(plan)
    )
    # below BOTH inner joins: orders' filtered rows join customer, then lineitem
    inner = _joins(plan, ("inner",))
    assert len(inner) == 2
    assert all(any(n is semi for n in walk(j.left)) for j in inner)
    # the subquery's plan moved with it, untouched
    assert isinstance(_under_projects(semi.right), Aggregate)
    # EXPLAIN shows where it stands
    lines = engine.explain(sql).splitlines()
    at = next(i for i, line in enumerate(lines) if "Join semi" in line)
    assert "TableScan tpch.orders" in lines[at + 1]
    assert_rows_equal(engine.query(sql), oracle.query(sql), ordered=False)


def test_nothing_moves_without_catalogs(engine):
    """`optimize(plan)` alone has no estimate to adapt by: the join stays
    where the planner laid it."""
    raw = engine.planner.plan(_benchmark_text("q18"))
    before = _sunk()
    plan = optimize(raw)
    assert _sunk() == before
    (semi,) = _joins(plan, _FILTERING)
    assert isinstance(_under_projects(semi.left), Join)


def test_a_second_optimize_moves_nothing(engine):
    """Idempotent: the join stands on the smallest input of its way already,
    and no join lies between it and a smaller one."""
    once = engine.plan(_benchmark_text("q18"))
    before = _sunk()
    twice = optimize(once, engine.catalogs, engine.session)
    assert _sunk() == before
    (semi,) = _joins(twice, _FILTERING)
    assert isinstance(semi.left, TableScan) and semi.left.table == "orders"
    assert plan_to_json(twice) == plan_to_json(once)


# ------------------------------------- (b) the cases of the soundness list
_BIG_ORDERS = (
    "select l_orderkey from lineitem group by l_orderkey"
    " having sum(l_quantity) > 250"
)


def _on_scan(table, kind="semi"):
    """The one filtering join stands directly on `table`'s scan, and the
    counter read one more join of its kind."""

    def check(plan, moved):
        (j,) = _joins(plan, _FILTERING)
        assert j.kind == kind
        assert isinstance(j.left, TableScan) and j.left.table == table
        assert moved == {kind: 1}
        return j

    return check


def _stays_on(node_type, kinds=_FILTERING):
    """The one join of `kinds` stays over a `node_type` (under Projects and
    Filters alone): nothing sank, the counter stands."""

    def check(plan, moved):
        (j,) = _joins(plan, kinds)
        assert isinstance(_under_projects(j.left), node_type)
        assert moved == {}
        return j

    return check


def _all_stay(*lefts):
    """A TPC-H statement whose filtering joins stay where the planner laid
    them: `lefts` are what each stands on (under Projects and Filters), in
    plan order."""

    def check(plan, moved):
        assert moved == {}
        stands = [_under_projects(j.left) for j in _joins(plan, _FILTERING)]
        assert len(stands) == len(lefts)
        for stand, want in zip(stands, lefts):
            if isinstance(want, str):
                assert isinstance(stand, TableScan) and stand.table == want
            else:
                assert isinstance(stand, want)

    return check


def _renamed_key(plan, moved):
    j = _on_scan("orders")(plan, moved)
    # `k` was the derived table's name for o_orderkey: under the Project the
    # key is the scan's own column again
    (key,) = j.left_keys
    assert isinstance(key, FieldRef)
    assert j.left.column_names[key.index] == "o_orderkey"


def _computed_key(plan, moved):
    j = _on_scan("orders")(plan, moved)
    # the Project's expression itself, over the scan's o_orderkey
    (key,) = j.left_keys
    assert not isinstance(key, FieldRef)
    assert [j.left.column_names[i] for i in _field_indices(key)] == ["o_orderkey"]


def _right_of_inner(plan, moved):
    j = _on_scan("orders")(plan, moved)
    (parent,) = _parents_of(plan, j)
    assert isinstance(parent, Join) and parent.kind == "inner"
    assert parent.right is j and _scan_tables(parent.left) == ["lineitem"]


def _scan_tables(node):
    return [n.table for n in walk(node) if isinstance(n, TableScan)]


def _left_of_left_join(plan, moved):
    j = _on_scan("orders")(plan, moved)
    (parent,) = _parents_of(plan, j)
    assert isinstance(parent, Join) and parent.kind == "left" and parent.left is j


def _not_into_null_extended(plan, moved):
    j = _stays_on(Join)(plan, moved)
    assert _under_projects(j.left).kind == "left"


def _null_probe_key(plan, moved):
    j = _on_scan("orders", kind="null_anti")(plan, moved)
    (key,) = j.left_keys
    assert not isinstance(key, FieldRef)  # the CASE itself, over the scan


def _residual_follows(plan, moved):
    j = _on_scan("lineitem")(plan, moved)
    assert j.residual is not None
    # the residual reads (left ++ right): its left half is the scan's own
    # l_suppkey, its right half begins at the scan's width
    nl = len(j.left.output_types)
    refs = _field_indices(j.residual)
    assert len(refs) == 2 and refs[0] < nl <= refs[1]
    assert j.left.column_names[refs[0]] == "l_suppkey"
    assert j.right.output_names[refs[1] - nl] == "l_suppkey"


def _field_indices(e):
    return sorted(field_refs(e))


_CASES = {
    # ---- TPC-H's own statements with a filtering join that must NOT move
    # q04: EXISTS over orders under its date filter: no join to cross
    "tpch_q04_nothing_to_cross": (QUERIES["q04"], _all_stay("orders")),
    # q16: NOT IN over partsupp x (a filtered part): partsupp alone is the
    # larger input
    "tpch_q16_estimate_holds_it": (QUERIES["q16"], _all_stay(Join)),
    # q20: supplier x (the one nation) estimates under supplier; the inner
    # IN stands on partsupp with nothing to cross
    "tpch_q20_estimate_holds_it": (QUERIES["q20"], _all_stay(Join, "partsupp")),
    # q21: both EXISTS tests stand on what survives three joins, far fewer
    # rows than lineitem's
    "tpch_q21_estimate_holds_it": (QUERIES["q21"], _all_stay(Join, Join)),
    # q22: NOT EXISTS over customers richer than the average (a cross join
    # with the scalar subquery, filtered): fewer rows than customer below it
    "tpch_q22_estimate_holds_it": (QUERIES["q22"], _all_stay(Join)),
    # ---- where a join may go
    # through a Project that renames the key, and the inner join above it
    "project_renamed_key": (
        "select k, c_name from (select o_orderkey as k, o_custkey as ck"
        " from orders) o, customer where ck = c_custkey"
        f" and k in ({_BIG_ORDERS})",
        _renamed_key,
    ),
    # through a Project that COMPUTES the key: the expression goes with it
    "project_computed_key": (
        "select k, c_name from (select o_orderkey + 1 as k, o_custkey as ck"
        " from orders) o, customer where ck = c_custkey"
        f" and k in (select l_orderkey + 1 from ({_BIG_ORDERS}) b)",
        _computed_key,
    ),
    # the key's relation is the RIGHT input of the join as written
    "right_side_of_inner_join": (
        "select l_orderkey, l_linenumber, o_totalprice from lineitem"
        " join orders on l_orderkey = o_orderkey"
        f" where o_orderkey in ({_BIG_ORDERS})",
        _right_of_inner,
    ),
    "preserved_side_of_left_join": (
        "select o_orderkey, l_linenumber from orders left join lineitem"
        " on o_orderkey = l_orderkey and l_quantity > 45"
        f" where o_orderkey in ({_BIG_ORDERS})",
        _left_of_left_join,
    ),
    # a filter under the null-extended side would let the join above bring
    # the dropped orders back as NULLs
    "not_into_null_extended_side": (
        "select c_custkey, o_orderkey from customer left join orders"
        " on c_custkey = o_custkey"
        f" where o_orderkey in ({_BIG_ORDERS})",
        _not_into_null_extended,
    ),
    "not_through_aggregate": (
        "select ck, n from (select o_custkey as ck, count(*) as n from orders,"
        " customer where o_custkey = c_custkey group by o_custkey) t"
        " where ck in (select c_custkey from customer where c_acctbal < 0)",
        _stays_on(Aggregate),
    ),
    "not_through_limit": (
        "select o_orderkey, c_name from (select o_orderkey, c_name from orders,"
        " customer where o_custkey = c_custkey limit 1000000) t"
        f" where o_orderkey in ({_BIG_ORDERS})",
        _stays_on(Limit),
    ),
    "not_through_topn": (
        "select o_orderkey, c_name from (select o_orderkey, c_name from orders,"
        " customer where o_custkey = c_custkey order by o_orderkey limit 3000) t"
        f" where o_orderkey in ({_BIG_ORDERS})",
        _stays_on(TopN),
    ),
    # under the window the surviving orders would be numbered 1, 2, ... anew
    "not_through_window": (
        "select o_orderkey, rn from (select o_orderkey, row_number() over"
        " (partition by o_custkey order by o_orderkey) as rn from orders,"
        " customer where o_custkey = c_custkey) t"
        f" where o_orderkey in ({_BIG_ORDERS})",
        _stays_on(Window),
    ),
    # IN under OR is a column (mark join), not a filter: it stays
    "mark_join_unmoved": (
        "select o_orderkey, c_name from orders, customer"
        " where o_custkey = c_custkey"
        f" and (o_orderkey in ({_BIG_ORDERS}) or o_totalprice > 400000)",
        _stays_on(Join, kinds=("mark", "mark_in")),
    ),
    # NOT IN over a set that holds a NULL is never TRUE: no row, wherever
    # the test stands
    "not_in_with_null_in_subquery": (
        "select o_orderkey, c_name from orders, customer"
        " where o_custkey = c_custkey and o_orderkey not in ("
        "select case when l_orderkey % 7 = 0 then null else l_orderkey end"
        " from lineitem where l_quantity > 49)",
        _on_scan("orders", kind="null_anti"),
    ),
    # a NULL probe key is UNKNOWN, so dropped — under the join as above it
    "not_in_with_null_probe_key": (
        "select o_orderkey, c_name from orders, customer"
        " where o_custkey = c_custkey"
        " and (case when o_orderkey % 5 = 0 then null else o_orderkey end)"
        " not in (select l_orderkey from lineitem where l_quantity > 49)",
        _null_probe_key,
    ),
    # TPC-H q21's shape: the residual's left half is remapped with the keys
    "residual_follows_the_join": (
        "select c_name, o_orderkey, l1.l_suppkey from customer, orders,"
        " lineitem l1 where c_custkey = o_custkey"
        " and o_orderkey = l1.l_orderkey and exists (select * from lineitem l2"
        " where l2.l_orderkey = l1.l_orderkey"
        " and l2.l_suppkey <> l1.l_suppkey and l2.l_quantity > 49)",
        _residual_follows,
    ),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_where_the_join_stands_and_what_it_answers(case, engine, oracle):
    sql, check = _CASES[case]
    plan, moved = _plan(engine, sql)
    try:
        check(plan, moved)
    except AssertionError as e:
        raise AssertionError(f"{e}\n{format_plan(plan)}") from e
    assert_rows_equal(engine.query(sql), oracle.query(sql), ordered=False)


# ---------------- (c) plans without a filtering join are the parent's, byte
# for byte: their capacity keys (exec/capcache.py `_key`) and with them their
# programs' compile-cache keys cannot move
_PARENT_PLANS = os.path.join(
    os.path.dirname(__file__), "data", "parent_plans_pr37_sf0.01.json"
)


@pytest.mark.parametrize("name", ["q06", "q01", "q12"])
def test_plans_without_filtering_joins_are_byte_identical_to_parent(engine, name):
    """Literals: `plan_to_json(Engine.plan(text))` at commit 47418f3 (PR 37),
    TPC-H SF0.01, captured before this pass existed.  The counter reads
    nothing for them, and the pass hands back the very nodes it was given."""
    with open(_PARENT_PLANS) as f:
        parent = json.load(f)[name]
    plan, moved = _plan(engine, _benchmark_text(name))
    assert moved == {}
    assert plan_to_json(plan) == parent
    assert _sink_filtering_joins(plan, engine.catalogs) is plan
