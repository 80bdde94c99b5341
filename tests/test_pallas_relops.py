"""`ops/relops.py` above the Pallas kernels that are left (PR 45: the hash
group-by and hash join kernels went; joins and keyed group-bys sort).

The sorted group-by against a plain Python reference over the live rows —
nulls in keys and arguments, dictionary-coded keys, decimal128 limb
aggregation, empty and all-filtered inputs, a frame nearly full and one
overflowed — what it traces to (sorts, operands, no scatter, no hash
kernel) and what EXPLAIN ANALYZE says of it; then the fused scan
(`ops/pallas/fused.py`) against the operator-at-a-time path the session's
`data_plane_kernels` switch restores: plans, scatter forms, resident operands,
parameters.
"""

import decimal

import numpy as np
import pytest
import jax.numpy as jnp

from trino_tpu.data.types import BIGINT, BOOLEAN, DOUBLE, INTEGER, DecimalType
from trino_tpu.ops import kernels, relops
from trino_tpu.ops.expr import ColumnVal
from trino_tpu.ops.relops import AggSpec


@pytest.fixture(autouse=True)
def _restore_policy():
    yield
    kernels.set_policy(kernels.KernelPolicy())


def _cv(data, valid=None, dict_=None, typ=None, data2=None):
    return ColumnVal(
        jnp.asarray(data),
        None if valid is None else jnp.asarray(valid),
        dict_,
        typ,
        None if data2 is None else jnp.asarray(data2),
    )


# ------------------------------------------- the sorted group-by by itself
#
# What group_aggregate runs for every keyed group-by the direct-code path
# declines: one sort that carries the aggregated columns, one compaction of
# the group ends.  Each case is held to a plain Python reference over the
# live rows.


def _u128(lo, hi):
    return int(hi) * (1 << 64) + int(np.uint64(lo))


def _cell(cv, i):
    """Row i of a test column as a Python value (None for NULL)."""
    if cv.valid is not None and not bool(np.asarray(cv.valid)[i]):
        return None
    d = np.asarray(cv.data)[i]
    if cv.data2 is not None:
        return _u128(d, np.asarray(cv.data2)[i])
    if cv.dict is not None:
        return cv.dict.values[int(d)]
    return d.item()


def _nearest_rank(vals, p):
    vals = sorted(vals)
    return vals[int(np.floor(p * (len(vals) - 1) + 0.5))]


def _reference_agg(spec, arg, arg2, rows):
    if spec.fn == "count_star":
        return len(rows)
    vals = [v for v in (_cell(arg, i) for i in rows) if v is not None]
    if spec.fn == "count":
        return len(set(vals)) if spec.distinct else len(vals)
    if spec.fn == "array_agg":
        return tuple(sorted(vals))
    if spec.fn in ("covar_pop", "corr"):
        pairs = [(_cell(arg, i), _cell(arg2, i)) for i in rows]
        pairs = [(y, x) for y, x in pairs if y is not None and x is not None]
        if not pairs or (spec.fn == "corr" and len(pairs) < 2):
            return None
        y, x = (np.asarray(c, np.float64) for c in zip(*pairs))
        cov = float(np.mean(x * y) - np.mean(x) * np.mean(y))
        if spec.fn == "covar_pop":
            return cov
        den = float(np.std(x) * np.std(y))
        return cov / den if den > 0 else None
    if not vals:
        return None
    if spec.fn == "sum":
        return sum(vals)
    if spec.fn == "avg":
        return float(sum(vals)) / len(vals)
    if spec.fn == "percentile":
        return _nearest_rank(vals, spec.param)
    return {"min": min, "max": max}[spec.fn](vals)


def _reference_groups(keys, args, args2, specs, live):
    groups: dict = {}
    for i in np.flatnonzero(np.asarray(live)):
        groups.setdefault(tuple(_cell(k, i) for k in keys), []).append(i)
    return [
        k + tuple(_reference_agg(s, a, a2, rows)
                  for s, a, a2 in zip(specs, args, args2))
        for k, rows in groups.items()
    ]


def _decoded_groups(out, keys, args, specs):
    out_keys, out_aggs, out_live, n_groups = out
    rows = []
    for g in np.flatnonzero(np.asarray(out_live)):
        row = []
        for (d, v, hi), kv in zip(out_keys, keys):
            got = ColumnVal(d, v, kv.dict, kv.type, hi)
            row.append(_cell(got, g))
        for a, arg, spec in zip(out_aggs, args, specs):
            if len(a) == 4:  # decimal128: (lo, valid, None, hi)
                got = ColumnVal(a[0], a[1], None, None, a[3])
            elif len(a) == 3:  # host-collected: its own dictionary
                got = ColumnVal(a[0], a[1], a[2])
            else:
                d = arg.dict if arg is not None and spec.fn in ("min", "max") else None
                got = ColumnVal(a[0], a[1], d)
            v = _cell(got, g)
            row.append(tuple(sorted(v)) if isinstance(v, tuple) else v)
        rows.append(tuple(row))
    return rows, int(np.asarray(n_groups))


def _same(a, b):
    if isinstance(a, float) or isinstance(b, float):
        return a is not None and b is not None and a == pytest.approx(b, rel=1e-9, abs=1e-9)
    return a == b


def _sorted_case(case):
    """(keys, args, args2, specs, live, G) of one named case."""
    from trino_tpu.data.page import Dictionary
    from trino_tpu.data.types import VARCHAR

    rng = np.random.default_rng(41)
    n, G = 3000, 1024
    d38 = DecimalType(38, 2)
    key = _cv(rng.integers(0, 300, n).astype(np.int32), None, None, INTEGER)
    arg = _cv(rng.integers(-1000, 1000, n), None, None, BIGINT)
    live = np.ones(n, bool)
    keys, args, args2 = [key], [arg], [None]
    specs = [AggSpec("sum")]
    if case == "nulls_in_keys_and_args":
        kvalid = rng.random(n) > 0.1
        keys = [_cv(np.where(kvalid, np.asarray(key.data), 0), kvalid, None, INTEGER)]
        args = [_cv(np.asarray(arg.data), rng.random(n) > 0.15, None, BIGINT)] * 3 + [None]
        specs = [AggSpec("sum"), AggSpec("count"), AggSpec("min"), AggSpec("count_star")]
    elif case == "dead_lanes_interleaved":
        live = rng.random(n) > 0.4
        args, specs = [arg, arg], [AggSpec("sum"), AggSpec("max")]
    elif case == "all_dead_page":
        live = np.zeros(n, bool)
    elif case == "one_group":
        keys = [_cv(np.full(n, 7, np.int32), None, None, INTEGER)]
        args, specs = [arg, None], [AggSpec("sum"), AggSpec("count_star")]
    elif case == "every_row_its_own_group":
        n = 900
        keys = [_cv(rng.permutation(n).astype(np.int32), None, None, INTEGER)]
        args = [_cv(rng.integers(-9, 9, n), None, None, BIGINT)] * 2
        specs, live = [AggSpec("sum"), AggSpec("min")], np.ones(n, bool)
    elif case == "more_groups_than_the_frame":
        keys = [_cv(rng.integers(0, 2500, n).astype(np.int32), None, None, INTEGER)]
    elif case == "decimal128_key":
        keys = [_cv(rng.integers(0, 25, n), None, None, DecimalType(38, 0),
                    data2=rng.integers(-2, 2, n))]
    elif case == "wide_sum_of_an_int32_past_2_31":
        # 3,000 rows of ~2^30 into 5 groups: every total passes 2^31
        keys = [_cv(rng.integers(0, 5, n).astype(np.int32), None, None, INTEGER)]
        args = [_cv(rng.integers(1 << 29, 1 << 30, n).astype(np.int32),
                    None, None, DecimalType(9, 2))]
        specs = [AggSpec("sum", type=d38)]
    elif case == "wide_sum_of_an_int64_past_2_63":
        keys = [_cv(rng.integers(0, 5, n).astype(np.int32), None, None, INTEGER)]
        args = [_cv(rng.integers(-(1 << 61), 1 << 62, n), rng.random(n) > 0.1,
                    None, DecimalType(18, 2))]
        specs = [AggSpec("sum", type=d38)]
    elif case == "wide_sum_of_two_limbs":
        args = [_cv(rng.integers(-(1 << 62), 1 << 62, n), rng.random(n) > 0.1,
                    None, d38, data2=rng.integers(-4, 4, n))]
        specs = [AggSpec("sum", type=d38)]
    elif case == "min_max_over_a_dictionary":
        words = np.asarray([f"w{(i * 37) % 50:02d}" for i in range(50)], object)
        dcol = _cv(rng.integers(0, 50, n).astype(np.int32), rng.random(n) > 0.1,
                   Dictionary(words), VARCHAR)
        args, specs = [dcol, dcol], [AggSpec("min"), AggSpec("max")]
    elif case == "count_distinct":
        darg = _cv(rng.integers(0, 6, n), rng.random(n) > 0.2, None, BIGINT)
        args, specs = [darg, arg], [AggSpec("count", distinct=True), AggSpec("sum")]
        args2 = [None, None]
    elif case == "two_value_sorted_aggregates":
        darg = _cv(rng.integers(0, 6, n), rng.random(n) > 0.2, None, BIGINT)
        args = [darg, arg]
        specs = [AggSpec("count", distinct=True), AggSpec("percentile", param=0.9)]
        args2 = [None, None]
    elif case == "approx_percentile":
        parg = _cv(rng.normal(0, 10, n), rng.random(n) > 0.1, None, DOUBLE)
        args, specs = [parg], [AggSpec("percentile", param=0.5)]
    elif case == "moment_aggregate_with_arg2":
        y = _cv(rng.normal(0, 3, n), rng.random(n) > 0.1, None, DOUBLE)
        x = _cv(rng.normal(1, 2, n), None, None, DOUBLE)
        keys = [_cv(rng.integers(0, 20, n).astype(np.int32), None, None, INTEGER)]
        args, args2 = [y, y], [x, x]
        specs = [AggSpec("covar_pop"), AggSpec("corr")]
    elif case == "host_collected_aggregate":
        keys = [_cv(rng.integers(0, 20, n).astype(np.int32), None, None, INTEGER)]
        args, specs = [arg, arg], [AggSpec("array_agg"), AggSpec("sum")]
        args2 = [None, None]
    elif case == "dictionary_key_beside_a_wide_int64_key":
        # the second key keeps the direct-code path out; its values pass 2^37
        n = 2000
        d = Dictionary(np.asarray([f"v{i}" for i in range(30)], object))
        keys = [_cv(rng.integers(0, 30, n).astype(np.int32), None, d, VARCHAR),
                _cv(rng.integers(0, 8, n) * ((1 << 37) + 12345), None, None, BIGINT)]
        darg = _cv(rng.normal(0, 10, n), None, None, DOUBLE)
        args, specs = [darg, darg], [AggSpec("sum"), AggSpec("avg")]
        live = rng.random(n) > 0.3
    elif case == "decimal128_limb_sum_with_null_arguments":
        n = 1500
        keys = [_cv(rng.integers(0, 20, n), None, None, BIGINT)]
        args = [_cv(rng.integers(-(1 << 62), 1 << 62, n), rng.random(n) > 0.1,
                    None, d38, data2=rng.integers(-4, 4, n))]
        specs = [AggSpec("sum", type=d38)]
        live = rng.random(n) > 0.2
    elif case == "500_keys_in_a_512_frame":
        n, G = 8192, 512
        uniq = rng.integers(-(1 << 60), 1 << 60, 500)
        keys = [_cv(uniq[rng.integers(0, 500, n)], None, None, BIGINT)]
        small = _cv(rng.integers(-50, 50, n), None, None, BIGINT)
        args = [small, small, None]
        specs = [AggSpec("sum"), AggSpec("min"), AggSpec("count_star")]
        live = np.ones(n, bool)
    elif case == "six_aggregates_over_two_keys":
        k2valid = rng.random(n) > 0.1
        keys = [_cv(rng.integers(0, 40, n), None, None, BIGINT),
                _cv(np.where(k2valid, rng.integers(-5, 5, n), 0).astype(np.int32),
                    k2valid, None, INTEGER)]
        narg = _cv(rng.integers(-1000, 1000, n), rng.random(n) > 0.15, None, BIGINT)
        args = [narg] * 5 + [None]
        specs = [AggSpec("sum"), AggSpec("count"), AggSpec("min"),
                 AggSpec("max"), AggSpec("avg"), AggSpec("count_star")]
        live = rng.random(n) > 0.2
    else:
        raise AssertionError(case)
    if len(args2) != len(args):
        args2 = [None] * len(args)
    return keys, args, args2, specs, live, G


_SORTED_CASES = [
    "nulls_in_keys_and_args", "dead_lanes_interleaved", "all_dead_page",
    "one_group", "every_row_its_own_group", "more_groups_than_the_frame",
    "decimal128_key", "wide_sum_of_an_int32_past_2_31",
    "wide_sum_of_an_int64_past_2_63", "wide_sum_of_two_limbs",
    "min_max_over_a_dictionary", "count_distinct",
    "two_value_sorted_aggregates", "approx_percentile",
    "moment_aggregate_with_arg2", "host_collected_aggregate",
    "dictionary_key_beside_a_wide_int64_key",
    "decimal128_limb_sum_with_null_arguments", "500_keys_in_a_512_frame",
    "six_aggregates_over_two_keys",
]


@pytest.mark.parametrize("case", [
    "nulls_in_keys_and_args", "wide_sum_of_an_int64_past_2_63",
    "decimal128_key", "count_distinct",
])
def test_groupby_sorted_path_under_the_kernel_ceiling(case, monkeypatch):
    """Up to 8,192 groups the chip reduces the sorted rows with the Pallas
    seg_reduce kernel (here interpreted) and only the keys are read at the
    group ends: the same answers."""
    from trino_tpu.ops.pallas import segreduce

    monkeypatch.setattr(segreduce, "INTERPRET", True)
    test_groupby_sorted_path_matches_reference(case, reducer="pallas")


@pytest.mark.parametrize("case", _SORTED_CASES)
def test_groupby_sorted_path_matches_reference(case, reducer=None):
    keys, args, args2, specs, live, G = _sorted_case(case)
    kernels.set_policy(kernels.KernelPolicy(enabled=True, interpret=True))
    ev = kernels.begin_capture()
    try:
        out = relops.group_aggregate(
            keys, args, specs, jnp.asarray(live), G, agg_args2=args2)
    finally:
        kernels.end_capture()
    (event,) = [e for e in ev if e[0] == "group_by"]
    assert event[1] == "sort" and "sort carries" in event[2], ev
    assert {e[1] for e in ev if e[0] == "segment_reduce"} == (
        {reducer} if reducer else set()), ev
    got, n_groups = _decoded_groups(out, keys, args, specs)
    want = _reference_groups(keys, args, args2, specs, live)
    assert n_groups == len(want)  # the TRUE count, also past the frame
    assert len(got) == min(len(want), G)
    want = sorted(want, key=repr)
    if len(want) > G:
        # overflow: the executor grows the tier on n_groups; what the frame
        # holds are whole groups of the page all the same
        assert set(map(repr, got)) <= set(map(repr, want))
        return
    got = sorted(got, key=repr)
    for g, w in zip(got, want):
        assert len(g) == len(w) and all(_same(a, b) for a, b in zip(g, w)), (g, w)


def _primitives(jaxpr, acc=None):
    acc = [] if acc is None else acc
    for e in jaxpr.eqns:
        acc.append(e)
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, acc)
    return acc


@pytest.mark.parametrize("nullable_key", [False, True], ids=["key", "nullable-key"])
def test_groupby_sorted_path_holds_its_budget(nullable_key):
    """One int32 key, one decimal(38,2) sum: at most two
    sorts (the group sort carrying the argument, the compaction of the group
    ends carrying the key and the running sum), no scatter, and no gather
    with n lanes on either side; a validity operand rides only when the
    column has a mask; the dispatch event says what was carried."""
    import jax

    n, G = 10_000, 4_096
    wide = DecimalType(38, 2)

    def run(k, kvalid, q, live):
        key = ColumnVal(k, kvalid if nullable_key else None, None, INTEGER)
        arg = ColumnVal(q, None, None, DecimalType(12, 2))
        return relops.group_aggregate(
            [key], [arg], [AggSpec("sum", type=wide)], live, G)

    ev = kernels.begin_capture()
    try:
        jaxpr = jax.make_jaxpr(run)(
            jnp.zeros(n, jnp.int32), jnp.ones(n, bool),
            jnp.zeros(n, jnp.int32), jnp.ones(n, bool))
    finally:
        kernels.end_capture()
    eqns = _primitives(jaxpr.jaxpr)
    names = [e.primitive.name for e in eqns]
    sorts = [e for e in eqns if e.primitive.name == "sort"]
    assert len(sorts) <= 2, names
    assert not any(name.startswith("scatter") for name in names), names
    for e in eqns:
        if e.primitive.name == "gather":
            assert all(v.aval.size < n for v in e.invars[:2]), e
    group_sort, ends = sorts
    # (dead, [key's validity,] key) are keys; the argument rides, at the
    # width it is resident in; no iota, no validity of the argument
    dtypes = [str(v.aval.dtype) for v in group_sort.invars]
    assert dtypes == ["int8"] + ["bool"] * nullable_key + ["int32", "int32"]
    assert group_sort.params["num_keys"] == 2 + nullable_key
    # the ends' positions are the key; the key, its validity where it has
    # one, and ONE running int64 sum ride: 3 (4) words
    assert ends.params["num_keys"] == 1
    assert sorted(str(v.aval.dtype) for v in ends.invars) == sorted(
        ["int32", "int32", "int64"] + ["bool"] * nullable_key)
    (event,) = [e for e in ev if e[0] == "group_by"]
    assert event[1] == "sort"
    assert event[2] == (
        f"cap {G}; sort carries 1 cols, "
        f"ends carry {3 + nullable_key} words")


def test_groupby_under_interpret_traces_no_hash_kernel():
    """A keyed group-by of the shape the hash kernel took (two word-encodable
    keys, a frame of 2,048) with every kernel switched to interpret: the
    program holds no `hash_agg` call, and its one group_by event says sort."""
    import jax

    n, G = 8_192, 2_048

    def run(k1, k2, v, live):
        keys = [ColumnVal(k1, None, None, INTEGER), ColumnVal(k2, None, None, BIGINT)]
        return relops.group_aggregate(
            keys, [ColumnVal(v, None, None, BIGINT)], [AggSpec("sum")], live, G)

    kernels.set_policy(kernels.KernelPolicy(enabled=True, interpret=True))
    ev = kernels.begin_capture()
    try:
        jaxpr = jax.make_jaxpr(run)(
            jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.int64),
            jnp.zeros(n, jnp.int64), jnp.ones(n, bool))
    finally:
        kernels.end_capture()
    assert "hash_agg" not in str(jaxpr)
    assert [e[:2] for e in ev if e[0] == "group_by"] == [("group_by", "sort")]


def test_explain_analyze_says_what_the_sorted_groupby_carried(kernel_engine):
    """q18's subquery aggregation (GROUP BY l_orderkey) under EXPLAIN ANALYZE: the `-- kernel:` line of its dispatch event names
    the operands of the sort and the words of the compaction."""
    from tests.tpch_queries import QUERIES

    ex = [str(r[0]) for r in kernel_engine.execute(
        "EXPLAIN ANALYZE " + QUERIES["q18"])]
    lines = [l for l in ex if l.startswith("-- kernel:") and " group_by " in l]
    assert any(
        "sort group_by (cap " in l and "; sort carries 1 cols, ends carry " in l
        for l in lines
    ), ex


def test_explain_analyze_of_q18_names_one_join_and_one_keyed_groupby(kernel_engine):
    """Every join of q18 prints `sort join` and a `merged` or `scan`
    join_rank beside it, every keyed group-by `sort group_by`; the dispatch
    counter carries no `fallback` and no Pallas join or group-by."""
    from tests.tpch_queries import QUERIES

    ex = [str(r[0]) for r in kernel_engine.execute(
        "EXPLAIN ANALYZE " + QUERIES["q18"])]
    lines = [l[len("-- kernel: "):] for l in ex if l.startswith("-- kernel:")]
    joins = [l for l in lines if l.split()[1] == "join"]
    ranks = [l for l in lines if l.split()[1] == "join_rank"]
    groupbys = [l for l in lines if l.split()[1] == "group_by"]
    assert len(joins) == len(ranks) == 3 and len(groupbys) == 2, lines
    assert [l.split(" build ")[0] for l in joins] == [
        "sort join (semi", "sort join (inner", "sort join (inner"], joins
    assert all(l.split()[0] in ("merged", "scan") for l in ranks), ranks
    assert all(l.startswith("sort group_by (cap ") for l in groupbys), groupbys
    for op in ("group_by", "join", "join_rank", "fused_pipeline", "compact"):
        assert kernels._DISPATCH.value(op, "fallback") == 0
    for op in ("group_by", "join", "join_rank"):
        assert kernels._DISPATCH.value(op, "pallas") == 0


def test_kill_switch_restores_legacy_dispatch():
    rng = np.random.default_rng(37)
    n = 800
    keys = [_cv(rng.integers(0, 10, n), None, None, BIGINT)]
    arg = _cv(rng.integers(0, 100, n), None, None, BIGINT)
    kernels.set_policy(kernels.KernelPolicy(enabled=False))
    ev = kernels.begin_capture()
    try:
        relops.group_aggregate(keys, [arg], [AggSpec("sum")],
                               jnp.ones(n, bool), 512)
    finally:
        kernels.end_capture()
    impls = {e[1] for e in ev if e[0] == "group_by"}
    assert impls == {"sort"}


@pytest.mark.parametrize("side", ["agg", "join"])
def test_a_hash_kernels_limit_is_an_unknown_property(kernel_engine, side):
    """The two knobs went with the kernels (PR 45): setting one fails as any
    unknown name does, through SQL and on the session itself."""
    from trino_tpu.runtime.session import PROPERTIES

    name = f"hash_{side}_kernel_limit"
    assert name not in PROPERTIES
    with pytest.raises(KeyError, match=f"unknown session property: {name}"):
        kernel_engine.session.set(name, "512")
    with pytest.raises(Exception, match="(?i)unknown session property"):
        kernel_engine.execute(f"SET SESSION {name} = 512")


# ------------------------------------------------------- engine-level fused


@pytest.fixture(scope="module")
def kernel_engine(tpch_tiny):
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    return eng


@pytest.mark.parametrize("name", ["q01", "q06"])
def test_fused_pipeline_engine_differential(kernel_engine, name):
    """q01/q06 shapes fuse scan->filter->project->aggregate into one Pallas
    pass; the session kill-switch restores the legacy plan, and both agree
    (f32-matmul partials floor at ~1e-8 relative, same bound as the
    segreduce kernel tier)."""
    from tests.oracle import assert_rows_equal
    from tests.tpch_queries import ORDERED, QUERIES

    eng = kernel_engine
    sql = QUERIES[name]
    eng.session.set("data_plane_kernels", "false")
    legacy = eng.query(sql)
    eng.session.set("data_plane_kernels", "true")
    eng.session.set("pallas_interpret", "true")
    try:
        fused = eng.query(sql)
        ex = eng.execute(f"EXPLAIN ANALYZE {sql}")
    finally:
        eng.session.set("pallas_interpret", "false")
    lines = [r[0] for r in ex if str(r[0]).startswith("-- kernel:")]
    assert any("pallas fused_pipeline" in l for l in lines), lines
    # 6 groups x 15 streams (q01) and 1 x 3 (q06): a select and an add each
    assert any("scatter vpu tile 128" in l for l in lines), lines
    # the fused kernel's f32 partial sums round BY DESIGN (ops/pallas/
    # fused.py accuracy note: ~1e-8 relative at this size, interpreted) —
    # that applies to its decimal outputs too, so compare them under the
    # float tolerance instead of the oracle's exact-Decimal equality
    def _approx(rows):
        return [
            tuple(
                float(v) if isinstance(v, decimal.Decimal) else v
                for v in r
            )
            for r in rows
        ]

    assert_rows_equal(
        _approx(fused), _approx(legacy), ordered=ORDERED[name], rtol=1e-6
    )


def test_fused_pipeline_sees_through_compact():
    """Above 64k scan rows the optimizer wraps scan filters in Compact
    points (plan/optimizer.py); the fused pipeline must still be selected —
    at SF0.01 there are none, which hid that it never fused at real scale."""
    from tests.tpch_queries import QUERIES
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.02))  # 120k lineitem rows
    sql = QUERIES["q06"]
    assert "Compact" in eng.explain(sql)
    eng.session.set("data_plane_kernels", "false")
    (legacy,) = eng.query(sql)
    eng.session.set("data_plane_kernels", "true")
    eng.session.set("pallas_interpret", "true")
    try:
        (fused,) = eng.query(sql)
        ex = eng.execute(f"EXPLAIN ANALYZE {sql}")
    finally:
        eng.session.set("pallas_interpret", "false")
    assert any("pallas fused_pipeline" in str(r[0]) for r in ex)
    assert float(fused[0]) == pytest.approx(float(legacy[0]), rel=1e-6)


def test_fused_dispatch_metric_increments(kernel_engine):
    # dispatch counts at TRACE time, so use a q06 variant no other test has
    # traced (a jit-cache hit would legitimately not re-count)
    sql = """
    select sum(l_extendedprice * l_discount) as revenue
    from lineitem
    where l_shipdate >= date '1995-01-01' and l_quantity < 23
    """
    eng = kernel_engine
    eng.session.set("pallas_interpret", "true")
    try:
        before = kernels._DISPATCH.value("fused_pipeline", "pallas")
        forms = kernels.FUSED_SCATTER.value("vpu"), kernels.FUSED_SCATTER.value("mxu")
        eng.query(sql)
        after = kernels._DISPATCH.value("fused_pipeline", "pallas")
    finally:
        eng.session.set("pallas_interpret", "false")
    assert after > before
    # one keyless program traced: the select-and-add form, never the one-hot
    assert kernels.FUSED_SCATTER.value("vpu") == forms[0] + (after - before)
    assert kernels.FUSED_SCATTER.value("mxu") == forms[1]


# ------------------------------------------------- the fused scan's scatter


def _fused_case(domains, n=17_500, seed=34, v_dtype=np.int32, v_nulls=False):
    """Synthetic scan columns + the recipe of
    `select k.., sum(v), sum(v * w), avg(d), count(*) where q < 40 group by k..`
    -> (recipe, scan_cols, live, numpy columns).  Values are small whole
    numbers (and quarters), so every f32 partial sum is exact whatever the
    scatter's form and a fault shows as a wrong group, not as rounding.
    `v_dtype`: what the money column `v` is resident as; `v_nulls`: it has a
    validity mask (a tenth of its rows NULL; `v` is then zero where NULL in
    the numpy columns handed back, which is what the sums skip)."""
    from trino_tpu.data.page import Dictionary
    from trino_tpu.data.types import VARCHAR
    from trino_tpu.ops.pallas import fused
    from trino_tpu.plan.ir import Call, Const, FieldRef

    rng = np.random.default_rng(seed)
    dec, dec4 = DecimalType(12, 2), DecimalType(18, 4)
    v = rng.integers(-500, 1_600, n).astype(v_dtype)
    w = rng.integers(0, 11, n).astype(np.int32)
    d = rng.integers(-4_000, 4_000, n) / 4.0
    q = rng.integers(0, 50, n).astype(np.int32)
    keys = [rng.integers(0, k, n).astype(np.int32) for k in domains]
    live = rng.random(n) < 0.9
    v_ok = rng.random(n) >= 0.1 if v_nulls else None
    cols = [_cv(v, v_ok, typ=dec), _cv(w, typ=dec), _cv(d, typ=DOUBLE), _cv(q, typ=INTEGER)]
    if v_nulls:
        v = np.where(v_ok, v, 0)
    cols += [
        _cv(k, dict_=Dictionary(np.array([f"k{i}" for i in range(dom)], object)), typ=VARCHAR)
        for k, dom in zip(keys, domains)
    ]
    f = [FieldRef(i, c.type) for i, c in enumerate(cols)]
    recipe, why = fused.plan_pipeline(
        cols,
        [Call("lt", (f[3], Const(40, INTEGER)), BOOLEAN)],
        f[4:],
        ["sum", "sum", "avg", "count_star"],
        [f[0], Call("mul", (f[0], f[1]), dec4), f[2], None],
        [DecimalType(38, 2), DecimalType(38, 4), DOUBLE, BIGINT],
    )
    assert recipe is not None, why
    return recipe, cols, jnp.asarray(live), (v, w, d, q, keys, live)


def _fused_sums(recipe, cols, live):
    """`_fused_case`'s four aggregates off the kernel, interpreted:
    -> (sum(v), sum(v * w), avg(d), count(*)) as numpy arrays over the groups."""
    from trino_tpu.ops.pallas import fused

    totals = fused.run(recipe, cols, live, interpret=True)
    _, aggs, _, _ = fused.assemble(recipe, totals)
    (sv, _, _, _), (svw, _, _, _), (avg, _), (cnt, _) = aggs
    return np.asarray(sv), np.asarray(svw), np.asarray(avg), np.asarray(cnt)


def _numpy_sums(domains, columns, keep):
    v, w, d, q, keys, _ = columns
    domain = int(np.prod(domains)) if domains else 1
    keep = keep & (q < 40)
    code = np.zeros(len(v), np.int64)
    for k, dom in zip(keys, domains):
        code = code * dom + k
    by = lambda x: np.bincount(  # noqa: E731
        code[keep], weights=np.asarray(x, np.float64)[keep], minlength=domain)
    return by(v), by(v.astype(np.int64) * w), by(d), by(np.ones(len(v)))


@pytest.mark.parametrize(
    "domains,tile",
    [((), 128), ((2,), 128), ((3, 2), 128), ((7,), 128), ((128,), 128),
     ((129,), 256), ((32, 16), 512)],
    ids=["keyless", "2", "3x2", "7", "128", "129", "32x16"],
)
def test_fused_scatter_by_domain(monkeypatch, domains, tile):
    """The scatter's width and form follow the key domain (1 = keyless, a
    handful of groups, one lane tile exactly, one group more, the widest the
    planner accepts): against a numpy float64 group-by over a ragged last
    step and dead lanes, counts exactly and sums to 1e-9; and the
    accumulator the kernel hands back is as wide as the rule says."""
    from trino_tpu.ops.pallas import fused

    recipe, cols, live, (v, w, d, q, keys, live_np) = _fused_case(domains)
    domain = int(np.prod(domains)) if domains else 1
    assert recipe.domain == domain
    shapes = []
    recombine = fused._totals
    monkeypatch.setattr(
        fused, "_totals",
        lambda r, out: shapes.append(out.shape) or recombine(r, out),
    )
    totals = fused.run(recipe, cols, live, interpret=True)
    key_codes, aggs, out_live, n_groups = fused.assemble(recipe, totals)
    (shape,) = shapes
    assert shape[-1] == tile == fused.scatter_form(recipe)[1]

    want = dict(zip(("v", "vw", "d", "n"), _numpy_sums(
        domains, (v, w, d, q, keys, live_np), live_np)))
    (sv, _, _, _), (svw, _, _, _), (avg, avg_ok), (cnt, _) = aggs
    np.testing.assert_array_equal(np.asarray(cnt), want["n"].astype(np.int64))
    if domains:
        np.testing.assert_array_equal(np.asarray(out_live), want["n"] > 0)
        assert int(n_groups) == int((want["n"] > 0).sum())
        got_code = np.zeros(domain, np.int64)
        for kc, dom in zip(key_codes, domains):
            got_code = got_code * dom + np.asarray(kc)
        np.testing.assert_array_equal(got_code, np.arange(domain))
    np.testing.assert_allclose(np.asarray(sv), want["v"], rtol=1e-9, atol=0)
    np.testing.assert_allclose(np.asarray(svw), want["vw"], rtol=1e-9, atol=0)
    some = want["n"] > 0
    np.testing.assert_array_equal(np.asarray(avg_ok), some)
    np.testing.assert_allclose(
        np.asarray(avg)[some], want["d"][some] / want["n"][some], rtol=1e-9, atol=1e-12
    )


# ------------------------------------------- the fused scan's operands in place


@pytest.mark.parametrize("masked", [False, True], ids=["count", "mask"])
@pytest.mark.parametrize("n", [1, 8_191, 8_192, 8_193, 17_500, 3 * 8_192 + 127])
def test_fused_scan_reads_columns_in_place(n, masked):
    """Each column goes to the kernel as its own (n,) operand at the dtype it
    is resident in, nothing padded: a page shorter than a sub-chunk, one row
    short of a grid step, a step exactly, one row over, a ragged last block;
    a page with a live mask of its own and one that hands over its row count
    (every row live, rows past the end dead by their index alone)."""
    from trino_tpu.ops.pallas import fused

    domains = (3, 2)
    recipe, cols, live, columns = _fused_case(domains, n=n, seed=n)
    # int32 columns as they are; the float64 one split outside, no cast
    assert sorted(recipe.operands) == [
        ("data", 0), ("data", 1), ("data", 3), ("data", 4), ("data", 5),
        ("hi", 2), ("lo", 2)]
    assert fused.operand_counts(recipe, masked) == (7 + masked, 5)
    live_np = columns[-1] if masked else np.ones(n, bool)
    sv, svw, avg, cnt = _fused_sums(recipe, cols, live if masked else n)
    want_v, want_vw, want_d, want_n = _numpy_sums(domains, columns, live_np)
    np.testing.assert_array_equal(cnt, want_n.astype(np.int64))
    np.testing.assert_array_equal(sv, want_v.astype(np.int64))
    np.testing.assert_array_equal(svw, want_vw.astype(np.int64))
    some = want_n > 0
    np.testing.assert_allclose(avg[some], want_d[some] / want_n[some], rtol=1e-12)


@pytest.mark.parametrize("resident", ["int32-nullable", "int64", "float64"])
def test_fused_scan_operand_follows_resident_dtype(resident):
    """What a money column's operand is follows the dtype it is resident in
    and nothing else: int32 (narrowed on upload) is read as it is and lifted
    in the kernel, its validity mask a cast of its own; int64 (not narrowed)
    and float64 keep the hi/lo f32 split outside the kernel, two operands."""
    from trino_tpu.ops.pallas import fused

    n, domains = 17_500, (2,)
    if resident == "float64":  # a DOUBLE column in v's place: d is one
        recipe, cols, live, columns = _fused_case(domains, n=n)
        assert cols[2].data.dtype == jnp.float64
        _, hi, lo, _, _ = dict(recipe.cols)[2]
        assert (recipe.operands[hi], recipe.operands[lo]) == (("hi", 2), ("lo", 2))
    else:
        recipe, cols, live, columns = _fused_case(
            domains, n=n, v_dtype=np.int64 if resident == "int64" else np.int32,
            v_nulls=resident == "int32-nullable")
        forms = [what for what, ci in recipe.operands if ci == 0]
        assert forms == (["hi", "lo"] if resident == "int64" else ["valid", "data"])
        assert str(cols[0].data.dtype) == resident.split("-")[0]
    n_ops, n_resident = fused.operand_counts(recipe, True)
    assert n_ops == len(recipe.operands) + 1
    assert n_resident == sum(w == "data" for w, _ in recipe.operands) < n_ops
    sv, svw, avg, cnt = _fused_sums(recipe, cols, live)
    want_v, want_vw, want_d, want_n = _numpy_sums(domains, columns, columns[-1])
    np.testing.assert_array_equal(cnt, want_n.astype(np.int64))
    np.testing.assert_array_equal(sv, want_v.astype(np.int64))
    np.testing.assert_array_equal(svw, want_vw.astype(np.int64))
    np.testing.assert_allclose(avg, want_d / want_n, rtol=1e-12)


_LIFT_EDGES = [0, 1, -1, 2**24 + 1, -(2**24 + 1), 2**31 - 64, -(2**31 - 64),
               2**31 - 1, -(2**31 - 1), -(2**31)]


@pytest.mark.parametrize("x", _LIFT_EDGES)
def test_fused_lift_of_an_int32_is_exact(x):
    """The kernel's lift of an int32 to a double-float pair: hi is f32(x)
    rounded to nearest, lo what that lost, for every int32 — also where
    f32(x) is 2^31, which no int32 holds (|x| > 2^31 - 64)."""
    from trino_tpu.ops.pallas.fused import _dd_planes, _lift_i32

    hi, lo = (np.asarray(a) for a in _lift_i32(jnp.full((8, 128), x, jnp.int32)))
    assert hi.dtype == lo.dtype == np.float32
    assert (hi == np.float32(x)).all()
    assert (hi.astype(np.float64) + lo.astype(np.float64) == x).all()
    # the pair the planes made outside the kernel held, to the bit
    phi, plo = _dd_planes(jnp.asarray([x], jnp.int32))
    assert hi[0, 0] == np.asarray(phi)[0] and lo[0, 0] == np.asarray(plo)[0]


def test_fused_scan_sums_the_int32_edges_exactly():
    """The same edges through the kernel: a decimal(10,0) column resident as
    int32 holding them, each its own group, so a group's sum is its value."""
    from trino_tpu.data.page import Dictionary
    from trino_tpu.data.types import VARCHAR
    from trino_tpu.ops.pallas import fused
    from trino_tpu.plan.ir import FieldRef

    vals = np.array(_LIFT_EDGES, np.int64)
    k = len(vals)
    cols = [
        _cv(vals.astype(np.int32), typ=DecimalType(10, 0)),
        _cv(np.arange(k, dtype=np.int32),
            dict_=Dictionary(np.array([f"k{i:02d}" for i in range(k)], object)), typ=VARCHAR),
    ]
    f = [FieldRef(i, c.type) for i, c in enumerate(cols)]
    recipe, why = fused.plan_pipeline(
        cols, [], [f[1]], ["sum", "count_star"], [f[0], None],
        [DecimalType(38, 0), BIGINT])
    assert recipe is not None, why
    assert recipe.operands == (("data", 1), ("data", 0))
    totals = fused.run(recipe, cols, k, interpret=True)
    _, ((total, ok, _, hi), (cnt, _)), _, n_groups = fused.assemble(recipe, totals)
    assert int(n_groups) == k and np.asarray(ok).all()
    np.testing.assert_array_equal(np.asarray(cnt), np.ones(k, np.int64))
    np.testing.assert_array_equal(np.asarray(total), vals)
    np.testing.assert_array_equal(np.asarray(hi), np.where(vals < 0, -1, 0))


# ------------------------------------------------- the fused scan's running sums


def test_fused_group_total_past_2_24_and_2_31_is_exact():
    """One group's integer total passes 2^24 and then 2^31 by its values.
    Each row is under 2^14, so a 1024-row sub-chunk's f32 partial is an exact
    integer; what carries the table is the accumulator across sub-chunks,
    and acc + err has to stay the exact integer past f32's 2^24."""
    import decimal

    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.connectors.spi import ColumnSchema
    from trino_tpu.data.types import VARCHAR, DecimalType
    from trino_tpu.runtime.engine import Engine

    rng = np.random.default_rng(24)
    n = 300_000
    k = np.where(rng.random(n) < 0.9, "big", "small").astype(object)
    v = rng.integers(1, 16_000, n).astype(np.int64)  # hundredths
    conn = MemoryConnector()
    conn.create_table("t", [ColumnSchema("k", VARCHAR), ColumnSchema("v", DecimalType(12, 2))])
    conn.insert("t", {"k": k, "v": v})
    eng = Engine(default_catalog="mem")
    eng.register_catalog("mem", conn)
    eng.session.set("pallas_interpret", "true")
    before = kernels._DISPATCH.value("fused_pipeline", "pallas")
    try:
        rows = eng.query("select k, sum(v), count(*) from t group by k order by k")
    finally:
        eng.session.set("pallas_interpret", "false")
    assert kernels._DISPATCH.value("fused_pipeline", "pallas") == before + 1
    want = [(g, decimal.Decimal(int(v[k == g].sum())).scaleb(-2), int((k == g).sum()))
            for g in ("big", "small")]
    assert int(v[k == "big"].sum()) > 2**31 and 2**24 < int(v[k == "small"].sum()) < 2**31
    assert [tuple(r) for r in rows] == want


def test_fused_accumulator_counts_exactly_past_2_24_rows():
    """The accumulator step alone, fed one count per sub-chunk (0..1024 live
    rows each) for 70,000 sub-chunks, as a 60M-row table feeds it: f32 stops
    holding odd integers at 2^24, acc + err does not."""
    import jax

    from trino_tpu.ops.pallas.fused import _accumulate

    counts = np.random.default_rng(60).integers(0, 1025, 70_000)
    assert counts.sum() > 2 * 2**24

    def step(carry, part):
        return _accumulate(*carry, part), None

    zero = jnp.zeros((), jnp.float32)
    (acc, err), _ = jax.lax.scan(step, (zero, zero), jnp.asarray(counts, jnp.float32))
    assert acc.dtype == err.dtype == jnp.float32
    assert float(acc) != float(counts.sum())  # the f32 sum alone has rounded
    assert int(np.float64(acc) + np.float64(err)) == int(counts.sum())


# ------------------------------------------ prepared statements on the kernel


def _bench(*names):
    """benchmarks/loader.py, traffic.py, compare.py: the templates'
    prepared texts, a binding's literals and reference arguments, and the
    comparison that decides `correct` — what the benchmark's cells use."""
    import importlib
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return [importlib.import_module(n) for n in names]


# the domain's corners (clause 2.4.6.3 / 2.4.1.3) and the validation values
_PREPARED_BINDINGS = {
    "q06": [{"year": 1994, "discount": 6, "quantity": 24},
            {"year": 1993, "discount": 2, "quantity": 24},
            {"year": 1997, "discount": 9, "quantity": 25},
            {"year": 1993, "discount": 9, "quantity": 25},
            {"year": 1997, "discount": 2, "quantity": 24},
            {"year": 1995, "discount": 5, "quantity": 25}],
    "q01": [{"delta": 90}, {"delta": 60}, {"delta": 120}, {"delta": 61},
            {"delta": 119}, {"delta": 75}],
}
# how many of a template's bindings are also sent as text: a text with new
# literals is a new program, and q01's compiles for ~10 s interpreted
_AS_TEXT = {"q06": 6, "q01": 1}


@pytest.mark.parametrize("name", ["q06", "q01"])
def test_prepared_bindings_ride_the_fused_scan_as_scalars(name, tpch_tiny):
    """PREPARE + EXECUTE of the benchmark's prepared texts: every binding is
    answered by ONE compiled program whose fused scan takes the bindings as
    scalar operands, equal to the last digit to the text statement with the
    same literals (one kernel, the values constants there) and within the
    benchmark's limits of the plain numpy reference."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.exec.compilesvc import SERVICE
    from trino_tpu.runtime.engine import Engine

    loader, traffic, compare = _bench("loader", "traffic", "compare")
    t = loader.load_json("templates", name + ".json")
    reference = loader.load_module("reference", t["reference"]).reference
    limits = loader.load_json("configs", "tpch_sf10_served_prepared.json")["limits"]
    data = {"lineitem": tpch_tiny["lineitem"]}
    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    eng.session.set("pallas_interpret", "true")
    eng.execute(f"PREPARE {name} FROM " + loader.sql_text(t, "prepared_text"))
    bindings = [traffic.Binding(t, p) for p in _PREPARED_BINDINGS[name]]
    assert len({b.key for b in bindings}) >= 6
    builds = SERVICE.builds
    scatter = kernels.FUSED_SCATTER.value("vpu")
    got = [eng.execute(f"EXECUTE {name} USING " + ", ".join(b.literals))
           for b in bindings]
    assert SERVICE.builds == builds + 1, "one program per prepared statement"
    assert kernels.FUSED_SCATTER.value("vpu") == scatter + 1
    ex = [r[0] for r in eng.execute(
        f"EXPLAIN ANALYZE EXECUTE {name} USING " + ", ".join(bindings[0].literals))]
    assert SERVICE.builds == builds + 1
    detail = [l for l in ex if l.startswith("-- kernel: pallas fused_pipeline")]
    columns = {"q06": 4, "q01": 7}[name]  # every one resident as int32
    assert len(detail) == 1 and detail[0].endswith(
        f"scatter vpu tile 128 operands {columns} resident {columns} "
        f"params {len(t['sites'])})"), ex
    assert any("plan_cache=hit" in l and f"bound={len(t['sites'])} baked=0" in l
               for l in ex), ex
    for b, rows in zip(bindings, got):
        c = compare.compare(rows, reference(data, *b.args), t["ordered"])
        assert all(c[k] <= limits[k] for k in limits), (b.params, c)
    for b, rows in list(zip(bindings, got))[: _AS_TEXT[name]]:
        sql = loader.sql_text(t, "prepared_text")
        for lit in b.literals:
            sql = sql.replace("?", lit, 1)
        assert eng.execute(sql) == rows, b.params


@pytest.mark.parametrize("name", ["q06", "q01", "execute-q06"])
def test_fused_dispatch_detail_counts_operands_read_in_place(name):
    """`EXPLAIN ANALYZE`'s kernel line says how many row operands the fused
    scan takes and how many are resident arrays read in place — every one for
    a TPC-H statement: the lineitem columns are all resident as int32 and the
    page has no mask — and still ends in `params <n>`, where the benchmark's
    `fused_param_dispatch_share` looks for it; `/metrics` counts the same
    once per traced program, not per execution."""
    import re

    from tests.tpch_queries import QUERIES
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.exec.compilesvc import CompileService
    from trino_tpu.runtime.engine import Engine

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    eng.session.set("pallas_interpret", "true")
    # its own service, so the program is traced here whatever this process
    # holds already: the counters move at trace time
    eng.executor.compile_service = eng._local_fallback.compile_service = CompileService()
    if name == "execute-q06":
        (loader,) = _bench("loader")
        t = loader.load_json("templates", "q06.json")
        eng.execute("PREPARE q06 FROM " + loader.sql_text(t, "prepared_text"))
        sql = "EXECUTE q06 USING DATE '1994-01-01', DATE '1995-01-01', 0.05, 0.07, 24"
        columns, params = 4, 5
    else:
        sql, columns, params = QUERIES[name], {"q06": 4, "q01": 7}[name], 0
    before = {f: kernels.FUSED_OPERANDS.value(f) for f in ("resident", "prepared")}
    eng.execute(sql)
    traced = {f: kernels.FUSED_OPERANDS.value(f) for f in ("resident", "prepared")}
    assert traced == {"resident": before["resident"] + columns,
                      "prepared": before["prepared"]}
    eng.execute(sql)  # the program again, not a trace
    assert {f: kernels.FUSED_OPERANDS.value(f) for f in traced} == traced
    ex = [r[0] for r in eng.execute("EXPLAIN ANALYZE " + sql)]
    (detail,) = [l for l in ex if l.startswith("-- kernel: pallas fused_pipeline")]
    assert detail.endswith(
        f"operands {columns} resident {columns} params {params})"), detail
    # layer_metrics/fused_param_dispatch_share.py's pattern
    got = re.search(r"fused_pipeline \([^)]*\bparams (\d+)", detail)
    assert got and int(got.group(1)) == params
    from trino_tpu.utils import metrics

    text = metrics.GLOBAL.render()
    assert 'trino_tpu_fused_operands_total{form="resident"}' in text


@pytest.mark.parametrize("case", ["varchar", "null", "wide_decimal", "wide_compared"])
def test_prepared_parameter_the_kernel_cannot_take(case, tpch_tiny):
    """What cannot ride as a kernel scalar still answers right: a varchar
    and a NULL are baked per value by the fast path (constants of the plan),
    a BIGINT in arithmetic could pass what a double-float pair holds and
    declines the kernel; under a comparison any BIGINT orders right."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    text, using = {
        "varchar": ("select count(*), sum(l_quantity) from lineitem where l_shipmode = ?"
                    " and l_quantity < ?", "'MAIL', 24"),
        "null": ("select count(*), sum(l_quantity) from lineitem where l_quantity < ?"
                 " or l_discount = ?", "24, NULL"),
        "wide_decimal": ("select sum(l_extendedprice * ?) from lineitem where l_quantity < ?",
                         "3, 24"),
        "wide_compared": ("select count(*), sum(l_quantity) from lineitem where l_quantity < ?",
                          "100000000000000000"),
    }[case]
    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    eng.session.set("pallas_interpret", "true")
    eng.execute("PREPARE p FROM " + text)
    got = eng.execute("EXECUTE p USING " + using)
    ex = [r[0] for r in eng.execute("EXPLAIN ANALYZE EXECUTE p USING " + using)]
    fused = [l for l in ex if l.startswith("-- kernel: pallas fused_pipeline")]
    footer = next(l for l in ex if l.startswith("-- fastpath:"))
    if case == "varchar":  # a constant of the plan; no string predicate fuses
        assert "bound=1 baked=1" in footer and not fused, ex
    elif case == "null":  # a constant NULL of the plan, the number a scalar
        assert "bound=1 baked=1" in footer and fused[0].endswith("params 1)"), ex
    elif case == "wide_decimal":  # a BIGINT factor: off the kernel, one scalar of the program
        assert "bound=2 baked=0" in footer and not fused, ex
    else:
        assert "bound=1 baked=0" in footer and fused[0].endswith("params 1)"), ex
    sql = text
    for lit in using.split(", "):
        sql = sql.replace("?", lit, 1)
    eng.session.set("data_plane_kernels", "false")
    want = eng.execute(sql)
    assert got == want, (got, want)
    if case == "wide_compared":
        assert got[0][0] == len(tpch_tiny["lineitem"]["l_quantity"])
