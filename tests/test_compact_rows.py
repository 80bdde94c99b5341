"""`ops/relops.py` `compact_rows`: one stable partition by sort whose columns
either ride the sort (`carry`) or are fetched through its permutation
(`gather`), chosen by `compact_form` from the traced shapes alone (PR 43).

(a) both forms against a numpy stable partition and against each other, every
lane, dead ones included; (b) what each form traces to at q12's and q18's SF10
shapes; (c) the rule at every sized compaction point of the three embedded
cells, read from the statements' own traces over `ShapeDtypeStruct`s at the
shipped tiers (nothing of SF1 or SF10 is generated, nothing runs).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_benchmark_caps import (
    CASES, _load, _scan_stand_ins, _shipped_keys, planned_engine)
from trino_tpu.data.page import Dictionary
from trino_tpu.ops import kernels, relops
from trino_tpu.ops.expr import ColumnVal

FORMS = ("carry", "gather")


def _page(case: str, rng):
    """(cols, live, cap) of one case; every array a numpy one."""
    n, cap = 1000, 256
    live = rng.random(n) < 0.2
    i32 = lambda: rng.integers(-2**31, 2**31, n, dtype=np.int64).astype(np.int32)  # noqa: E731
    cols = [ColumnVal(i32(), None), ColumnVal(i32(), None)]
    if case == "valid":
        cols.append(ColumnVal(i32(), rng.random(n) < 0.7))
    elif case == "data2":  # a decimal128: two int64 lanes and a mask
        i64 = lambda: rng.integers(-2**62, 2**62, n, dtype=np.int64)  # noqa: E731
        cols.append(ColumnVal(i64(), rng.random(n) < 0.9, None, None, i64()))
    elif case == "dictionary":
        d = Dictionary(["AIR", "MAIL", "SHIP"])
        cols.append(ColumnVal(rng.integers(0, 3, n).astype(np.int32), None, d))
    elif case == "all_live":
        live = np.ones(n, bool)
    elif case == "all_dead":
        live = np.zeros(n, bool)
    elif case == "overflow":  # more live rows than lanes: required says how many
        live = rng.random(n) < 0.6
    elif case == "pow2_under_odd_n":
        n, cap = 777, 512
        live = rng.random(n) < 0.5
        cols = [ColumnVal(rng.integers(0, 99, n).astype(np.int32), None)] * 2
    elif case == "double_bool_shared":  # NaNs and -0.0 keep their bits; one array in two columns
        f = rng.standard_normal(n)
        f[::7], f[::11] = np.nan, -0.0
        flag = rng.random(n) < 0.5
        cols += [ColumnVal(f, flag), ColumnVal(flag, None), ColumnVal(cols[0].data, flag)]
    elif case == "rows_of_limbs":  # not one lane a row: gathered through an iota that rides
        cols.append(ColumnVal(rng.integers(0, 2**31, (n, 3)).astype(np.int32), None))
    else:
        assert case == "plain"
    return cols, live, cap


def _numpy_partition(cols, live, cap):
    """Live rows first, dead rows after, each in page order; `cap` lanes."""
    order = np.concatenate([np.flatnonzero(live), np.flatnonzero(~live)])[:cap]
    take = lambda a: None if a is None else np.asarray(a)[order]  # noqa: E731
    required = int(live.sum())
    return ([(take(c.data), take(c.valid), take(c.data2)) for c in cols],
            np.arange(cap) < min(required, cap), required)


@pytest.mark.parametrize("case", [
    "plain", "valid", "data2", "dictionary", "all_live", "all_dead", "overflow",
    "pow2_under_odd_n", "double_bool_shared", "rows_of_limbs"])
def test_both_forms_are_the_numpy_stable_partition(case, monkeypatch):
    cols, live, cap = _page(case, np.random.default_rng(43))
    want_cols, want_live, want_required = _numpy_partition(cols, live, cap)
    device = [ColumnVal(jnp.asarray(c.data), None if c.valid is None else jnp.asarray(c.valid),
                        c.dict, c.type, None if c.data2 is None else jnp.asarray(c.data2))
              for c in cols]
    if case == "double_bool_shared":  # the same array object, as a Project leaves it
        device[-1] = ColumnVal(device[0].data, device[2].valid)
    for form in FORMS:
        monkeypatch.setattr(relops, "compact_form", lambda n, cap, words: form)
        got, got_live, required = relops.compact_rows(device, jnp.asarray(live), cap)
        assert int(required) == want_required, form
        np.testing.assert_array_equal(np.asarray(got_live), want_live, err_msg=form)
        for g, c, (data, valid, data2) in zip(got, cols, want_cols):
            assert g.dict is c.dict and g.type is c.type
            for have, want in ((g.data, data), (g.valid, valid), (g.data2, data2)):
                assert (have is None) == (want is None), form
                if want is not None:  # bits, so a NaN equals itself and -0.0 is not 0.0
                    assert have.dtype == want.dtype and have.shape == want.shape, form
                    assert np.asarray(have).tobytes() == want.tobytes(), (form, case)


# --------------------------------------------------------------- structure

def _parent_compact_rows(cols, live, cap):
    """`compact_rows` as it stood before PR 43 (commit ed8954f)."""
    n = live.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    perm = jax.lax.sort([(~live).astype(jnp.int8), iota], num_keys=2,
                        is_stable=True)[-1]
    take = perm[:cap]
    required = jnp.sum(live.astype(jnp.int64))
    out = [
        ColumnVal(
            jnp.take(cv.data, take),
            None if cv.valid is None else jnp.take(cv.valid, take),
            cv.dict,
            cv.type,
            None if cv.data2 is None else jnp.take(cv.data2, take),
        )
        for cv in cols
    ]
    out_live = jnp.arange(cap, dtype=jnp.int64) < jnp.minimum(required, cap)
    return out, out_live, required


def _traced(fn, n, cap, arrays):
    """The jaxpr of `fn` over columns of `arrays` = [(dtype of data, masked?,
    dtype of data2 or None)] at `n` lanes into `cap`."""
    def call(raw, live):
        cols = [ColumnVal(d, v, None, None, d2) for d, v, d2 in raw]
        out, out_live, required = fn(cols, live, cap)
        return [(c.data, c.valid, c.data2) for c in out], out_live, required

    s = lambda dt: jax.ShapeDtypeStruct((n,), dt)  # noqa: E731
    raw = [(s(d), s(jnp.bool_) if masked else None, None if d2 is None else s(d2))
           for d, masked, d2 in arrays]
    return jax.make_jaxpr(call)(raw, s(jnp.bool_))


def _count(jaxpr, primitive):
    return sum(e.primitive.name == primitive for e in jaxpr.jaxpr.eqns)


I32 = (jnp.int32, False, None)
# q12's lineitem below its filters: five int32 columns, no mask; q18's
# subquery: the order key and a decimal(38,2) sum in two limbs under a mask
Q12_COLS = [I32] * 5
Q18_COLS = [I32, (jnp.int64, True, jnp.int64)]


@pytest.mark.parametrize("n,cap,arrays,words,form", [
    (60_000_466, 33_554_432, Q12_COLS, 5, "carry"),
    (33_554_432, 16_777_216, Q12_COLS, 5, "carry"),
    (16_777_216, 4_096, Q18_COLS, 6, "gather"),
    (15_000_000, 4_096, [I32] * 4, 4, "gather"),
])
def test_what_each_form_traces_to(n, cap, arrays, words, form):
    """`carry` is one sort and no gather at all; `gather` is the program the
    parent traced, equation for equation (q18's program differs from the
    parent's by nothing but the recorded event)."""
    events = kernels.begin_capture()
    try:
        jaxpr = _traced(relops.compact_rows, n, cap, arrays)
    finally:
        kernels.end_capture()
    assert events == [("compact", form, f"{n} -> {cap} lanes, {words} words")]
    assert _count(jaxpr, "sort") == 1
    if form == "carry":
        assert _count(jaxpr, "gather") == 0
        (sort,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "sort"]
        # one u32 key (dead flag over the lane number) and every array once
        assert sort.params["num_keys"] == 1 and sort.invars[0].aval.dtype == jnp.uint32
        assert len(sort.invars) == 1 + len(arrays)
    else:
        assert str(jaxpr) == str(_traced(_parent_compact_rows, n, cap, arrays))


# ------------------------------------------------------------------- the rule

@pytest.mark.parametrize("n,cap,words,form", [
    (60_000_466, 33_554_432, 5, "carry"),   # the frame over half the input
    (60_000_466, 4_194_304, 5, "carry"),    # a 14th: measured 448 ms for 703
    (60_000_466, 2_097_152, 5, "gather"),   # a 29th: 448 for 424
    (6_001_215, 262_144, 5, "carry"),       # a 23rd: 27 ms for 44
    (6_001_215, 131_072, 5, "gather"),      # a 46th: 27 for 20
    (60_000_466, 4_096, 1, "carry"),        # one word rides in the permutation's place
    (60_000_466, 4_096, 2, "gather"),
    (6_002_367, 2_048, 6, "gather"),
])
def test_the_rule_by_shape(n, cap, words, form):
    assert relops.compact_form(n, cap, words) == form


# statement -> its sized compaction points in plan order, at the shipped tiers
# (`benchmarks/caps/`): (input lanes, frame, 32-bit words a row, form)
POINTS = {
    ("tpch_sf1_embedded", "q12"): [
        (6_002_367, 2_097_152, 5, "carry"), (2_097_152, 1_048_576, 5, "carry"),
        (1_048_576, 262_144, 5, "carry"), (262_144, 65_536, 5, "carry")],
    ("tpch_sf1_embedded", "q18"): [
        (2_097_152, 2_048, 6, "gather"), (1_500_000, 2_048, 4, "gather")],
    ("tpch_sf10_embedded", "q12"): [
        (60_000_466, 33_554_432, 5, "carry"), (33_554_432, 16_777_216, 5, "carry"),
        (16_777_216, 4_194_304, 5, "carry"), (4_194_304, 1_048_576, 5, "carry")],
    ("tpch_sf10_embedded", "q18"): [
        (16_777_216, 4_096, 6, "gather"), (15_000_000, 4_096, 4, "gather")],
    ("tpch_sf10_embedded_multiway", "q05"): [(15_000_000, 8_388_608, 3, "carry")],
    ("tpch_sf10_embedded_multiway", "q09"): [(2_000_000, 262_144, 2, "carry")],
    # PR 47: lineitem's 60M-lane filters stay pass-throughs (38M and 26M rows keep their tier)
    ("tpch_sf10_embedded_subquery", "q16"): [
        (2_000_000, 1_048_576, 4, "carry"), (100_000, 2_048, 2, "gather")],
    ("tpch_sf10_embedded_subquery", "q20"): [
        (2_000_000, 65_536, 2, "gather"), (8_000_000, 262_144, 3, "gather"),
        (60_000_466, 33_554_432, 4, "carry")],
    ("tpch_sf10_embedded_subquery", "q21"): [(2_097_152, 262_144, 3, "carry")],
    ("tpch_sf10_embedded_subquery", "q22"): [
        (1_500_000, 1_048_576, 3, "carry"), (1_500_000, 1_048_576, 2, "carry"),
        (1_048_576, 524_288, 6, "carry"), (524_288, 131_072, 3, "carry")],
}


@pytest.fixture(scope="module")
def tiny_executor():
    """An executor over SF0.01: its scan pages give each column's dtype,
    mask and dictionary as they are resident; the lanes are lifted."""
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(0.01))
    return engine.executor


@pytest.mark.parametrize("config,name", CASES)
def test_compaction_points_of_the_embedded_cells(config, name, tiny_executor):
    """The statement as the cell plans it, traced (not compiled, not run) at
    the cell's row counts and shipped tiers: the `compact` events of its
    `dispatch` span."""
    from trino_tpu.exec.capcache import _key
    from trino_tpu.exec.compiler import _make_call, _node_ids
    from trino_tpu.plan.nodes import TableScan

    with planned_engine(config) as engine:
        plan = engine.plan("\n".join(_load("templates", f"{name}.json")["text"]))
        stand_ins = _scan_stand_ins(engine, plan)
    key = _key(plan, stand_ins)
    caps = {int(i): c for i, c in _load("caps", _shipped_keys()[key])["entries"][key].items()}
    pages = {
        str(i): jax.tree_util.tree_map(
            lambda a, rows=stand_ins[str(i)].capacity: jax.ShapeDtypeStruct((rows,), a.dtype),
            tiny_executor._scan_page(i, node))
        for i, node in _node_ids(plan).items() if isinstance(node, TableScan)
    }
    call, holder = _make_call(plan, caps, False)
    jax.make_jaxpr(call)(pages)
    want = [f"{form} compact ({n} -> {cap} lanes, {words} words)"
            for n, cap, words, form in POINTS[config, name]]
    assert [k for k in kernels.describe(plan) if " compact " in k] == want
    assert [k for k in holder["dispatch"]["kernels"].split("; ") if " compact " in k] == want
    for n, cap, words, form in POINTS[config, name]:
        assert relops.compact_form(n, cap, words) == form
