"""`ops/relops.py`: the sort join ranks once (PR 44).  `equi_join`'s bounds
come from ONE merged sort of build ++ probe hashes and a sort home
(`_merged_bounds`), its expansion index from a scatter and a running maximum
(`expand_rows`), and
`rank_form` picks between either and a binary search from BOTH sizes.

(a) the bounds and the expansion against `numpy.searchsorted`, every lane;
(b) `equi_join` of every kind against a copy of the parent's form (three
`searchsorted` calls by sort), every lane of every output array, dead ones
included; (c) what the sort path traces to at the cells' shapes; (d) the rule
by shape.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.data.types import BIGINT
from trino_tpu.ops import kernels, relops
from trino_tpu.ops.expr import ColumnVal

FORMS = ("merged", "scan")
SENT_B, SENT_P = relops._SENT_BUILD, relops._SENT_PROBE


def _force(monkeypatch, form):
    monkeypatch.setattr(relops, "rank_form", lambda keys, queries: form)


# ------------------------------------------------------------------ (a)

def _hashes(case: str, rng):
    """(build hashes, probe hashes) as `_combined_hash` leaves them: int63
    values, dead and NULL-keyed rows at their side's sentinel."""
    nr, nl = 700, 900
    draw = lambda n, hi=300: rng.integers(0, hi, n, dtype=np.int64)  # noqa: E731
    bh, ph = draw(nr), draw(nl)
    if case == "dead_rows":
        bh[rng.random(nr) < 0.3] = SENT_B
        ph[rng.random(nl) < 0.3] = SENT_P
    elif case == "sentinels_only_probe":  # every probe dead: nothing matches
        ph[:] = SENT_P
    elif case == "empty_build":  # no live build row
        bh[:] = SENT_B
    elif case == "unique_keys":
        bh, ph = rng.permutation(nr).astype(np.int64), rng.permutation(nl).astype(np.int64)
    elif case == "one_key":  # one run as long as the build side
        bh[:], ph[:] = 7, 7
        ph[::3] = 8
    elif case == "wide_hashes":  # the top of the int63 range, under the sentinels
        bh, ph = draw(nr, 50) + (1 << 62) - 50, draw(nl, 50) + (1 << 62) - 50
    elif case == "few_probes":
        ph = ph[:5]
    elif case == "one_build_row":
        bh = bh[:1]
    else:
        assert case == "duplicates"
    return bh, ph


@pytest.mark.parametrize("case", [
    "duplicates", "dead_rows", "sentinels_only_probe", "empty_build", "unique_keys",
    "one_key", "wide_hashes", "few_probes", "one_build_row"])
def test_merged_bounds_are_numpy_searchsorted(case):
    bh, ph = _hashes(case, np.random.default_rng(44))
    lo, hi = relops._merged_bounds(jnp.asarray(bh), jnp.asarray(ph))
    sorted_b = np.sort(bh)
    assert lo.dtype == hi.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(lo), np.searchsorted(sorted_b, ph, "left"))
    np.testing.assert_array_equal(np.asarray(hi), np.searchsorted(sorted_b, ph, "right"))


def _counts(case: str, rng):
    """(rows' counts, C)"""
    n, C = 500, 4096
    counts = rng.integers(0, 6, n, dtype=np.int64) * (rng.random(n) < 0.5)
    if case == "overflow":  # C smaller than the total
        C = int(counts.sum()) // 3
    elif case == "exact_fit":
        C = int(counts.sum())
    elif case == "all_empty":
        counts[:] = 0
    elif case == "empty_ends":  # empty rows first and last
        counts[:40], counts[-40:] = 0, 0
    elif case == "last_row_full":
        counts[-1] = 3
    elif case == "one_row":
        counts = np.asarray([9], np.int64)
    elif case == "one_lane":
        C = 1
    else:
        assert case == "plain"
    return counts, C


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("case", [
    "plain", "overflow", "exact_fit", "all_empty", "empty_ends", "last_row_full", "one_row",
    "one_lane"])
def test_expand_rows_is_numpy_searchsorted(case, form, monkeypatch):
    counts, C = _counts(case, np.random.default_rng(45))
    ends = np.cumsum(counts)
    _force(monkeypatch, form)
    row, off = relops.expand_rows(jnp.asarray(ends), C)
    j = np.arange(C)
    want = np.minimum(np.searchsorted(ends, j, "right"), len(ends) - 1)
    assert row.dtype == jnp.int32 and off.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(row), want)  # past the total too
    np.testing.assert_array_equal(np.asarray(off), j - (ends - counts)[want])


# ------------------------------------------------------------------ (b)

def _parent_equi_join(monkeypatch):
    """`equi_join` as it stood before PR 44 (commit 177217b): the bounds and
    the expansion index each a `searchsorted` by sort."""
    by_sort = lambda a, v, side="left": jnp.searchsorted(a, v, side=side, method="sort")  # noqa: E731

    def expand(ends, C):
        j = jnp.arange(C, dtype=jnp.int64)
        row = jnp.minimum(by_sort(ends, j, "right").astype(jnp.int32), ends.shape[0] - 1)
        counts = jnp.diff(ends, prepend=0)
        return row, j - (jnp.take(ends, row) - jnp.take(counts, row))

    # `_sort_lohi`'s "scan" lines are the parent's own, but for the method
    monkeypatch.setattr(relops, "rank_form", lambda keys, queries: "scan")
    monkeypatch.setattr(relops, "searchsorted_tpu", by_sort)
    monkeypatch.setattr(relops, "expand_rows", expand)


def _join_page(case: str, rng):
    nl, nr, C = 300, 200, 1024
    kv = lambda n, hi: rng.integers(0, hi, n, dtype=np.int64)  # noqa: E731
    valid_l = valid_r = None
    ll, rl = rng.random(nl) < 0.9, rng.random(nr) < 0.9
    keys = 40
    if case == "null_keys":
        valid_l, valid_r = rng.random(nl) < 0.8, rng.random(nr) < 0.8
    elif case == "overflow":  # more matches than lanes: `required` says how many
        keys, C = 4, 512
    elif case == "empty_build":
        rl = np.zeros(nr, bool)
    elif case == "collisions":  # eight hashes for forty keys: the verification sorts them out
        C = 8192
    elif case == "small_build":
        # the shape the hash kernel took until PR 45 (a build side of a few
        # dozen rows), on the path that takes it now: nullable keys, dead
        # lanes on both sides
        nr, keys = 40, 30
        valid_l, valid_r = rng.random(nl) < 0.85, rng.random(nr) < 0.85
        ll, rl = rng.random(nl) < 0.8, rng.random(nr) < 0.8
    else:
        assert case == "plain"
    lk, rk = ColumnVal(kv(nl, keys), valid_l, None, BIGINT), ColumnVal(kv(nr, keys), valid_r, None, BIGINT)
    lc = [lk, ColumnVal(kv(nl, 1000), rng.random(nl) < 0.9, None, BIGINT)]
    rc = [rk, ColumnVal(kv(nr, 1000).astype(np.int32), None, None, None)]
    dev = lambda c: ColumnVal(jnp.asarray(c.data), None if c.valid is None else jnp.asarray(c.valid),  # noqa: E731
                              c.dict, c.type)
    return ([dev(c) for c in lc], jnp.asarray(ll), [dev(c) for c in rc], jnp.asarray(rl),
            [dev(lk)], [dev(rk)], C)


def _same_page(got, want, msg=""):
    """(cols, live, ...) against (cols, live, ...): every lane of every
    array, dead ones too, its dtype, and the column's dictionary and type."""
    (cols, live), (want_cols, want_live) = got[:2], want[:2]
    np.testing.assert_array_equal(np.asarray(live), np.asarray(want_live), err_msg=msg)
    assert len(cols) == len(want_cols)
    for g, w in zip(cols, want_cols):
        assert g.dict is w.dict and g.type is w.type
        for have, other in ((g.data, w.data), (g.valid, w.valid), (g.data2, w.data2)):
            assert (have is None) == (other is None), msg
            if other is not None:
                assert have.dtype == other.dtype
                np.testing.assert_array_equal(np.asarray(have), np.asarray(other), err_msg=msg)


KINDS = ("inner", "left", "full", "semi", "anti", "null_anti", "mark", "mark_in")


@pytest.mark.parametrize("case", [
    "plain", "null_keys", "overflow", "empty_build", "collisions", "small_build"])
@pytest.mark.parametrize("kind", KINDS)
def test_every_kind_is_the_parents_join(kind, case, monkeypatch):
    lc, ll, rc, rl, lk, rk, C = _join_page(case, np.random.default_rng(46))
    if case == "collisions":
        monkeypatch.setattr(relops, "_mix64", lambda x: x.astype(jnp.uint64) & jnp.uint64(7))
    residual = None
    if kind in ("inner", "left", "full"):  # a non-equi conjunct over the expansion frame
        residual = lambda cols, n: cols[1].data % 3 != 0  # noqa: E731
    args = (kind, lc, ll, rc, rl, lk, rk, residual, C)
    got = {}
    for form in FORMS:
        with monkeypatch.context() as m:
            _force(m, form)
            got[form] = relops.equi_join(*args)
    with monkeypatch.context() as m:
        _parent_equi_join(m)
        tight = relops.equi_join(*args)
        # a frame too small for the matches answers for the lanes it holds (the
        # caller retries at `required`); the rank needs no frame and answers
        # at once what the parent answers in a frame that holds them all
        whole = relops.equi_join(*args[:-1], 16384)
    assert int(whole[2]) == int(tight[2]) <= 16384
    for form, (cols, live, required) in got.items():
        # a filtering kind without a residual reads its answer off the merged
        # rank and builds no frame: it has no need to report
        no_frame = kind in relops._FILTERING and form == "merged"
        want_cols, want_live, want_required = whole if no_frame else tight
        if no_frame:
            assert required is None
        else:
            assert int(required) == int(want_required), form
        _same_page((cols, live), (want_cols, want_live), form)


# --- a filtering kind with a residual: the run's min and max against the frame

def _ir(op, x, y):
    from trino_tpu.data.types import BOOLEAN
    from trino_tpu.plan.ir import Call, FieldRef

    ref = lambda i: FieldRef(i, BIGINT)  # noqa: E731
    return Call(op, (ref(x), ref(y)), BOOLEAN)


def _residual_page(case: str, rng):
    """Two columns a side (the key, the compared value): fields 0, 1 | 2, 3."""
    nl, nr, C = 300, 260, 4096
    kv = lambda n, hi: rng.integers(0, hi, n, dtype=np.int64)  # noqa: E731
    ll, rl = rng.random(nl) < 0.9, rng.random(nr) < 0.9
    lkey, rkey = kv(nl, 40), kv(nr, 40)
    a, b = kv(nl, 6), kv(nr, 6)  # few values: runs whose every b equals a exist
    rkey[:20], b[:20] = 41, 3  # a run of one b: `ne` turns on min == max == a
    lkey[:30] = 41
    va = vb = vlk = vrk = None
    extra_l, extra_r, hi_l, hi_r = [], [], None, None
    if case == "nulls":
        va, vb = rng.random(nl) < 0.8, rng.random(nr) < 0.7
        vlk, vrk = rng.random(nl) < 0.9, rng.random(nr) < 0.9
    elif case == "two_keys":
        extra_l, extra_r = [kv(nl, 3)], [kv(nr, 3)]
    elif case == "limbed_key":  # the build side carries a high limb, the probe side none
        hi_r = np.where(rng.random(nr) < 0.2, 1, rkey >> 63)
    elif case == "narrow":  # int32 against int64, as narrowed lanes meet computed ones
        a = a.astype(np.int32)
    elif case == "extremes":  # b at its dtype's largest value sorts among the probes' slots
        b = np.where(rng.random(nr) < 0.3, np.iinfo(np.int64).max, b)
        a = np.where(rng.random(nl) < 0.3, np.iinfo(np.int64).max, a)
    else:
        assert case == "plain"
    col = lambda d, v=None, d2=None: ColumnVal(  # noqa: E731
        jnp.asarray(d), None if v is None else jnp.asarray(v), None, BIGINT,
        None if d2 is None else jnp.asarray(d2))
    lk, rk = col(lkey, vlk, hi_l), col(rkey, vrk, hi_r)
    lc, rc = [lk, col(a, va)], [rk, col(b, vb)]
    lkeys, rkeys = [lk] + [col(e) for e in extra_l], [rk] + [col(e) for e in extra_r]
    return lc, jnp.asarray(ll), rc, jnp.asarray(rl), lkeys, rkeys, C


def _filter_events(call):
    events = kernels.begin_capture()
    try:
        out = call()
    finally:
        kernels.end_capture()
    return out, [e[1] for e in events if e[0] == "join_filter"]


RESIDUALS = {  # name -> (IR over fields 0, 1 | 2, 3, the form it takes)
    "ne": (_ir("ne", 1, 3), "minmax"), "lt": (_ir("lt", 1, 3), "minmax"),
    "ge": (_ir("ge", 1, 3), "minmax"), "le": (_ir("le", 1, 3), "minmax"),
    "gt": (_ir("gt", 1, 3), "minmax"),
    # the build side's expression first: `b < a` is `a > b`
    "ne_mirrored": (_ir("ne", 3, 1), "minmax"), "lt_mirrored": (_ir("lt", 3, 1), "minmax"),
    "ge_mirrored": (_ir("ge", 3, 1), "minmax"),
    "eq": (_ir("eq", 1, 3), "frame"),           # no question about a run's ends
    "one_side": (_ir("ne", 2, 3), "frame"),     # both arguments read the build side
}


def _with_residual(kind, page, ir, hand_over=True):
    from trino_tpu.exec.compiler import _one_comparison
    from trino_tpu.ops.expr import eval_expr, eval_predicate

    lc, ll, rc, rl, lkeys, rkeys, C = page
    residual = lambda cols, n: eval_predicate(ir, cols, n)  # noqa: E731
    compare = None
    sides = _one_comparison(ir, len(lc)) if hand_over else None
    if sides is not None:
        op, x, y = sides
        compare = (op, eval_expr(x, lc, ll.shape[0]), eval_expr(y, [*lc, *rc], rl.shape[0]))
    return _filter_events(lambda: relops.equi_join(
        kind, lc, ll, rc, rl, lkeys, rkeys, residual, C, compare))


@pytest.mark.parametrize("residual", sorted(RESIDUALS))
@pytest.mark.parametrize("kind", ["semi", "anti", "mark"])
def test_one_comparison_is_asked_of_the_runs_min_and_max(kind, residual):
    """Every lane of the answer against the frame path's (the same call with
    the comparison not handed over), and the `join_filter` event's form."""
    ir, form = RESIDUALS[residual]
    page = _residual_page("plain", np.random.default_rng(48))
    got, said = _with_residual(kind, page, ir)
    want, frame = _with_residual(kind, page, ir, hand_over=False)
    assert said == [form] and frame == ["frame"]
    assert (got[2] is None) == (form == "minmax") and want[2] is not None
    _same_page(got, want)
    kept = int(np.sum(np.asarray(got[1] if kind != "mark" else got[0][-1].data)))
    assert form == "frame" or 0 < kept < 300  # the page tells the forms apart


@pytest.mark.parametrize("case", ["nulls", "two_keys", "limbed_key", "narrow", "extremes"])
@pytest.mark.parametrize("op", ["ne", "lt", "ge"])
@pytest.mark.parametrize("kind", ["semi", "anti", "mark"])
def test_min_and_max_under_nulls_wide_keys_and_limbs(kind, op, case):
    """NULLs in the compared column on both sides and in the keys, a
    two-column key, a key with a `data2` limb on one side, lanes of two
    widths, values at the dtype's end: the frame path's answer."""
    page = _residual_page(case, np.random.default_rng(49))
    got, said = _with_residual(kind, page, _ir(op, 1, 3))
    want, _ = _with_residual(kind, page, _ir(op, 1, 3), hand_over=False)
    assert said == ["minmax"] and got[2] is None
    _same_page(got, want)


@pytest.mark.parametrize("kind,why", [
    ("null_anti", "NOT IN's three-valued answer keeps the frame under a residual"),
    ("mark_in", "and IN's"), ("inner", "an expansion is the output"), ("left", "the same")])
def test_what_keeps_the_frame_under_one_comparison(kind, why):
    page = _residual_page("plain", np.random.default_rng(50))
    got, said = _with_residual(kind, page, _ir("ne", 1, 3))
    assert said == (["frame"] if kind in relops._FILTERING else []), why
    assert got[2] is not None


def test_a_residual_of_two_conjuncts_takes_the_frame():
    from trino_tpu.data.types import BOOLEAN
    from trino_tpu.exec.compiler import _one_comparison
    from trino_tpu.plan.ir import Call

    both = Call("and", (_ir("ne", 1, 3), _ir("lt", 1, 3)), BOOLEAN)
    assert _one_comparison(both, 2) is None
    assert _one_comparison(_ir("ne", 1, 3), 2)[0] == "ne"
    assert _one_comparison(_ir("lt", 3, 1), 2)[0] == "gt"  # mirrored: probe op build
    page = _residual_page("plain", np.random.default_rng(51))
    got, said = _with_residual("semi", page, both)
    assert said == ["frame"] and got[2] is not None


@pytest.mark.parametrize("kind", relops._FILTERING)
def test_a_floating_point_key_keeps_the_frame(kind):
    """A key whose sort order is not its `==` (NaN, -0.0) is verified in the
    frame as before."""
    lk = ColumnVal(jnp.asarray([0.5, 7.0, 1.5, 2.5]), None)
    rk = ColumnVal(jnp.asarray([7.0, 1.5, 1.5]), None)
    live_l, live_r = jnp.ones((4,), jnp.bool_), jnp.ones((3,), jnp.bool_)
    (cols, live, required), said = _filter_events(lambda: relops.equi_join(
        kind, [lk], live_l, [rk], live_r, [lk], [rk], None, 16))
    assert said == ["frame"] and int(required) == 3
    hit = np.asarray(cols[-1].data if kind.startswith("mark") else live)
    want = np.asarray([False, True, True, False])
    np.testing.assert_array_equal(hit, want if kind in ("semi", "mark", "mark_in") else ~want)


def test_unnest_takes_the_helper(monkeypatch):
    """`unnest_expand` in both forms of `expand_rows`: the same page."""
    from trino_tpu.data.page import Dictionary
    from trino_tpu.data.types import ArrayType

    arrays = Dictionary(np.asarray([(), (1, 2, 3), (4,), (5, 6)], dtype=object))
    rng = np.random.default_rng(47)
    n, C = 64, 128
    arr = ColumnVal(jnp.asarray(rng.integers(0, 4, n).astype(np.int32)), None, arrays, ArrayType(BIGINT))
    cols = [ColumnVal(jnp.arange(n, dtype=jnp.int64), None, None, BIGINT)]
    live = jnp.asarray(rng.random(n) < 0.8)
    out = {}
    for form in FORMS:
        with monkeypatch.context() as m:
            _force(m, form)
            out[form] = relops.unnest_expand(cols, live, [arr], [BIGINT], True, True, C)
    (ca, la, ta), (cb, lb, tb) = out["merged"], out["scan"]
    assert int(ta) == int(tb) and 0 < int(ta) <= C
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for a, b in zip(ca, cb):
        np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))
        assert (a.valid is None) == (b.valid is None)
        if a.valid is not None:
            np.testing.assert_array_equal(np.asarray(a.valid), np.asarray(b.valid))


# --------------------------------------------------------------- structure

def _count(jaxpr, name, lanes=None) -> int:
    """Equations of primitive `name` in `jaxpr` and every jaxpr under it (a
    `searchsorted` is a `jit`, a binary search a `scan`); with `lanes`,
    only those whose first operand has that many rows."""
    n = 0
    for e in jaxpr.eqns:
        if e.primitive.name == name and (lanes is None or e.invars[0].aval.shape[:1] == (lanes,)):
            n += 1
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _count(inner, name, lanes)
    return n


def _traced_join(nr, nl, C):
    """The jaxpr of an inner sort-path join, one bigint key and one payload
    column a side, over shapes alone."""
    def call(lkey, lpay, ll, rkey, rpay, rl):
        lk, rk = ColumnVal(lkey, None, None, BIGINT), ColumnVal(rkey, None, None, BIGINT)
        cols, live, required = relops.equi_join(
            "inner", [lk, ColumnVal(lpay, None)], ll, [rk, ColumnVal(rpay, None)], rl,
            [lk], [rk], None, C)
        return [c.data for c in cols], live, required

    s = lambda n, dt: jax.ShapeDtypeStruct((n,), dt)  # noqa: E731
    events = kernels.begin_capture()
    try:
        jaxpr = jax.make_jaxpr(call)(
            s(nl, jnp.int64), s(nl, jnp.int32), s(nl, jnp.bool_),
            s(nr, jnp.int64), s(nr, jnp.int32), s(nr, jnp.bool_))
    finally:
        kernels.end_capture()
    assert [e for e in events if e[0] == "join"] == [
        ("join", "sort", f"inner build {nr} probe {nl} -> C {C}")]
    return jaxpr.jaxpr, [e for e in events if e[0] == "join_rank"]


# (nr, nl, C): SF10 q18's Join#5 and Join#8, SF10 q12's Join#3, Q9's Join#10, SF1 q12's Join#3;
# then the three shapes the hash kernel took until PR 45 (frames as the cells' plans trace at
# benchmarks/caps/'s tiers) — SF1 q18's Join#8 (orders against the 2,048-lane frame of the 63
# orderkeys that pass), Q9's Join#7 (an 8.4M-lane frame x nation), Q5's Join#9 (nation x
# region) — and SF1 q18's Join#5 (x lineitem) and Join#6 (x customer)
@pytest.mark.parametrize("nr,nl,C,bounds,expansion", [
    (60_000_466, 4_096, 16_384, "scan", "merged"),
    (4_096, 15_000_000, 4_096, "merged", "scan"),
    (1_048_576, 15_000_000, 1_048_576, "merged", "merged"),
    (8_000_000, 60_000_466, 67_108_864, "merged", "merged"),
    (65_536, 1_500_000, 65_536, "merged", "merged"),
    (2_048, 1_500_000, 2_048, "merged", "scan"),
    (25, 8_388_608, 8_388_608, "merged", "merged"),
    (5, 25, 32, "merged", "merged"),
    (6_002_367, 2_048, 2_048, "scan", "merged"),
    (150_000, 2_048, 2_048, "merged", "merged"),
])
def test_what_the_sort_path_traces_to(nr, nl, C, bounds, expansion):
    jaxpr, events = _traced_join(nr, nl, C)
    assert events == [("join_rank", bounds, f"{nr} ++ {nl} lanes -> C {C}")]
    assert relops.rank_form(nl, C) == expansion
    # the build side's sort and, in the merged form, two of build ++ probe: by
    # hash, and home by lane; none of C lanes or of nl + C (the expansion sorts
    # nothing) but where those are the same numbers
    sorts = collections.Counter({nr: 1, **({nr + nl: 2} if bounds == "merged" else {})})
    assert _count(jaxpr, "sort") == sum(sorts.values())
    assert {n: _count(jaxpr, "sort", n) for n in (nr, nr + nl, C, nl + C)} == {
        n: sorts[n] for n in (nr, nr + nl, C, nl + C)}
    # the one scatter marks the rows' starts; a binary search is a loop of
    # gathers and scatters nothing
    assert _count(jaxpr, "scatter") == (expansion == "merged")
    assert _count(jaxpr, "scan") == 2 * (bounds == "scan") + (expansion == "scan")


def _traced_filter(kind, nr, nl, C, op):
    """The jaxpr of a filtering join over shapes alone — one narrowed key and
    one compared column a side, `a <op> b` the residual, handed over as
    `exec/compiler.py` does — its dispatch events, and whether it reported
    a need."""
    from trino_tpu.ops.expr import eval_predicate

    ir = None if op is None else _ir(op, 1, 3)
    needs = []

    def call(lkey, a, ll, rkey, b, rl):
        col = lambda d: ColumnVal(d, None, None, BIGINT)  # noqa: E731
        lc, rc = [col(lkey), col(a)], [col(rkey), col(b)]
        residual = None if ir is None else (lambda cols, n: eval_predicate(ir, cols, n))
        cols, live, required = relops.equi_join(
            kind, lc, ll, rc, rl, lc[:1], rc[:1], residual, C,
            None if ir is None else (op, lc[1], rc[1]))
        needs.append(required is not None)
        return [c.data for c in cols], live

    s = lambda n, dt: jax.ShapeDtypeStruct((n,), dt)  # noqa: E731
    events = kernels.begin_capture()
    try:
        jaxpr = jax.make_jaxpr(call)(
            s(nl, jnp.int32), s(nl, jnp.int32), s(nl, jnp.bool_),
            s(nr, jnp.int32), s(nr, jnp.int32), s(nr, jnp.bool_))
    finally:
        kernels.end_capture()
    return jaxpr.jaxpr, events, needs[0]


def _lanes(jaxpr, out: set, name=None) -> set:
    """The leading sizes of every operand and result in `jaxpr` and under it
    (of primitive `name` alone, where given: its results)."""
    for e in jaxpr.eqns:
        if name is None or e.primitive.name == name:
            for v in (e.outvars if name else [*e.invars, *e.outvars]):
                shape = getattr(v.aval, "shape", ())
                if shape:
                    out.add(shape[0])
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _lanes(inner, out, name)
    return out


# q21's Join#8 and Join#6 (all of lineitem, and its late lines in Compact#15's 2^26-lane
# frame, against 2,097,152 probes; frames of 16,777,216 lanes until PR 48), q22's Join#5,
# q20's Join#13, SF10 and SF1 q18's Join#8
@pytest.mark.parametrize("kind,nr,nl,C,op,form", [
    ("semi", 60_000_466, 2_097_152, 16_777_216, "ne", "minmax"),
    ("anti", 67_108_864, 2_097_152, 16_777_216, "ne", "minmax"),
    ("anti", 15_000_000, 524_288, 4_194_304, None, "rank"),
    ("semi", 65_536, 8_000_000, 262_144, None, "rank"),
    ("semi", 4_096, 15_000_000, 4_096, None, "rank"),
    ("semi", 2_048, 1_500_000, 2_048, None, "rank"),
    ("null_anti", 16_384, 4_194_304, 4_096, None, "rank"),
    ("mark", 60_000_466, 2_097_152, 16_777_216, "lt", "minmax"),
])
def test_a_filtering_join_traces_to_no_frame(kind, nr, nl, C, op, form):
    """No operand of `C` lanes, no scatter, no `cond`, no binary search, no
    sort of the build side alone: ONE sort of build ++ probe lanes and ONE
    home, and gathers of `nl` lanes only (the run's min and max: two)."""
    jaxpr, events, needed = _traced_filter(kind, nr, nl, C, op)
    assert not needed
    said = kind + ("+residual" if op else "")
    assert [e for e in events if e[0] == "join"] == [
        ("join", "sort", f"{said} build {nr} probe {nl} -> C {C}")]
    assert [e[:2] for e in events if e[0] in ("join_rank", "join_filter")] == [
        ("join_rank", "merged"), ("join_filter", form)]
    lanes = _lanes(jaxpr, set())
    assert C not in lanes or C in (nr, nl), lanes
    assert lanes <= {1, nr, nl, nr + nl, nr + nl - 1}, lanes
    assert _count(jaxpr, "sort") == _count(jaxpr, "sort", nr + nl) == 2
    for absent in ("scatter", "scatter-add", "scatter-max", "cond", "scan", "while"):
        assert _count(jaxpr, absent) == 0, absent
    assert _count(jaxpr, "gather") == (2 if form == "minmax" else 0)
    assert _lanes(jaxpr, set(), "gather") <= {nl}


def test_the_frame_form_of_a_filtering_join_is_the_inner_joins_path():
    """A residual of another shape at q21's sizes: the frame, its `C`-lane
    operands, the scatter home, and a need to report."""
    nr, nl, C = 60_000_466, 2_097_152, 16_777_216
    jaxpr, events, needed = _traced_filter("semi", nr, nl, C, "eq")
    assert needed
    assert [e[:2] for e in events if e[0] == "join_filter"] == [("join_filter", "frame")]
    assert C in _lanes(jaxpr, set()) and _count(jaxpr, "sort") == 3
    assert _count(jaxpr, "scatter") == _count(jaxpr, "scatter-max") == 1  # the rows' starts; home


def test_the_parent_traced_to_seven_sorts(monkeypatch):
    """What the count above is held against: the parent's form at q12's shape."""
    _parent_equi_join(monkeypatch)
    jaxpr, _ = _traced_join(1_048_576, 15_000_000, 1_048_576)
    assert _count(jaxpr, "sort") == 7 and _count(jaxpr, "scatter") == 6


def test_a_small_build_traces_one_branch_and_no_kernel():
    """A build side under the former gate (2,048 lanes) with every kernel
    switched to interpret: no `cond` (the hash kernel's runtime guard compiled
    the sort path beside it), no `pallas_call`, one `join` event."""
    jaxprs = {}
    for policy in (kernels.KernelPolicy(), kernels.KernelPolicy(enabled=True, interpret=True)):
        kernels.set_policy(policy)
        try:
            jaxprs[policy.interpret], events = _traced_join(64, 600, 1024)
        finally:
            kernels.set_policy(kernels.KernelPolicy())
        assert events == [("join_rank", "merged", "64 ++ 600 lanes -> C 1024")]
        assert _count(jaxprs[policy.interpret], "cond") == 0
        assert _count(jaxprs[policy.interpret], "pallas_call") == 0
    assert str(jaxprs[True]) == str(jaxprs[False])  # the policy moves nothing in a join


# ------------------------------------------------------------------ (d)

@pytest.mark.parametrize("keys,queries,form", [
    (60_000_466, 4_096, "scan"),       # SF10 q18's Join#5: 4,096 probes never sort 60M hashes
    (15_000_000, 4_096, "scan"),       # its Join#8's expansion: 4,096 lanes out of 15M rows
    (6_001_215, 2_048, "scan"),        # SF1 q18's Join#5, a binary search before PR 44 too
    (4_096, 15_000_000, "merged"),     # 15M probes never search, however small the build side
    (1_048_576, 15_000_000, "merged"),
    (8_000_000, 60_000_466, "merged"),
    (60_000_466, 65_536, "scan"),        # Q5's Join#7: a 65,536-lane supplier frame probes lineitem
    (65_536, 16_777_216, "merged"),      # and its expansion into 16.8M lanes
    (60_000_466, 67_108_864, "merged"),  # Q9's Join#10's expansion
    (65_536, 1_500_000, "merged"),
    (1, 1, "merged"), (2_048, 2_048, "merged"),
    # by the chip's prices (PERF.md section 6): search | merged ~0.40 | 0.57 s, then ~0.80 | 0.57
    (60_000_466, 262_144, "scan"), (60_000_466, 524_288, "merged"),
    (15_000_000, 65_536, "scan"), (15_000_000, 131_072, "merged"),
])
def test_rank_form_reads_both_sizes(keys, queries, form):
    assert relops.rank_form(keys, queries) == form


def test_rank_form_turns_where_the_search_costs_the_sorted_form():
    """Along one haystack the rule turns once, where the search's gathered
    lanes (`queries` a round) weigh what `keys + queries` sorted lanes do."""
    keys = 15_000_000
    forms = [relops.rank_form(keys, 1 << p) for p in range(27)]
    turn = forms.index("merged")
    assert forms == ["scan"] * turn + ["merged"] * (27 - turn)
    q = 1 << turn
    assert q * keys.bit_length() * relops._RANK_SCAN_RATIO > keys + q
