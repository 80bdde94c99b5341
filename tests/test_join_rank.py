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
        want_cols, want_live, want_required = relops.equi_join(*args)
    for form, (cols, live, required) in got.items():
        assert int(required) == int(want_required), form
        np.testing.assert_array_equal(np.asarray(live), np.asarray(want_live), err_msg=form)
        assert len(cols) == len(want_cols)
        for g, w in zip(cols, want_cols):  # every lane, dead ones too
            assert g.dict is w.dict and g.type is w.type
            for have, want in ((g.data, w.data), (g.valid, w.valid), (g.data2, w.data2)):
                assert (have is None) == (want is None), form
                if want is not None:
                    assert have.dtype == want.dtype
                    np.testing.assert_array_equal(np.asarray(have), np.asarray(want), err_msg=form)


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
