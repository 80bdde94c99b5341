"""Relational operator kernels: static-shape, mask-based, jit-traceable.

These replace the reference's virtual-call operator chain (operator/*.java)
with whole-page device kernels:

- aggregation: the reference's FlatHash Swiss-table (operator/FlatHash.java:38)
  becomes a SORT-BASED group-by: lax.sort on the key columns with the
  aggregated columns riding it, run-boundary detection, then running sums
  and extremes read where each run ends.  On TPU, a bitonic sort over HBM-
  resident lanes beats scalar hash probing by orders of magnitude, and the
  fixed reduction tree makes float aggregation deterministic (a north-star
  requirement the Java engine itself cannot honor across runs).
- equi-join: the reference's PagesHash + JoinProbe (operator/join/) becomes
  a sort of the build side's hashes, the probe's bounds over it (a running
  count over one merged sort, or a binary search for few probes: rank_form)
  and a prefix-sum expansion.  A join that only filters or marks its left
  page (semi, anti, NOT IN, mark) reads its answer off the probe rows' rank
  in one sort by the key's own words and expands nothing (filter_form).
  Output capacity is static; the kernel reports the true match count so the
  host can retry at a bigger tier (exec/executor.py), mirroring how the
  reference's planner-fed stats size hash tables.
- sort/topn: multi-key lax.sort with direction/null-order key transforms.

Every kernel takes and returns columns + a `live` mask; dead lanes carry
garbage and are never branched on (XLA sees straight-line vector code).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..data.page import Dictionary
from .expr import ColumnVal

__all__ = [
    "group_aggregate", "equi_join", "broadcast_single_row", "sort_rows",
    "compact_rows", "top_n", "limit_mask", "unnest_expand", "AggSpec",
    "SortSpec", "MINMAX_KINDS",
]


@dataclass(frozen=True)
class AggSpec:
    fn: str  # sum | count | count_star | min | max | avg | bool_and |
    #          bool_or | stddev_samp | stddev_pop | var_samp | var_pop |
    #          percentile | corr | covar_samp | covar_pop | regr_slope |
    #          regr_intercept | array_agg | map_agg | listagg
    distinct: bool = False
    param: Optional[float] = None  # percentile's p
    sep: Optional[str] = None  # listagg separator
    type: Optional[object] = None  # result SqlType (decimal SUMs with
    #          precision > 18 accumulate in two-limb int128 even when the
    #          input column is single-lane; without it int64 wraps silently)


# aggregates computed on the HOST over the sorted grouping (their outputs
# are dict-coded structured values a traced kernel cannot intern); the
# executor routes plans containing them through eager execution
HOST_AGGS = frozenset({"array_agg", "map_agg", "listagg"})

# two-argument moment aggregates (pairwise sums on the device)
MOMENT_AGGS = frozenset(
    {"corr", "covar_samp", "covar_pop", "regr_slope", "regr_intercept"}
)


@dataclass(frozen=True)
class SortSpec:
    ascending: bool = True
    nulls_first: bool = False


def _valid_of(v: ColumnVal, n: int) -> jnp.ndarray:
    return jnp.ones((n,), jnp.bool_) if v.valid is None else v.valid


_MATMUL_SEGMENT_LIMIT = 1024

# rank_form: what a lane gathered by one round of a binary search costs in
# lanes of the sorted forms.  The chip (PERF.md section 6, PR 44): a round
# 22-30 ns a query lane, twice for two bounds; the merged bounds 7-12 ns a
# lane of keys + queries, the expansion's scatter 6-10.  At 60M keys the
# bounds cross near 375k queries, at 15M near 90k, the expansion of 15M rows
# near 125k output lanes; 6 puts the turn at 387k, 105k and 105k
_RANK_SCAN_RATIO = 6


def rank_form(keys: int, queries: int) -> str:
    """How `queries` values are ranked among `keys` sorted ones: "scan" (a
    binary search, `searchsorted_tpu`) or "merged" (a running count over one
    sorted order: `_merged_bounds`, `expand_rows`).  The search gathers
    `queries` lanes in each of its ceil(log2(keys + 1)) rounds; the sorted
    form sorts or scatters `keys + queries` lanes once, whatever the
    queries' count — so 4,096 probes never sort a 60M-lane haystack and 15M
    probes never search one.  A function of the traced shapes alone: the
    CPU traces the form the chip runs."""
    rounds = int(keys).bit_length()
    if queries * rounds * _RANK_SCAN_RATIO <= keys + queries:
        return "scan"
    return "merged"


def searchsorted_tpu(a: jnp.ndarray, v: jnp.ndarray, side: str = "left"):
    """jnp.searchsorted as a binary search: log2(n) SEQUENTIAL gather rounds
    over HBM, each of `v.size` lanes (~1.8 s for 8M probes into 8M keys —
    measured; it was the q03/q18 bottleneck).  Right only for few queries
    against many keys, which `rank_form` decides from BOTH sizes; callers —
    equi_join's bounds and `expand_rows` — ask it first and otherwise rank
    by one sorted order of their own.  JAX's method="sort" (an argsort of
    a ++ v, a scatter of as many lanes and the same again over v: at
    60,000,466 + 16,777,216 lanes 0.49 s a sort and 0.45 s a scatter, twice
    a call; ledger, PR 40) is reached from nowhere since PR 44."""
    return jnp.searchsorted(a, v, side=side, method="scan")


def _merged_bounds(bh: jnp.ndarray, ph: jnp.ndarray):
    """searchsorted(sort(bh), ph, "left" / "right"), value for value, from
    ONE sort of bh ++ ph: the lane number is the sort's last key, so the
    order is total (no stable sort's hidden index operand) and among equal
    hashes the build's lanes come first.  There `cb`, the running count of
    build lanes, IS the right bound at a probe's lane, and the count before
    the first lane of its run of equal hashes the left one — monotone, so a
    running maximum carries it through the run.  A second sort, on the lane
    number with both bounds riding, takes them home to the probe's order
    (the probe's lanes first): the chip reads a scatter of as many lanes at
    8 ns a lane and bound, this sort at ~3.5 for both (PERF.md section 6,
    PR 44)."""
    nr, nl = bh.shape[0], ph.shape[0]
    lane = jnp.arange(nr + nl, dtype=jnp.int32)
    h_s, lane_s = jax.lax.sort(
        [jnp.concatenate([bh, ph]), lane], num_keys=2, is_stable=False)
    is_b = lane_s < nr
    cb = jnp.cumsum(is_b.astype(jnp.int32))
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), h_s[1:] != h_s[:-1]])
    lo_s = jax.lax.cummax(jnp.where(first, cb - is_b, 0))
    home = jnp.where(is_b, lane_s + nl, lane_s - nr)
    _, lo, hi = jax.lax.sort([home, lo_s, cb], num_keys=1, is_stable=False)
    return lo[:nl].astype(jnp.int64), hi[:nl].astype(jnp.int64)


def expand_rows(ends: jnp.ndarray, C: int):
    """Row i yields ends[i] - ends[i-1] output lanes (`ends` an inclusive
    running sum): (row, offset in row) of each of `C` output lanes, the row
    clipped to the last one past the total.  The row of lane j is
    searchsorted(ends, j, "right") — a question about a sorted iota, so no
    sort answers it: it is the LAST row that starts at or before j.  Each
    row's number is scattered to its start (of the rows that share a start,
    empty ones and the row after them, the last) and a running maximum
    carries it over the row's lanes; the running maximum of the marked
    positions is the row's start.  Few output lanes against many rows take
    the binary search (`rank_form`).  Shared by equi_join's expansion and
    unnest_expand."""
    n = ends.shape[0]
    j = jnp.arange(C, dtype=jnp.int64)
    if rank_form(n, C) == "scan":
        row = jnp.minimum(searchsorted_tpu(ends, j, side="right"), n - 1)
        row = row.astype(jnp.int32)
        start = jnp.where(row > 0, jnp.take(ends, jnp.maximum(row - 1, 0)), 0)
        return row, j - start
    i = jnp.arange(n, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    last = (ends != starts) | (i == n - 1)
    at = jnp.where(last & (starts < C), starts, C).astype(jnp.int32)
    mark = jnp.full((C,), -1, jnp.int32).at[at].set(i, mode="drop")
    row = jax.lax.cummax(mark)
    start = jax.lax.cummax(
        jnp.where(mark >= 0, jnp.arange(C, dtype=jnp.int32), 0))
    return row, j - start.astype(jnp.int64)


def _segment_sum(values: jnp.ndarray, seg: jnp.ndarray, num: int) -> jnp.ndarray:
    """Backend-aware segment sum.  On CPU, XLA's scatter-add is fine.  On
    TPU, scatter serializes — but a one-hot matmul runs on the MXU, which is
    exactly how a TPU wants to aggregate (SURVEY §7: keep the FLOPs where
    the systolic array is).  Used when the segment count is small enough
    that the [n, G] one-hot is cheap."""
    if jax.default_backend() != "cpu" and num <= _MATMUL_SEGMENT_LIMIT:
        if jnp.issubdtype(values.dtype, jnp.integer):
            return _limb_segment_sum(values, seg, num)
        return _chunked_f32_segment_sum(values, seg, num).astype(values.dtype)
    return jax.ops.segment_sum(values, seg, num_segments=num)


def _limb_segment_sum(values: jnp.ndarray, seg: jnp.ndarray, num: int):
    """EXACT int64 segment sum on the MXU: decompose |v| into 15-bit limbs so
    every 512-row chunk partial stays below 2^24 (exact in f32), sum each
    limb with the f32 einsum, recombine in f64 (exact to 2^53 — counts and
    SQL-realistic BIGINT sums)."""
    sign = jnp.sign(values).astype(jnp.float64)
    mag = jnp.abs(values.astype(jnp.int64))
    total = jnp.zeros((num,), jnp.float64)
    for limb in range(4):  # 60 bits
        part = ((mag >> (15 * limb)) & 0x7FFF).astype(jnp.float64) * sign
        total = total + _chunked_f32_segment_sum(part, seg, num) * float(1 << (15 * limb))
    return jnp.round(total).astype(values.dtype)


_CHUNK = 512


def _chunked_f32_segment_sum(values: jnp.ndarray, seg: jnp.ndarray, num: int):
    """f32 MXU einsum per 512-row chunk, f64 accumulation across chunks.

    Per-chunk f32 error is ~sqrt(512) ulp and chunk partials are combined
    exactly-ish in f64, giving ~1e-8 relative error on money-scale sums —
    inside the differential-test tolerance, at MXU speed.  (The emulated-f64
    matmul this replaces is ~5x slower; true exactness comes with the Pallas
    segment-reduce kernel.)"""
    n = values.shape[0]
    C = -(-n // _CHUNK)
    pad = C * _CHUNK - n
    v = jnp.pad(values.astype(jnp.float32), (0, pad)).reshape(C, _CHUNK)
    s = jnp.pad(seg, (0, pad), constant_values=num).reshape(C, _CHUNK)
    onehot = jax.nn.one_hot(s, num, dtype=jnp.float32, axis=-1)  # [C, K, G]
    partial = jnp.einsum("ck,ckg->cg", v, onehot)  # MXU
    return partial.astype(jnp.float64).sum(axis=0)


def _sortable_operands(v: ColumnVal, descending: bool = False) -> list:
    """Sort operand list for one key: one array for single-lane columns,
    TWO for decimal128 (lexicographic (hi signed, lo unsigned) == 128-bit
    numeric order; descending negates at 128-bit width first)."""
    if v.data2 is not None:
        from ..data.dec128 import neg128

        lo = v.data.astype(jnp.int64)
        hi = v.data2.astype(jnp.int64)
        if descending:
            lo, hi = neg128(lo, hi)
        lo_u = jax.lax.bitcast_convert_type(lo, jnp.uint64)
        return [hi, lo_u]
    return [_sortable_key(v, descending)]


def _sortable_key(v: ColumnVal, descending: bool = False) -> jnp.ndarray:
    """Lower a column to a sortable numeric array (varchar -> dictionary rank,
    bool -> int8); negated for descending order."""
    if v.data2 is not None:
        raise NotImplementedError(
            "decimal128 lanes in this operation (two-limb keys)"
        )
    data = v.data
    if v.dict is not None:
        data = jnp.take(jnp.asarray(v.dict.sorted_rank()), v.data)
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.int8)
    if descending:
        data = -data.astype(jnp.promote_types(data.dtype, jnp.int8))
    return data


# ------------------------------------------------------------ aggregation


def group_aggregate(
    key_vals: Sequence[ColumnVal],
    agg_args: Sequence[Optional[ColumnVal]],
    specs: Sequence[AggSpec],
    live: jnp.ndarray,
    num_groups_cap: int,
    agg_args2: Optional[Sequence[Optional[ColumnVal]]] = None,
    agg_order: Optional[Sequence[tuple]] = None,
):
    """Grouped aggregation: global (no keys), direct-code (dictionary keys
    of a small domain index their groups) or sorted.

    Returns (out_keys: list[(data, valid, data2-or-None)], out_aggs:
    list[(data, valid) or (data, valid, Dictionary) for host-collected
    aggregates], out_live, n_groups) where outputs have capacity
    `num_groups_cap` and n_groups is the true group count (> cap ==
    overflow, host retries).
    """
    G = num_groups_cap
    if agg_args2 is None:
        agg_args2 = [None] * len(specs)
    if agg_order is None:
        agg_order = [()] * len(specs)

    if not key_vals:
        return _global_aggregate(agg_args, specs, live, agg_args2, agg_order)

    fast = _direct_code_aggregate(key_vals, agg_args, specs, live, agg_args2)
    if fast is not None:
        return fast

    return _sorted_aggregate(key_vals, agg_args, specs, live, G, agg_args2, agg_order)


class _GroupedSort:
    """Rows sorted by (dead-last, group keys..., [one value-sorted
    aggregate's validity and value]) WITH the columns that are aggregated
    riding the sort as operands that are not keys: nothing is gathered
    through a permutation afterwards (on the v5e a sort carries a column
    several times cheaper than a gather of 60M lanes moves it: PERF.md
    section 6, PR 41).  What rides follows what the trace can see — a
    validity operand only for a column that has a mask, `iota` only when a
    caller asks for the permutation itself (`want_perm`: the host-collected
    aggregates).  `live` needs no operand of its own: the dead flag is the
    first key, so its sorted form is the sort's own output."""

    def __init__(self, key_vals, live, G, extra=None, carry=(), want_perm=False):
        n = live.shape[0]
        keys: list[jnp.ndarray] = [(~live).astype(jnp.int8)]
        self._key_slots: list[tuple] = []  # per key: (validity slot, operand slots)
        for kv in key_vals:
            vslot = None
            if kv.valid is not None:  # nulls group together (last)
                vslot = len(keys)
                keys.append(~kv.valid)
            ops = _sortable_operands(kv)  # 2 operands for decimal128
            self._key_slots.append((vslot, range(len(keys), len(keys) + len(ops))))
            keys.extend(ops)
        n_group_ops = len(keys)
        # Validity of `extra` sorts before its value so a NULL lane whose
        # code equals a live value cannot become the "first occurrence"
        # (the round-1 COUNT(DISTINCT) advisory bug).
        extra_vslot = None
        if extra is not None:
            if extra.valid is not None:
                extra_vslot = len(keys)
                keys.append(~extra.valid)
            keys.extend(_sortable_operands(extra))
        by_id: dict = {}  # id(array as the caller holds it) -> operand slot
        payload: list[jnp.ndarray] = []
        carry = list(carry)
        if extra is not None:
            if extra.data2 is None and extra.dict is None and (
                extra.data.dtype != jnp.bool_
            ):  # the key operand is the column itself
                by_id[id(extra.data)] = len(keys) - 1
            else:
                carry.append(extra.data)
        for arr in carry:
            if id(arr) not in by_id:
                by_id[id(arr)] = len(keys) + len(payload)
                payload.append(arr)
        if want_perm:
            payload.append(jnp.arange(n, dtype=jnp.int32))
        # a group's rows keep their page order where that order can show: in
        # a floating sum's rounding and in what a host collection lists
        stable = want_perm or any(
            jnp.issubdtype(a.dtype, jnp.floating) for a in payload
        )
        self._ops = jax.lax.sort(
            keys + payload, num_keys=len(keys), is_stable=stable
        )
        self._by_id = by_id
        self.carried = len(payload)
        self.perm = self._ops[-1] if want_perm else None
        self.live = self._ops[0] == 0
        first = jnp.arange(n, dtype=jnp.int32) == 0

        def changed(slots):
            diff = jnp.zeros((n,), jnp.bool_)
            for i in slots:
                op = self._ops[i]
                diff = diff | (op != jnp.concatenate([op[:1], op[:-1]]))
            return diff

        new_group = self.live & (first | changed(range(1, n_group_ops)))
        from .pallas.segreduce import SortedRuns

        self.runs = SortedRuns(new_group, self.live)
        seg = jnp.cumsum(new_group.astype(jnp.int32), dtype=jnp.int32) - 1
        # dead rows and the groups past the frame -> overflow bucket
        self.seg = jnp.minimum(jnp.where(self.live, seg, G), G)
        if extra is not None:
            # the value-sorted argument as it lies after the sort: valid
            # values ascending at the front of each group's run
            self.extra_valid = (
                self.live if extra_vslot is None
                else self.live & ~self._ops[extra_vslot]
            )
            self.extra_new = new_group | changed(range(n_group_ops, len(keys)))

    def sorted(self, arr: jnp.ndarray) -> jnp.ndarray:
        return self._ops[self._by_id[id(arr)]]

    def col(self, cv: Optional[ColumnVal]) -> Optional[ColumnVal]:
        """A carried column in the sorted order."""
        if cv is None:
            return None
        return ColumnVal(
            self.sorted(cv.data),
            None if cv.valid is None else self.sorted(cv.valid),
            cv.dict,
            cv.type,
            None if cv.data2 is None else self.sorted(cv.data2),
        )

    def key_reads(self) -> list:
        """The group keys as 'last' reductions: a run's last row holds the
        run's key, and the sorted key operands are the sort's own outputs."""
        from .pallas.segreduce import SegRed

        return [
            SegRed("last", self._ops[i], None)
            for vslot, slots in self._key_slots
            for i in ([] if vslot is None else [vslot]) + list(slots)
        ]

    def out_keys(self, key_vals, read: list) -> list[tuple]:
        """(data, valid, data2-or-None) per key from `key_reads`' results."""
        it = iter(read)
        out: list[tuple] = []
        for kv, (vslot, slots) in zip(key_vals, self._key_slots):
            valid = None if vslot is None else ~next(it)
            if kv.data2 is not None:  # (hi signed, lo unsigned) operands
                hi, lo_u = next(it), next(it)
                lo = jax.lax.bitcast_convert_type(lo_u, jnp.int64)
                out.append((lo.astype(kv.data.dtype), valid, hi.astype(kv.data2.dtype)))
                continue
            data = next(it)
            if kv.dict is not None:  # sorted by rank: back to the code
                inv = np.argsort(kv.dict.sorted_rank()).astype(np.int32)
                data = jnp.take(
                    jnp.asarray(inv), jnp.clip(data, 0, max(len(inv) - 1, 0))
                ).astype(kv.data.dtype)
            elif kv.data.dtype == jnp.bool_:
                data = data != 0
            out.append((data, valid, None))
        return out


def _carried_arrays(cols) -> list:
    """The arrays of `cols` (None entries skipped) a sort has to carry."""
    out = []
    for cv in cols:
        if cv is not None:
            out.extend(a for a in (cv.data, cv.data2, cv.valid) if a is not None)
    return out


def _sorted_aggregate(key_vals, agg_args, specs, live, G, agg_args2, agg_order):
    """The sort-based group-by: what group_aggregate runs when the direct-
    code path declines.  ONE sort that carries the aggregated columns, then
    ONE compaction of the group ends carrying the keys and the running sums
    (SortedRuns.read)."""
    from .kernels import record_dispatch
    from .pallas.segreduce import fused_segment_reduce

    n = live.shape[0]
    # value-sorted aggregates (DISTINCT adjacency, percentile selection) ride
    # the group sort; the FIRST one shares the main sort, each additional one
    # gets its own sort pass below (group order is key-determined, so the
    # frames align across sorts).
    vs_ix = [
        i
        for i, s in enumerate(specs)
        if (s.distinct or s.fn == "percentile") and agg_args[i] is not None
    ]
    host_ix = [i for i, s in enumerate(specs) if s.fn in HOST_AGGS]
    # the value-sorted aggregate whose reductions share the main sort's pass
    vs_main = vs_ix[0] if vs_ix and vs_ix[0] not in host_ix else None
    # arguments that do not ride: read from the sort's keys, or on the host
    apart = set(vs_ix) | set(host_ix)
    riding = [None if i in apart else a for i, a in enumerate(agg_args)]
    riding2 = [None if i in apart else a for i, a in enumerate(agg_args2)]
    gs = _GroupedSort(
        key_vals, live, G,
        extra=agg_args[vs_ix[0]] if vs_ix else None,
        carry=_carried_arrays(riding + riding2), want_perm=bool(host_ix),
    )
    sorted_args = [gs.col(a) for a in riding]
    sorted_args2 = [gs.col(a) for a in riding2]

    # ---- every reduction of the main sort in one pass over its ends --------
    reds, finish = _agg_reductions(
        sorted_args, specs, gs.seg, gs.live, G, n, sorted_args2, runs=gs.runs
    )
    key_at = len(reds)
    reds = reds + gs.key_reads()
    vs_at = len(reds)
    if vs_main is not None:
        vs_reds, vs_finish = _value_sorted_reduction(
            gs, agg_args[vs_main], specs[vs_main]
        )
        reds = reds + vs_reds
    results = fused_segment_reduce(gs.seg, reds, G, runs=gs.runs)
    out_keys = gs.out_keys(key_vals, results[key_at:vs_at])
    out_aggs = finish(results[:key_at])

    for i, (arg, spec) in enumerate(zip(agg_args, specs)):
        if out_aggs[i] is not None:
            continue
        if spec.fn == "approx_distinct":
            sa = sorted_args[i]
            out_aggs[i] = _segment_hll(
                sa.data, gs.live if sa.valid is None else sa.valid & gs.live,
                gs.seg, G,
            )
        elif spec.fn in HOST_AGGS:
            out_aggs[i] = _host_collect_agg(
                spec, arg, agg_args2[i], gs.perm, gs.seg, gs.live, G, n,
                order=agg_order[i],
            )
        elif i == vs_main:  # DISTINCT/percentile: sorted adjacency
            out_aggs[i] = vs_finish(results[vs_at:])
        else:  # additional value-sorted agg: its own sort pass
            own = _GroupedSort(key_vals, live, G, extra=arg)
            own_reds, own_finish = _value_sorted_reduction(own, arg, spec)
            out_aggs[i] = own_finish(
                fused_segment_reduce(own.seg, own_reds, G, runs=own.runs)
            )

    record_dispatch(
        "group_by", "sort",
        f"cap {G}; sort carries {gs.carried} cols, "
        f"ends carry {gs.runs.ends_words} words",
    )
    n_groups = gs.runs.n_groups
    out_live = jnp.arange(G, dtype=jnp.int32) < jnp.minimum(n_groups, G)
    return out_keys, out_aggs, out_live, n_groups


def _value_sorted_reduction(gs: _GroupedSort, arg: ColumnVal, spec: AggSpec):
    """(reductions, finish) of a DISTINCT count or a percentile over a
    _GroupedSort whose `extra` is `arg`: rows arrive ordered by (group keys,
    validity, value), so a value's first occurrence within its group is an
    adjacency test and a percentile is a row at a computed offset from its
    group's start."""
    from .pallas.segreduce import SegRed

    if spec.fn == "percentile":
        # exact nearest-rank selection on the grouped sort.  The reference
        # uses T-digest sketches (aggregation/
        # TDigestAndPercentileAggregation); an exact answer over the sorted
        # page is within any approximation contract and is the natural fit
        # for the sort-based group-by.
        data_s = gs.sorted(arg.data)
        n = data_s.shape[0]

        def finish(res):
            vcnt, size = res
            starts = jnp.cumsum(size) - size  # runs are packed from lane 0
            off = jnp.floor(
                spec.param * jnp.maximum(vcnt - 1, 0).astype(jnp.float64) + 0.5
            )
            idx = jnp.clip(starts + off.astype(jnp.int64), 0, max(n - 1, 0))
            return jnp.take(data_s, idx), vcnt > 0

        return [
            SegRed("count", None, gs.extra_valid), SegRed("count", None, gs.live)
        ], finish
    if not (spec.distinct and spec.fn == "count"):
        raise NotImplementedError(f"DISTINCT {spec.fn}")
    return (
        [SegRed("count", None, gs.extra_new & gs.extra_valid)],
        lambda res: (res[0], None),
    )


_DIRECT_DOMAIN_LIMIT = 4096


def _direct_code_aggregate(key_vals, agg_args, specs, live, agg_args2=None):
    """Fast path: every group key is a dictionary-coded column with no nulls
    and the key-domain product is small -> segment id IS the fused code; no
    sort, no scatter, just segment reductions.  This is the case the
    reference's DictionaryAwarePageProjection + BigintGroupByHash fast paths
    chase (TPC-H Q1: returnflag x linestatus = 6 groups over 6B rows at
    SF1000); on TPU it turns group-by into a bandwidth-bound reduction."""
    if agg_args2 is None:
        agg_args2 = [None] * len(specs)
    if any(
        s.distinct or s.fn in ("percentile", "approx_distinct") or s.fn in HOST_AGGS
        for s in specs
    ):
        return None
    domains = []
    for kv in key_vals:
        if kv.dict is None or kv.valid is not None:
            return None
        domains.append(len(kv.dict))
    total = 1
    for d in domains:
        total *= max(d, 1)
    if not (0 < total <= _DIRECT_DOMAIN_LIMIT):
        return None
    domains = [max(d, 1) for d in domains]  # empty dicts (all-dead pages)
    n = live.shape[0]
    G = total
    seg = jnp.zeros((n,), jnp.int32)
    for kv, d in zip(key_vals, domains):
        seg = seg * d + jnp.clip(kv.data.astype(jnp.int32), 0, d - 1)
    seg = jnp.where(live, seg, G)
    num = G + 1
    cnt_any = _segment_sum(live.astype(jnp.int64), seg, num)[:G]
    out_live = cnt_any > 0
    n_groups = jnp.sum(out_live.astype(jnp.int32))

    # decode segment index -> key codes (host-side iota tables)
    out_keys = []
    idx = np.arange(G, dtype=np.int64)
    rem = idx
    codes_per_key = []
    for d in reversed(domains):
        codes_per_key.append(rem % d)
        rem = rem // d
    codes_per_key.reverse()
    for kv, codes in zip(key_vals, codes_per_key):
        out_keys.append((jnp.asarray(codes.astype(np.int32)), None, None))

    out_aggs = _fused_aggs(agg_args, specs, seg, live, G, n, agg_args2=agg_args2)
    return out_keys, out_aggs, out_live, n_groups


def _fused_aggs(agg_args, specs, seg, live, G, n, agg_args2=None):
    """All non-DISTINCT aggregates of a GROUP BY in one fused segmented
    reduction (ops/pallas/segreduce.py): on TPU a single Pallas pass over HBM
    computes every SUM/COUNT/AVG on the MXU (exact int64 via limb
    decomposition, Kahan-compensated doubles) and every MIN/MAX on the VPU;
    on CPU the same call falls back to XLA segment ops.  This replaces the
    reference's per-function Accumulator loop (operator/aggregation/, 224
    files) with one bandwidth-bound kernel.

    Returns a list aligned with specs; DISTINCT entries are None (the caller
    computes those with the sorted-adjacency path).
    """
    from .pallas.segreduce import fused_segment_reduce

    reds, finish = _agg_reductions(agg_args, specs, seg, live, G, n, agg_args2)
    return finish(fused_segment_reduce(seg, reds, G) if reds else [])


def _agg_reductions(agg_args, specs, seg, live_s, G, n, agg_args2=None, runs=None):
    """(reductions, finish) of `_fused_aggs`: the SegReds every fusable
    aggregate asks for, and the function that turns their results into the
    list aligned with specs.  `runs`: the rows are the sorted group-by's
    (SortedRuns) — the caller adds its own reductions and makes the one
    reducer call; there a count of a column without a mask is the group's
    size, and a single-lane argument's 128-bit sum travels as the fewest
    exact int64 partial sums (one below 64 bits, two at 64), since every
    word read at a group's end is a word the compaction carries."""
    from .pallas.segreduce import SegRed

    reds: list = []
    count_memo: dict = {}

    def add(red) -> int:
        reds.append(red)
        return len(reds) - 1

    def add_count(valid) -> int:
        key = id(valid)
        if key not in count_memo:
            count_memo[key] = add(SegRed("count", None, valid))
        return count_memo[key]

    if agg_args2 is None:
        agg_args2 = [None] * len(specs)
    recipe: list = []
    for arg, arg2, spec in zip(agg_args, agg_args2, specs):
        if any(
            v is not None and v.data2 is not None for v in (arg, arg2)
        ) and not (
            spec.fn in ("sum", "count", "min", "max") and not spec.distinct
        ):
            raise NotImplementedError(
                f"aggregate {spec.fn} over decimal128 lanes "
                f"(sum/count/min/max only)"
            )
        if (
            spec.distinct
            or spec.fn in ("percentile", "approx_distinct")
            or spec.fn in HOST_AGGS
        ):
            recipe.append(None)
            continue
        if spec.fn in MOMENT_AGGS:
            # pairwise moments (reference: CorrelationAggregation etc.):
            # sums of y, x, xy, xx, yy over rows where BOTH args are non-NULL
            pv = _valid_of(arg, n) & _valid_of(arg2, n) & live_s
            y = arg.data.astype(jnp.float64)
            x = arg2.data.astype(jnp.float64)
            recipe.append(
                (
                    "moment", spec.fn,
                    add(SegRed("sum", y, pv)),
                    add(SegRed("sum", x, pv)),
                    add(SegRed("sum", x * y, pv)),
                    add(SegRed("sum", x * x, pv)),
                    add(SegRed("sum", y * y, pv)),
                    add(SegRed("count", None, pv)),
                )
            )
            continue
        if spec.fn == "count_star":
            recipe.append(("count", add_count(live_s)))
            continue
        data = arg.data
        if runs is not None and arg.valid is None:
            valid = live_s  # one count serves every column without a mask
        else:
            valid = _valid_of(arg, n) & live_s
        res_t = spec.type
        wide_sum = (
            spec.fn == "sum"
            and res_t is not None
            and getattr(res_t, "is_decimal", False)
            and res_t.precision > 18
            and jnp.issubdtype(data.dtype, jnp.integer)
        )
        if spec.fn == "count":
            recipe.append(("count", add_count(valid)))
        elif spec.fn == "sum" and (arg.data2 is not None or wide_sum):
            # decimal128 sum: four 32-bit limb sums (each exact in int64 for
            # n < 2^31 rows) recombined into two-limb outputs (the segreduce
            # analogue of Int128Math.addWithOverflow accumulation).  Also
            # taken when the RESULT precision > 18 over a single-lane input:
            # the int64 inputs fit, but their sum can overflow int64.
            from ..data.dec128 import limbs32

            lo64 = data.astype(jnp.int64)
            if runs is not None and arg.data2 is None:
                # a single lane: n < 2^31 rows of under 2^31 sum exactly in
                # one int64; a 64-bit lane as its unsigned low and signed
                # high 32 bits (limbs l0 and l1 of a sign-extended value)
                if data.dtype.itemsize <= 4:
                    parts = [data]
                else:
                    parts = [lo64 & jnp.int64(0xFFFFFFFF), lo64 >> 32]
            else:
                if arg.data2 is not None:
                    hi = arg.data2
                else:
                    hi = lo64 >> 63  # sign-extend the single lane
                parts = limbs32(lo64, hi)
            recipe.append(
                ("sum128", [add(SegRed("sum", l, valid)) for l in parts],
                 add_count(valid))
            )
        elif arg.data2 is not None and spec.fn in ("min", "max"):
            # decimal128 min/max: lexicographic two-pass — the fused pass
            # reduces the SIGNED hi limb; a follow-up segmented pass picks
            # the best UNSIGNED lo limb among rows whose hi limb equals the
            # group winner (Int128 compare order = (hi, unsigned lo);
            # reference: spi/type/Int128Math.compare).  The lo limb is
            # XOR-biased so unsigned order matches int64 signed order.
            hi = arg.data2
            lo_b = jnp.bitwise_xor(
                data.astype(jnp.int64), jnp.int64(-(2 ** 63))
            )
            recipe.append(
                ("minmax128", spec.fn,
                 add(SegRed(spec.fn, hi.astype(jnp.int64), valid)),
                 add_count(valid), lo_b, valid, hi.astype(jnp.int64))
            )
        elif arg.data2 is not None:
            raise NotImplementedError(
                f"aggregate {spec.fn} over decimal128 lanes "
                f"(sum/count/min/max only)"
            )
        elif spec.fn in ("sum", "avg"):
            as_int = spec.fn == "sum" and jnp.issubdtype(data.dtype, jnp.integer)
            vals = data if as_int else data.astype(jnp.float64)
            recipe.append((spec.fn, add(SegRed("sum", vals, valid)), add_count(valid)))
        elif spec.fn in ("min", "max"):
            if arg.dict is not None:
                rank = jnp.take(jnp.asarray(arg.dict.sorted_rank()), arg.data)
                recipe.append(
                    ("dictmm", spec.fn, arg, add(SegRed(spec.fn, rank, valid)), add_count(valid))
                )
            else:
                recipe.append(("minmax", add(SegRed(spec.fn, data, valid)), add_count(valid)))
        elif spec.fn in ("bool_and", "bool_or"):
            # AND == min over {0,1}, OR == max (reference: aggregation/
            # BooleanAndAggregation / BooleanOrAggregation)
            b = data.astype(jnp.int32)
            red = "min" if spec.fn == "bool_and" else "max"
            recipe.append(("bool", add(SegRed(red, b, valid)), add_count(valid)))
        elif spec.fn in ("stddev_samp", "stddev_pop", "var_samp", "var_pop"):
            x = data.astype(jnp.float64)
            recipe.append(
                (
                    "var", spec.fn,
                    add(SegRed("sum", x, valid)),
                    add(SegRed("sum", x * x, valid)),
                    add_count(valid),
                )
            )
        else:
            raise NotImplementedError(f"aggregate {spec.fn}")

    def finish(results) -> list:
        return _finish_aggs(recipe, results, seg, G, runs)

    return reds, finish


def _finish_aggs(recipe, results, seg, G, runs) -> list:
    """The aggregates' outputs from their reductions' results (see
    _agg_reductions)."""
    from .pallas.segreduce import SegRed, fused_segment_reduce

    out: list = []
    for r in recipe:
        if r is None:
            out.append(None)
            continue
        kind = r[0]
        if kind == "count":
            out.append((results[r[1]], None))
        elif kind == "sum128":
            from ..data.dec128 import recombine32

            sums = [results[i].astype(jnp.int64) for i in r[1]]
            cnt = results[r[2]]
            if len(sums) == 1:  # fits one lane: sign-extend
                lo, hi = sums[0], sums[0] >> 63
            else:
                if len(sums) == 2:  # limbs the argument does not have
                    sums += [jnp.zeros_like(sums[0])] * 2
                lo, hi = recombine32(*sums)
            out.append((lo, cnt > 0, None, hi))
        elif kind in ("sum", "avg"):
            s, cnt = results[r[1]], results[r[2]]
            nonempty = cnt > 0
            if kind == "sum":
                out.append((s, nonempty))
            else:
                out.append((s / jnp.where(nonempty, cnt, 1).astype(jnp.float64), nonempty))
        elif kind == "minmax":
            s, cnt = results[r[1]], results[r[2]]
            out.append((s, cnt > 0))
        elif kind == "minmax128":
            _, fn, hi_i, ci, lo_b, valid_m, hi_rows = r
            hi_g, cnt = results[hi_i], results[ci]
            # second pass: best biased lo limb restricted to the rows whose
            # hi limb equals their group's winning hi limb
            at_best = valid_m & (
                hi_rows == jnp.take(hi_g.astype(jnp.int64), seg)
            )
            lo_best = fused_segment_reduce(
                seg, [SegRed(fn, lo_b, at_best)], G, runs=runs
            )[0]
            lo_g = jnp.bitwise_xor(
                lo_best.astype(jnp.int64), jnp.int64(-(2 ** 63))
            )
            out.append((lo_g, cnt > 0, None, hi_g.astype(jnp.int64)))
        elif kind == "bool":
            s, cnt = results[r[1]], results[r[2]]
            out.append((s > 0, cnt > 0))
        elif kind == "var":
            _, fn, si, qi, ci = r
            s, ss, cnt = results[si], results[qi], results[ci]
            cf = cnt.astype(jnp.float64)
            safe_n = jnp.where(cnt > 0, cf, 1.0)
            mean = s / safe_n
            # population variance; numerical floor at 0 (catastrophic
            # cancellation on near-constant data)
            var_pop = jnp.maximum(ss / safe_n - mean * mean, 0.0)
            if fn.endswith("_pop"):
                var = var_pop
                ok = cnt > 0
            else:
                var = var_pop * safe_n / jnp.where(cnt > 1, cf - 1.0, 1.0)
                ok = cnt > 1
            if fn.startswith("stddev"):
                var = jnp.sqrt(var)
            out.append((var, ok))
        elif kind == "moment":
            _, fn, iy, ix, ixy, ixx, iyy, ic = r
            sy, sx, sxy, sxx, syy, cnt = (
                results[iy], results[ix], results[ixy],
                results[ixx], results[iyy], results[ic],
            )
            nf = jnp.where(cnt > 0, cnt, 1).astype(jnp.float64)
            cov_n = sxy - sx * sy / nf  # n * cov
            varx_n = jnp.maximum(sxx - sx * sx / nf, 0.0)  # n * var(x)
            vary_n = jnp.maximum(syy - sy * sy / nf, 0.0)
            if fn == "covar_pop":
                out.append((cov_n / nf, cnt > 0))
            elif fn == "covar_samp":
                denom = jnp.where(cnt > 1, nf - 1.0, 1.0)
                out.append((cov_n / denom, cnt > 1))
            elif fn == "corr":
                denom = jnp.sqrt(varx_n * vary_n)
                ok = (cnt > 1) & (denom > 0)
                out.append((cov_n / jnp.where(ok, denom, 1.0), ok))
            elif fn == "regr_slope":
                ok = (cnt > 1) & (varx_n > 0)
                out.append((cov_n / jnp.where(ok, varx_n, 1.0), ok))
            else:  # regr_intercept = mean(y) - slope * mean(x)
                ok = (cnt > 1) & (varx_n > 0)
                slope = cov_n / jnp.where(ok, varx_n, 1.0)
                out.append(((sy - slope * sx) / nf, ok))
        else:  # dictmm: map best rank back to a dictionary code
            _, fn, arg, si, ci = r
            best_rank, cnt = results[si], results[ci]
            inv = np.argsort(arg.dict.sorted_rank()).astype(np.int32)
            code = jnp.take(
                jnp.asarray(inv),
                jnp.clip(best_rank.astype(jnp.int32), 0, len(inv) - 1),
            )
            out.append((code, cnt > 0))
    return out


_HLL_P = 12  # m = 4096 buckets: ~1.04/sqrt(m) = 1.6% standard error


def _hll_alpha(m: int) -> float:
    if m >= 128:
        return 0.7213 / (1.0 + 1.079 / m)
    return {16: 0.673, 32: 0.697, 64: 0.709}.get(m, 0.7)


def _hash64(data: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer over the value bits — uniform 64-bit hash lanes.
    Floats hash their FULL f64 bit pattern (a f32 downcast would collide
    every double within ~1e-7 relative, blowing the HLL error bound).  The
    reference HLL also hashes 64-bit (Murmur3Hash128 in airlift stats); a
    32-bit hash saturates its value space and biases approx_distinct low by
    ~1% at 1e8 distinct, ~10% at 1e9 (ADVICE r3)."""
    if jnp.issubdtype(data.dtype, jnp.floating):
        data = jax.lax.bitcast_convert_type(data.astype(jnp.float64), jnp.int64)
    mixed = _mix64(data.astype(jnp.int64))  # uint64 lanes
    # drop the sign bit and return int64: downstream packing (seg * m +
    # bucket) runs in int64, and jax promotes int64 x uint64 to f64 (!)
    return (mixed & jnp.uint64(0x7FFF_FFFF_FFFF_FFFF)).astype(jnp.int64)


def _bitlen64(v: jnp.ndarray) -> jnp.ndarray:
    """Bit length of non-negative int64 lanes via 6 halving steps — exact for
    the full 63-bit range (a float log2 is only exact to the mantissa)."""
    v = v.astype(jnp.int64)
    bl = jnp.zeros(v.shape, jnp.int32)
    for s in (32, 16, 8, 4, 2, 1):
        big = (v >> s) > 0
        bl = bl + jnp.where(big, jnp.int32(s), jnp.int32(0))
        v = jnp.where(big, v >> s, v)
    return bl + (v > 0).astype(jnp.int32)


def _segment_hll(
    data_s: jnp.ndarray,
    valid_s: jnp.ndarray,
    seg: jnp.ndarray,
    G: int,
):
    """Grouped HyperLogLog: approx_distinct with CONSTANT sketch state per
    group (reference: ApproximateCountDistinctAggregations over
    HyperLogLogType).  `seg` is each row's group (G for the lanes of no
    group; the groups that have a row are 0..k-1), `valid_s` the argument's
    non-NULL lanes.  TPU shape: one extra sort by (group,
    bucket, rho) puts every (group, bucket)'s MAX rho at its run end;
    per-group sums of 2^-rho are then read at the group ends like every
    other sorted reduction (SortedRuns.read) — no G x m dense state ever
    materializes (empty buckets enter the estimator arithmetically via
    m - nonempty)."""
    from .pallas.segreduce import SegRed, SortedRuns

    n = seg.shape[0]
    m = 1 << _HLL_P
    rest_bits = 63 - _HLL_P  # use the hash's low 63 bits (int64 sign-safe)
    h = _hash64(data_s)  # int64, sign bit clear
    bucket = (h >> rest_bits).astype(jnp.int32)
    rest = h & jnp.int64((1 << rest_bits) - 1)
    # rho = leading-zero count within the rest_bits window + 1
    rho = (rest_bits + 1 - _bitlen64(rest)).astype(jnp.int32)  # [1, 52]
    # a NULL lane stays in its group's run as (bucket 0, rho 0) and adds
    # nothing: every group keeps a row, so the runs stay packed in group
    # order and the frame's lane g is group g
    in_group = seg < G
    valid_s = valid_s & in_group
    combined = seg.astype(jnp.int64) * m + jnp.where(valid_s, bucket, 0)
    dead_val = jnp.int64(G) * m
    combined = jnp.where(in_group, combined, dead_val)
    c_s, rho_s = jax.lax.sort(
        [combined, jnp.where(valid_s, rho, 0)], num_keys=2
    )
    # run ends carry the bucket's max rho (rho ascends within a run)
    bucket_end = jnp.concatenate(
        [c_s[1:] != c_s[:-1], jnp.ones((min(n, 1),), jnp.bool_)]
    ) & (rho_s > 0)
    gid = c_s // m
    live2 = c_s < dead_val
    start = live2 & (
        (jnp.arange(n, dtype=jnp.int32) == 0)
        | (gid != jnp.concatenate([gid[:1], gid[:-1]]))
    )
    contrib_z = jnp.where(bucket_end, 2.0 ** (-rho_s.astype(jnp.float64)), 0.0)
    z_part, e_cnt = SortedRuns(start, live2).read(
        [SegRed("sum", contrib_z, None), SegRed("count", None, bucket_end)], G
    )
    e_cnt = e_cnt.astype(jnp.float64)
    z = (m - e_cnt) + z_part  # empty buckets contribute 2^0 each
    estimate = _hll_alpha(m) * m * m / jnp.maximum(z, 1e-12)
    # small-range (linear counting) correction
    v_empty = m - e_cnt
    small = m * jnp.log(m / jnp.maximum(v_empty, 1.0))
    estimate = jnp.where(
        (estimate < 2.5 * m) & (v_empty > 0), small, estimate
    )
    counts = jnp.round(estimate).astype(jnp.int64)
    counts = jnp.where(e_cnt > 0, counts, 0)
    return counts, None


def _host_collect_agg(
    spec: AggSpec,
    arg: ColumnVal,
    arg2: Optional[ColumnVal],
    perm: jnp.ndarray,
    seg: jnp.ndarray,
    live_s: jnp.ndarray,
    G: int,
    n: int,
    order: tuple = (),
):
    """array_agg / map_agg / listagg: per-group collection on the HOST over
    the sorted grouping (reference: aggregation/ArrayAggregationFunction,
    MapAggAggregationFunction, ListaggAggregationFunction).  Their outputs
    are interned structured values (dict-coded tuples) that a traced kernel
    cannot build, so the executor routes plans containing them through eager
    execution; under jit this raises at trace time."""
    import jax.core as _core

    if isinstance(seg, _core.Tracer):
        raise NotImplementedError(
            f"{spec.fn} requires eager execution (host-collected aggregate)"
        )
    from ..data.page import Dictionary

    perm_h = np.asarray(perm)
    seg_h = np.asarray(seg)
    live_h = np.asarray(live_s)

    def decode(cv: ColumnVal):
        d = np.asarray(cv.data)[perm_h]
        ok = np.asarray(_valid_of(cv, n))[perm_h] & live_h
        if cv.dict is not None:
            table = np.asarray(cv.dict.values, dtype=object)
            d = table[np.clip(d, 0, max(len(table) - 1, 0))]
        return d, ok

    vals, vok = decode(arg)
    keep = live_h & (seg_h < G)
    gs = seg_h[keep]
    v_k, ok_k = vals[keep], vok[keep]
    bounds = np.flatnonzero(np.diff(gs)) + 1
    group_ids = gs[np.concatenate([[0], bounds])] if len(gs) else np.zeros(0, np.int64)
    runs = np.split(np.arange(len(gs)), bounds)

    if order:
        # ordered collection: sort each group's run by the agg's ORDER BY
        # keys (reference: ordering-sensitive aggregation inputs,
        # OrderingCompiler over PagesIndex)
        from .matchrec import host_sort_rank

        lex: list[np.ndarray] = []
        for cv, asc, nulls_first in reversed(order):
            d, ok = decode(cv)
            null_rank, rank = host_sort_rank(
                d[keep], ok[keep], None, asc, nulls_first
            )
            lex.append(rank)
            lex.append(null_rank)
        runs = [r[np.lexsort([k[r] for k in lex])] if len(r) > 1 else r
                for r in runs]

    def _dedup_first(seq):
        seen: set = set()
        out = []
        for v in seq:
            if v not in seen:
                seen.add(v)
                out.append(v)
        return out

    results: list = []
    res_ok: list[bool] = []
    if spec.fn == "listagg":
        sep = spec.sep if spec.sep is not None else ","
        for r in runs:
            parts = [str(v_k[i]) for i in r if ok_k[i]]
            if spec.distinct:
                parts = _dedup_first(parts)
            results.append(sep.join(parts))
            res_ok.append(bool(parts))
    elif spec.fn == "array_agg":
        for r in runs:
            vals_r = [
                v_k[i].item() if isinstance(v_k[i], np.generic) else v_k[i]
                for i in r if ok_k[i]
            ]
            if spec.distinct:
                vals_r = _dedup_first(vals_r)
            results.append(tuple(vals_r))
            res_ok.append(True)
    else:  # map_agg(key, value): NULL keys skipped, last value wins
        kv, kok = decode(arg2)
        kv_k, kok_k = kv[keep], kok[keep]
        for r in runs:
            m: dict = {}
            for i in r:
                if ok_k[i]:
                    key = v_k[i].item() if isinstance(v_k[i], np.generic) else v_k[i]
                    val = (
                        (kv_k[i].item() if isinstance(kv_k[i], np.generic) else kv_k[i])
                        if kok_k[i]
                        else None
                    )
                    m[key] = val
            try:  # canonical map form: pairs sorted by key (data/types.py)
                items = sorted(m.items())
            except TypeError:
                items = sorted(m.items(), key=lambda it: repr(it[0]))
            results.append(tuple(items))
            res_ok.append(bool(m))

    # intern without sorting (tuples may mix None with values; np.unique
    # would compare them) and scatter into the [G] output frame
    table: dict = {}
    codes = np.zeros((G,), np.int32)
    valid = np.zeros((G,), bool)
    for gid, res, ok in zip(group_ids, results, res_ok):
        codes[gid] = table.setdefault(res, len(table))
        valid[gid] = ok
    uniq = np.empty(max(len(table), 1), dtype=object)
    uniq[0] = "" if spec.fn == "listagg" else ()
    for val, code in table.items():
        uniq[code] = val
    return jnp.asarray(codes), jnp.asarray(valid), Dictionary(uniq)


def _global_aggregate(agg_args, specs, live, agg_args2=None, agg_order=None):
    """No GROUP BY: one output row even over empty input (SQL semantics).

    Non-DISTINCT aggregates run through the fused segmented reduction with a
    single segment — on TPU that means the Pallas kernel's exact-int64 and
    Kahan-compensated float paths serve global sums too (a plain jnp.sum of
    "float64" on TPU silently accumulates in f32)."""
    n = live.shape[0]
    if agg_args2 is None:
        agg_args2 = [None] * len(specs)
    if agg_order is None:
        agg_order = [()] * len(specs)
    seg = jnp.zeros((n,), jnp.int32)
    fused = _fused_aggs(agg_args, specs, seg, live, 1, n, agg_args2=agg_args2)
    out_aggs = []
    for i, ((arg, spec), pre) in enumerate(zip(zip(agg_args, specs), fused)):
        if pre is not None:
            out_aggs.append(pre)
            continue
        if spec.fn in HOST_AGGS:
            perm1 = jnp.arange(n, dtype=jnp.int32)
            out_aggs.append(
                _host_collect_agg(
                    spec, arg, agg_args2[i], perm1, seg, live, 1, n,
                    order=agg_order[i],
                )
            )
            continue
        valid = _valid_of(arg, n) & live
        if spec.fn == "approx_distinct":
            # dead lanes to the back: the sketch wants its rows grouped
            cnts, _ = _segment_hll(
                arg.data, valid, jnp.where(live, 0, 1).astype(jnp.int32), 1
            )
            out_aggs.append((cnts, None))
            continue
        if spec.distinct:
            k = _sortable_key(arg)
            inv_s, k_s = jax.lax.sort([(~valid).astype(jnp.int8), k], num_keys=2)
            vs = ~(inv_s.astype(jnp.bool_))
            prev = jnp.concatenate([k_s[:1], k_s[:-1]])
            first = jnp.zeros((n,), jnp.bool_).at[0].set(True)
            cnt = jnp.sum(((first | (k_s != prev)) & vs).astype(jnp.int64))
            out_aggs.append((cnt.reshape(1), None))
            continue
        if spec.fn == "percentile":
            inv_s, d_s = jax.lax.sort(
                [(~valid).astype(jnp.int8), arg.data], num_keys=2
            )
            vcnt = jnp.sum(valid.astype(jnp.int64))
            off = jnp.floor(
                spec.param * jnp.maximum(vcnt - 1, 0).astype(jnp.float64) + 0.5
            )
            idx = jnp.clip(off.astype(jnp.int64), 0, max(n - 1, 0))
            out_aggs.append((jnp.take(d_s, idx).reshape(1), (vcnt > 0).reshape(1)))
            continue
        raise NotImplementedError(spec.fn)  # non-distinct is fully fused above
    out_live = jnp.ones((1,), jnp.bool_)
    return [], out_aggs, out_live, jnp.int32(1)


# ------------------------------------------------------------------- joins


_MIX_CONST = np.uint64(0x9E3779B97F4A7C15)


def _mix64(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer — vectorized avalanche mix."""
    x = x.astype(jnp.uint64)
    x = x + jnp.uint64(_MIX_CONST)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> jnp.uint64(31))
    return x


def _combined_hash(keys: Sequence[ColumnVal], live: jnp.ndarray, n: int, sentinel: int):
    """Hash-combine key columns to int63; rows that are dead or have a null
    key get `sentinel` (never matches).  Exact key equality is re-verified
    after candidate expansion, so collisions only cost, never corrupt.

    VARCHAR columns hash by dictionary VALUE via Dictionary.hash64() (the
    one value-hash table, shared with runtime/wire.py partition_page) — so
    hash-partitioning two different varchar columns routes equal strings to
    the same shard even though their code spaces differ.  This is what lets
    string-keyed joins run PARTITIONED instead of forcing broadcast."""
    h = jnp.zeros((n,), dtype=jnp.uint64)
    ok = live
    for kv in keys:
        if kv.dict is not None:
            table = kv.dict.hash64()
            bits = jnp.take(
                jnp.asarray(table), jnp.clip(kv.data, 0, len(table) - 1)
            )
        else:
            bits = kv.data
            if jnp.issubdtype(bits.dtype, jnp.floating):
                bits = jax.lax.bitcast_convert_type(bits.astype(jnp.float64), jnp.uint64)
            else:
                bits = bits.astype(jnp.int64).astype(jnp.uint64)
            if kv.data2 is not None:
                # Values that fit in int64 carry a sign-extension high limb;
                # mix hi only when it adds information so limbed and
                # non-limbed representations of the same value hash alike.
                lo = kv.data.astype(jnp.int64)
                hi = kv.data2.astype(jnp.int64)
                extra = jnp.where(
                    hi == (lo >> 63), jnp.uint64(0), _mix64(hi.astype(jnp.uint64))
                )
                bits = bits ^ extra
        h = _mix64(h ^ _mix64(bits))
        ok = ok & _valid_of(kv, n)
    h = (h & jnp.uint64(0x3FFF_FFFF_FFFF_FFFF)).astype(jnp.int64)
    return jnp.where(ok, h, jnp.int64(sentinel))


_SENT_BUILD = (1 << 62) + 2  # sorts after every real hash
_SENT_PROBE = (1 << 62) + 1  # != build sentinel -> dead probes match nothing


def _in_null_facts(left_keys, right_keys, left_live, right_live, nl, nr):
    """The three facts SQL IN's three-valued logic turns on: does the build
    side have any live row, does it hold a NULL key, is the probe key
    non-NULL.  Shared by null_anti (NOT IN filter) and mark_in (IN column)."""
    build_any = jnp.any(right_live)
    build_has_null = jnp.zeros((), jnp.bool_)
    probe_ok = jnp.ones((nl,), jnp.bool_)
    for rk in right_keys:
        build_has_null = build_has_null | jnp.any(right_live & ~_valid_of(rk, nr))
    for lk in left_keys:
        probe_ok = probe_ok & _valid_of(lk, nl)
    return build_any, build_has_null, probe_ok


# the kinds that filter or mark the left page: their answer is one bit a
# probe row, so they need no frame where the rank itself is exact
_FILTERING = ("semi", "anti", "null_anti", "mark", "mark_in")
# those of them a one-comparison residual leaves two-valued (IN's three-valued
# forms keep the frame under a residual)
MINMAX_KINDS = ("semi", "anti", "mark")

# "some build row of my run satisfies `a <op> b`", asked of the run's smallest
# and largest b
_RUN_COMPARE = {
    "ne": lambda a, bmin, bmax: (bmin != a) | (bmax != a),
    "lt": lambda a, bmin, bmax: a < bmax,
    "le": lambda a, bmin, bmax: a <= bmax,
    "gt": lambda a, bmin, bmax: a > bmin,
    "ge": lambda a, bmin, bmax: a >= bmin,
}


def _plain_integer(v: ColumnVal) -> bool:
    return (v.dict is None and v.data2 is None
            and jnp.issubdtype(v.data.dtype, jnp.integer))


def _all_valid(live: jnp.ndarray, vals) -> jnp.ndarray:
    for v in vals:
        if v.valid is not None:
            live = live & v.valid
    return live


def _exact_key_words(left_keys, right_keys) -> Optional[list]:
    """The key columns as (probe word, build word) pairs whose `==`, word for
    word, IS the SQL key's — the words the frame's verification compares:
    each column's `data` in the two sides' common dtype, and the high limb
    where a side has one (a single-lane side sign-extends).  None where a
    column's sort order is not its equality (floating point: NaN, -0.0)."""
    words = []
    for lk, rk in zip(left_keys, right_keys):
        limbed = lk.data2 is not None or rk.data2 is not None
        dt = jnp.int64 if limbed else jnp.promote_types(lk.data.dtype, rk.data.dtype)
        if dt == jnp.bool_:
            dt = jnp.int8
        if not jnp.issubdtype(dt, jnp.integer):
            return None
        lo_l, lo_r = lk.data.astype(dt), rk.data.astype(dt)
        words.append((lo_l, lo_r))
        if limbed:
            words.append((lo_l >> 63 if lk.data2 is None else lk.data2.astype(dt),
                          lo_r >> 63 if rk.data2 is None else rk.data2.astype(dt)))
    return words


def filter_form(kind: str, rank: str, key_words, residual, compare) -> Optional[str]:
    """How a join that filters or marks its left page finds its answer (None
    for the kinds that output an expansion): "rank" — no residual, a probe
    row matches iff its run of equal keys holds a build row; "minmax" — the
    residual is ONE comparison `a <op> b` of a probe-side with a build-side
    integer, asked of the run's smallest and largest b (semi, anti, mark:
    IN's three-valued forms keep the frame); "frame" — the inner join's
    expansion, for any other residual, a key whose order is not its equality,
    and the few probes that `rank_form` sends to a binary search (their
    frame is as small as they are).  Kind, the residual's shape and the
    traced shapes decide: no switch."""
    if kind not in _FILTERING:
        return None
    if rank == "scan" or key_words is None:
        return "frame"
    if residual is None:
        return "rank"
    if (compare is not None and kind in MINMAX_KINDS and compare[0] in _RUN_COMPARE
            and _plain_integer(compare[1]) and _plain_integer(compare[2])):
        return "minmax"
    return "frame"


def _hit_by_rank(key_words, probe_ok, build_ok, compare):
    """-> for each probe row, whether a live build row has its key (and, with
    `compare` = (op, a, b), whether one of them satisfies `a <op> b`), from
    ONE sort of build ++ probe lanes by (the key's own words[, b], lane):
    exact with nothing left to verify, and no lane of an expansion.

    A lane that is dead or has a NULL key (a build lane whose b is NULL too:
    it can satisfy nothing) carries the lane number's top bit, so within a
    run of equal keys the order is live build lanes (by b, where b rides),
    live probe lanes, then the dead of either side.  A probe's run holds a
    build row iff the last live build lane at or before it lies at or after
    the run's first lane — two running maxima of positions.  With b riding
    (probe and dead lanes at its dtype's largest value, so they sort behind),
    the run's smallest b stands at the run's first lane and its largest at
    that last build lane: two gathers of probe-many lanes after a sort home
    on the lane number, as `_merged_bounds` goes home.  Without b the answer
    is one bit and rides home in the lane number's lowest."""
    nl, nr = probe_ok.shape[0], build_ok.shape[0]
    operands = [jnp.concatenate([b, p]) for p, b in key_words]
    if compare is not None:
        op, a, b = compare
        probe_ok, build_ok = _all_valid(probe_ok, [a]), _all_valid(build_ok, [b])
        top = jnp.iinfo(b.data.dtype).max
        operands.append(jnp.concatenate(
            [jnp.where(build_ok, b.data, top), jnp.full((nl,), top, b.data.dtype)]))
    dead = jnp.uint32(1 << 31)
    lane = jnp.arange(nr + nl, dtype=jnp.uint32)
    lane = jnp.where(jnp.concatenate([build_ok, probe_ok]), lane, lane | dead)
    *sorted_words, lane_s = jax.lax.sort(
        operands + [lane], num_keys=len(operands) + 1, is_stable=False)
    first = jnp.zeros((nr + nl - 1,), jnp.bool_)
    for w in sorted_words[:len(key_words)]:
        first = first | (w[1:] != w[:-1])
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), first])
    pos = jnp.arange(nr + nl, dtype=jnp.int32)
    start = jax.lax.cummax(jnp.where(first, pos, 0))
    last_b = jax.lax.cummax(jnp.where(lane_s < nr, pos, -1))
    came = lane_s & ~dead  # the lane it came from: build lanes first
    is_p = came >= nr
    probe = came - jnp.uint32(nr)  # wraps above every probe for a build lane
    away = jnp.uint32(0xFFFFFFFF)
    if compare is None:
        held = (last_b >= start).astype(jnp.uint32)
        home = jax.lax.sort(jnp.where(is_p, (probe << 1) | held, away), is_stable=False)
        return probe_ok & ((home[:nl] & 1) == 1)
    _, start, last_b = jax.lax.sort(
        [jnp.where(is_p, probe, away), start, last_b], num_keys=1, is_stable=False)
    start, last_b = start[:nl], last_b[:nl]
    b_s = sorted_words[-1]
    bmin, bmax = jnp.take(b_s, start), jnp.take(b_s, jnp.maximum(last_b, 0))
    dt = jnp.promote_types(a.data.dtype, b_s.dtype)
    some = _RUN_COMPARE[op](a.data.astype(dt), bmin.astype(dt), bmax.astype(dt))
    return probe_ok & (last_b >= start) & some


def _filtered(kind, left_cols, left_live, left_keys, right_keys, right_live, hit):
    """A filtering kind's output from `hit`, one bit a probe row."""
    nl, nr = left_live.shape[0], right_live.shape[0]
    if kind in ("mark", "mark_in"):
        from ..data.types import BOOLEAN

        if kind == "mark":
            mark = ColumnVal(hit, None, None, BOOLEAN)
        else:
            build_any, build_has_null, probe_ok = _in_null_facts(
                left_keys, right_keys, left_live, right_live, nl, nr
            )
            # TRUE on match; else FALSE when definitively absent (non-null
            # probe, no build NULLs, or empty build); else NULL (unknown)
            definite = hit | ~build_any | (probe_ok & ~build_has_null)
            mark = ColumnVal(hit, definite, None, BOOLEAN)
        return list(left_cols) + [mark], left_live
    if kind == "semi":
        return list(left_cols), left_live & hit
    if kind == "anti":
        return list(left_cols), left_live & ~hit
    # null_anti: SQL three-valued NOT IN
    build_any, build_has_null, probe_ok = _in_null_facts(
        left_keys, right_keys, left_live, right_live, nl, nr
    )
    keep = jnp.where(build_any, ~hit & probe_ok & ~build_has_null, True)
    return list(left_cols), left_live & keep


def equi_join(
    kind: str,
    left_cols: Sequence[ColumnVal],
    left_live: jnp.ndarray,
    right_cols: Sequence[ColumnVal],
    right_live: jnp.ndarray,
    left_keys: Sequence[ColumnVal],
    right_keys: Sequence[ColumnVal],
    residual: Optional[Callable[[list[ColumnVal], int], jnp.ndarray]],
    out_capacity: int,
    compare: Optional[tuple[str, ColumnVal, ColumnVal]] = None,
):
    """Sort equi-join.  kind: inner | left | full | semi | anti | null_anti |
    mark | mark_in.

    inner/left -> (out_cols, out_live, required) with capacity
      out_capacity (+ n_left extra lanes for left-join unmatched rows).
    semi/anti  -> (left_cols, new_live, required): filters the left page.
    null_anti is the NOT IN lowering (reference: SemiJoinNode + the
      null-aware rewrite in TransformCorrelatedInPredicateToJoin): with a
      non-empty build side, probe rows whose key is NULL — or any probe row
      when the build side contains a NULL key — evaluate NOT IN to NULL and
      are filtered; an empty build side keeps every probe row.
    mark / mark_in -> (left_cols + [match BOOLEAN column], left_live,
      required): the membership test becomes a COLUMN instead of a filter —
      the lowering for EXISTS / IN in general expression positions (OR'd
      predicates, select items; reference: SemiJoinNode's
      semiJoinOutput symbol).  mark is two-valued (EXISTS); mark_in is
      SQL three-valued: NULL when the probe key is NULL or the build side
      holds a NULL key and there is no match (an empty build is FALSE).
    `required` is the true expansion size for the host's retry loop — or
    None where the join built no expansion and so has no need to report.

    The filtering and marking kinds build no frame of `out_capacity` lanes
    where their answer can be read off the probe rows' rank among the build
    rows (`filter_form`, `_hit_by_rank`): with no residual ("rank": all five
    kinds), and — semi, anti and mark — with a residual that is ONE
    comparison ne | lt | le | gt | ge between a probe-side and a build-side
    integer ("minmax"), which the caller that knows the residual's IR hands
    over as `compare` = (op, a over the left page, b over the right page),
    meaning `a <op> b`, beside `residual`.  Any other residual, null_anti or
    mark_in with one, a floating-point key, and the few probes that
    `rank_form` sends to a binary search take the frame, as inner, left and
    full do.
    """
    nl = left_live.shape[0]
    nr = right_live.shape[0]
    C = out_capacity

    from .kernels import record_dispatch

    rank = rank_form(nr, nl)
    said = kind + ("+residual" if residual is not None else "")
    record_dispatch("join", "sort", f"{said} build {nr} probe {nl} -> C {C}")
    record_dispatch("join_rank", rank, f"{nr} ++ {nl} lanes -> C {C}")
    key_words = _exact_key_words(left_keys, right_keys) if kind in _FILTERING else None
    form = filter_form(kind, rank, key_words, residual, compare)
    if form is not None:
        how = f" -> C {C}" if form == "frame" else f", {len(key_words)} key words"
        if form == "minmax":
            how += f", {compare[0]} of the run's min and max"
        record_dispatch("join_filter", form, f"{said} {nr} ++ {nl} lanes{how}")
    if form in ("rank", "minmax"):
        hit = _hit_by_rank(
            key_words, _all_valid(left_live, left_keys), _all_valid(right_live, right_keys),
            compare if form == "minmax" else None)
        return *_filtered(kind, left_cols, left_live, left_keys, right_keys,
                          right_live, hit), None
    bh = _combined_hash(right_keys, right_live, nr, _SENT_BUILD)
    ph = _combined_hash(left_keys, left_live, nl, _SENT_PROBE)
    iota_r = jnp.arange(nr, dtype=jnp.int32)
    bh_sorted, perm_b = jax.lax.sort([bh, iota_r], num_keys=1)
    if rank == "scan":
        lo = searchsorted_tpu(bh_sorted, ph, side="left").astype(jnp.int64)
        hi = searchsorted_tpu(bh_sorted, ph, side="right").astype(jnp.int64)
    else:
        lo, hi = _merged_bounds(bh, ph)
    counts = (hi - lo).astype(jnp.int64)
    cum = jnp.cumsum(counts)
    total = cum[-1]

    j = jnp.arange(C, dtype=jnp.int64)
    pidx_c, k = expand_rows(cum, C)
    bpos = jnp.take(lo, pidx_c).astype(jnp.int64) + k
    bpos_c = jnp.clip(bpos, 0, nr - 1).astype(jnp.int32)
    bidx = jnp.take(perm_b, bpos_c)
    in_range = j < total

    # exact key verification (hash collisions + sentinel lanes); decimal128
    # keys verify BOTH limbs — the combined hash folds only the lo limb, so
    # hi-limb collisions must be filtered here (a single-lane side
    # sign-extends into limb space, reference: spi/type/Int128Math.java)
    eq = in_range
    for lk, rk in zip(left_keys, right_keys):
        lv = jnp.take(lk.data, pidx_c)
        rv = jnp.take(rk.data, bidx)
        lval = jnp.take(_valid_of(lk, nl), pidx_c)
        rval = jnp.take(_valid_of(rk, nr), bidx)
        eq = eq & (lv == rv) & lval & rval
        if lk.data2 is not None or rk.data2 is not None:
            lhi = (
                jnp.take(lk.data2, pidx_c)
                if lk.data2 is not None
                else lv.astype(jnp.int64) >> 63
            )
            rhi = (
                jnp.take(rk.data2, bidx)
                if rk.data2 is not None
                else rv.astype(jnp.int64) >> 63
            )
            eq = eq & (lhi == rhi)

    # gather both sides into the expansion frame (decimal128 columns carry
    # their high limb through the gather)
    gathered: list[ColumnVal] = []
    for cv in left_cols:
        gathered.append(
            ColumnVal(
                jnp.take(cv.data, pidx_c),
                None if cv.valid is None else jnp.take(cv.valid, pidx_c),
                cv.dict,
                cv.type,
                None if cv.data2 is None else jnp.take(cv.data2, pidx_c),
            )
        )
    for cv in right_cols:
        gathered.append(
            ColumnVal(
                jnp.take(cv.data, bidx),
                None if cv.valid is None else jnp.take(cv.valid, bidx),
                cv.dict,
                cv.type,
                None if cv.data2 is None else jnp.take(cv.data2, bidx),
            )
        )
    match = eq
    if residual is not None:
        match = match & residual(gathered, C)

    required = total

    if kind in _FILTERING:
        hit = jnp.zeros((nl,), jnp.bool_).at[pidx_c].max(match, mode="drop")
        return *_filtered(kind, left_cols, left_live, left_keys, right_keys,
                          right_live, hit), required

    if kind == "inner":
        return gathered, match, required

    if kind in ("left", "full"):
        # expansion lanes ++ unmatched left lanes with null right columns
        # (full: ++ unmatched RIGHT lanes with null left columns too)
        hit = jnp.zeros((nl,), jnp.bool_).at[pidx_c].max(match, mode="drop")
        unmatched = left_live & ~hit
        full = kind == "full"
        if full:
            bhit = jnp.zeros((nr,), jnp.bool_).at[bidx].max(match, mode="drop")
            unmatched_r = right_live & ~bhit
        out: list[ColumnVal] = []
        for i, cv in enumerate(left_cols):
            data = jnp.concatenate([gathered[i].data, cv.data])
            data2 = (
                None
                if cv.data2 is None
                else jnp.concatenate([gathered[i].data2, cv.data2])
            )
            valid = (
                None
                if cv.valid is None and not full
                else jnp.concatenate(
                    [
                        gathered[i].valid
                        if gathered[i].valid is not None
                        else jnp.ones((C,), jnp.bool_),
                        cv.valid if cv.valid is not None else jnp.ones((nl,), jnp.bool_),
                    ]
                )
            )
            if full:
                data = jnp.concatenate([data, jnp.zeros((nr,), cv.data.dtype)])
                valid = jnp.concatenate([valid, jnp.zeros((nr,), jnp.bool_)])
                if data2 is not None:
                    data2 = jnp.concatenate([data2, jnp.zeros((nr,), data2.dtype)])
            out.append(ColumnVal(data, valid, cv.dict, cv.type, data2))
        off = len(left_cols)
        for i, cv in enumerate(right_cols):
            g = gathered[off + i]
            gv = g.valid if g.valid is not None else jnp.ones((C,), jnp.bool_)
            data = jnp.concatenate([g.data, jnp.zeros((nl,), cv.data.dtype)])
            valid = jnp.concatenate([gv, jnp.zeros((nl,), jnp.bool_)])
            data2 = (
                None
                if cv.data2 is None
                else jnp.concatenate([g.data2, jnp.zeros((nl,), cv.data2.dtype)])
            )
            if full:
                data = jnp.concatenate([data, cv.data])
                valid = jnp.concatenate(
                    [
                        valid,
                        cv.valid if cv.valid is not None else jnp.ones((nr,), jnp.bool_),
                    ]
                )
                if data2 is not None:
                    data2 = jnp.concatenate([data2, cv.data2])
            out.append(ColumnVal(data, valid, cv.dict, cv.type, data2))
        out_live = jnp.concatenate([match, unmatched])
        if full:
            out_live = jnp.concatenate([out_live, unmatched_r])
        return out, out_live, required

    raise NotImplementedError(f"join kind {kind}")


def broadcast_single_row(
    left_cols: Sequence[ColumnVal],
    left_live: jnp.ndarray,
    right_cols: Sequence[ColumnVal],
    right_live: jnp.ndarray,
):
    """Cross join against a single-row relation (scalar-subquery shape):
    broadcast the one live right row across the left page."""
    nl = left_live.shape[0]
    ridx = jnp.argmax(right_live)  # the single live row
    any_right = jnp.any(right_live)
    out = list(left_cols)
    for cv in right_cols:
        val = cv.data[ridx]
        data = jnp.full((nl,), val, dtype=cv.data.dtype)
        if cv.valid is None:
            valid = jnp.broadcast_to(any_right, (nl,))
        else:
            valid = jnp.broadcast_to(cv.valid[ridx] & any_right, (nl,))
        data2 = (
            None
            if cv.data2 is None
            else jnp.full((nl,), cv.data2[ridx], dtype=cv.data2.dtype)
        )
        out.append(ColumnVal(data, valid, cv.dict, cv.type, data2))
    return out, left_live


# ------------------------------------------------------------- sort / topn


# compact_rows: the columns ride the sort where the frame holds at least one
# lane in 24 of the input.  Chip microbenchmark (PERF.md section 6, PR 43),
# five words a row: carried 448 ms at 60M lanes whatever the frame, gathered
# 424 into 2^21 lanes (a 29th) and 703 into 2^22 (a 14th); 27 ms at 6M lanes
# against 20 into 2^17 (a 46th) and 44 into 2^18 (a 23rd)
_COMPACT_CARRY_RATIO = 24


def compact_form(n: int, cap: int, words: int) -> str:
    """How `compact_rows` moves `words` 32-bit words a row from `n` lanes
    into a frame of `cap`: "carry" (operands of the sort) or "gather"
    (fetched through the sorted permutation).  A word costs the sort about
    a nanosecond a lane of INPUT and a gather 19-27 a lane of OUTPUT, so
    the gather wins only into a frame that is small against the input; one
    word rides in the permutation's place at no cost at all.  A function of
    the traced shapes alone: the CPU traces the form the chip runs."""
    if words <= 1 or cap * _COMPACT_CARRY_RATIO >= n:
        return "carry"
    return "gather"


def compact_rows(cols, live, cap: int):
    """Move live rows into `cap` lanes (dead lanes drop).  One sort on
    (dead flag, lane number) brings live rows to the front in their
    original order (dead ones follow in theirs); no scatter (TPU scatters
    serialize).  How the columns follow is `compact_form`'s choice by
    shape: "carry" — every array of every column (`data`; `valid` and
    `data2` where there) is an operand of that sort, sliced to `cap`
    afterwards: no permutation, no gather (q12's compactions, frames of a
    quarter to a half of their inputs); "gather" — the sort yields the
    permutation and every array takes `cap` lanes through it (q18's: 4,096
    lanes out of 15M).  Both leave the same bits in every lane, dead ones
    included.  Returns (cols, live, required) with required = true live
    count for the capacity-retry protocol, whatever `cap` is."""
    from .kernels import record_dispatch

    n = live.shape[0]
    arrays: dict = {}  # id -> array: what a row holds, each once
    for cv in cols:
        for a in (cv.data, cv.valid, cv.data2):
            if a is not None:
                arrays.setdefault(id(a), a)
    words = sum(
        max(1, a.dtype.itemsize // 4) * int(np.prod(a.shape[1:]))
        for a in arrays.values()
    )
    form = compact_form(n, cap, words)
    record_dispatch("compact", form, f"{n} -> {cap} lanes, {words} words")
    if form == "gather":
        iota = jnp.arange(n, dtype=jnp.int32)
        perm = jax.lax.sort([(~live).astype(jnp.int8), iota], num_keys=2,
                            is_stable=True)[-1]
        take = perm[:cap]
        required = jnp.sum(live.astype(jnp.int64))
        at = {k: jnp.take(a, take, axis=0) for k, a in arrays.items()}
    else:
        # the lane's number under the dead flag, ONE u32 key (n < 2^31): a
        # strict total order, so the sort need not be stable — a stable one
        # on the flag alone drags a hidden index operand (PERF.md section 6,
        # PR 43: 491 ms for 448 at 60M lanes, compiled in 98 s for 60)
        key = jnp.arange(n, dtype=jnp.uint32) | (
            (~live).astype(jnp.uint32) << 31)
        # a mask rides as s8.  An array that is not one lane a row (none
        # reaches a Compact today) cannot be an operand of the sort: it is
        # gathered by row through the sorted key's lane numbers
        ride = {k: a for k, a in arrays.items() if a.ndim == 1}
        out = jax.lax.sort(
            [key] + [a.astype(jnp.int8) if a.dtype == jnp.bool_ else a
                     for a in ride.values()],
            num_keys=1, is_stable=False,
        )
        required = jnp.sum(live.astype(jnp.int64))
        at = {k: o[:cap].astype(a.dtype)
              for (k, a), o in zip(ride.items(), out[1:])}
        if len(ride) < len(arrays):
            take = (out[0][:cap] & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
            at.update((k, jnp.take(a, take, axis=0))
                      for k, a in arrays.items() if k not in ride)
    out_cols = [
        ColumnVal(
            at[id(cv.data)],
            None if cv.valid is None else at[id(cv.valid)],
            cv.dict,
            cv.type,
            None if cv.data2 is None else at[id(cv.data2)],
        )
        for cv in cols
    ]
    out_live = jnp.arange(cap, dtype=jnp.int64) < jnp.minimum(required, cap)
    return out_cols, out_live, required


def sort_rows(
    cols: Sequence[ColumnVal],
    live: jnp.ndarray,
    keys: Sequence[ColumnVal],
    specs: Sequence[SortSpec],
):
    """Stable multi-key sort; dead rows sink to the end."""
    n = live.shape[0]
    operands: list[jnp.ndarray] = [(~live).astype(jnp.int8)]
    for kv, spec in zip(keys, specs):
        valid = _valid_of(kv, n)
        # smaller flag sorts first: nulls-first -> nulls get 0, else nulls get 1
        null_flag = valid if spec.nulls_first else ~valid
        operands.append(null_flag.astype(jnp.int8))
        operands.extend(_sortable_operands(kv, descending=not spec.ascending))
    iota = jnp.arange(n, dtype=jnp.int32)
    sorted_ops = jax.lax.sort(operands + [iota], num_keys=len(operands), is_stable=True)
    perm = sorted_ops[-1]
    out = [
        ColumnVal(
            jnp.take(cv.data, perm),
            None if cv.valid is None else jnp.take(cv.valid, perm),
            cv.dict,
            cv.type,
            None if cv.data2 is None else jnp.take(cv.data2, perm),
        )
        for cv in cols
    ]
    return out, jnp.take(live, perm)


def top_n(cols, live, keys, specs, count: int, cap: Optional[int] = None):
    """TopN.  Returns (cols, live, required).

    Radix-select path (TPU, large inputs): find the exact K-th threshold of
    the leading key in four histogram passes (ops/pallas/topk.py), compact
    the <= `cap` candidate rows, and sort only those — no O(n log n) sort,
    no full-width permutation of the relation (the reference's bounded-heap
    TopNOperator.java:32 economy, achieved with branch-free vector passes).
    `required` is the candidate count for the executor's capacity retry;
    the sort fallback reports 0 (never retries).
    """
    n = live.shape[0]
    from .pallas.topk import radix_topk_supported, radix_topk_threshold, sortable_u32

    if (
        cap is not None and cap >= count and keys
        and keys[0].data2 is None  # radix threshold is 32-bit single-lane
        and radix_topk_supported(n, count)
    ):
        from .kernels import record_dispatch

        record_dispatch("top_n", "pallas", f"radix select k {count} of {n}")
        kv, spec = keys[0], specs[0]
        valid = _valid_of(kv, n)
        u = sortable_u32(_sortable_key(kv), descending=False)
        if spec.ascending:  # first rows of the order == smallest keys
            u = ~u
        null_u = jnp.uint32(0xFFFFFFFF) if spec.nulls_first else jnp.uint32(0)
        u = jnp.where(valid, u, null_u)
        thresh = radix_topk_threshold(u, live, count)
        cand = live & (u >= thresh)
        required = jnp.sum(cand.astype(jnp.int64))
        # compact candidate row ids into the static buffer
        pos = jnp.cumsum(cand.astype(jnp.int32)) - 1
        scatter_to = jnp.where(cand, pos, cap)
        idx_buf = (
            jnp.zeros((cap,), jnp.int32)
            .at[scatter_to]
            .set(jnp.arange(n, dtype=jnp.int32), mode="drop")
        )
        lane_live = jnp.arange(cap, dtype=jnp.int64) < jnp.minimum(
            required, cap
        )

        def gather(cv: ColumnVal) -> ColumnVal:
            return ColumnVal(
                jnp.take(cv.data, idx_buf),
                None if cv.valid is None else jnp.take(cv.valid, idx_buf),
                cv.dict,
                cv.type,
                None if cv.data2 is None else jnp.take(cv.data2, idx_buf),
            )

        sub_cols = [gather(cv) for cv in cols]
        sub_keys = [gather(kv_) for kv_ in keys]
        sorted_cols, sorted_live = sort_rows(sub_cols, lane_live, sub_keys, specs)
        k = min(count, n)
        out = [
            ColumnVal(
                cv.data[:k],
                None if cv.valid is None else cv.valid[:k],
                cv.dict,
                cv.type,
                None if cv.data2 is None else cv.data2[:k],
            )
            for cv in sorted_cols
        ]
        return out, sorted_live[:k], required

    sorted_cols, sorted_live = sort_rows(cols, live, keys, specs)
    k = min(count, n)
    out = [
        ColumnVal(
            cv.data[:k],
            None if cv.valid is None else cv.valid[:k],
            cv.dict,
            cv.type,
            None if cv.data2 is None else cv.data2[:k],
        )
        for cv in sorted_cols
    ]
    return out, sorted_live[:k], jnp.int64(0)


def limit_mask(live: jnp.ndarray, count: int) -> jnp.ndarray:
    return live & (jnp.cumsum(live.astype(jnp.int64)) <= count)


def unnest_expand(
    cols: Sequence[ColumnVal],
    live: jnp.ndarray,
    arrays: Sequence[ColumnVal],
    elem_types,
    with_ordinality: bool,
    outer: bool,
    C: int,
):
    """Expand rows by array length (reference: operator/unnest/UnnestOperator).

    Arrays are dict-coded (ArrayType): per-row lengths come from a host
    length table gathered by code; elements come from a padded [n_distinct,
    maxlen] device matrix.  Expansion is the standard static-shape pattern:
    exclusive-scan of lengths -> searchsorted row lookup per output lane,
    with the true required size reported for the capacity-retry loop.
    Multiple arrays zip (Trino semantics): rows extend to the longest array,
    shorter arrays NULL-pad.  `outer` emits one NULL-element row for
    empty/NULL arrays (LEFT JOIN UNNEST ... ON TRUE).
    """
    n = int(live.shape[0])

    len_tables = []  # jnp [n_distinct] per array
    elem_mats = []  # jnp [n_distinct, maxlen] per array
    elem_dicts = []  # Dictionary | None per array
    for arr, et in zip(arrays, elem_types):
        vals = arr.dict.values
        lens_np = np.asarray([len(v) for v in vals], dtype=np.int64)
        maxlen = max(1, int(lens_np.max()) if len(lens_np) else 1)
        if et.is_string:
            flat = sorted({str(x) for v in vals for x in v}) or [""]
            ed = Dictionary(np.asarray(flat, dtype=object))
            mat = np.zeros((len(vals), maxlen), dtype=np.int32)
            for r, v in enumerate(vals):
                for c, x in enumerate(v):
                    mat[r, c] = ed.code_of(str(x))
        else:
            ed = None
            mat = np.zeros((len(vals), maxlen), dtype=et.np_dtype)
            for r, v in enumerate(vals):
                for c, x in enumerate(v):
                    mat[r, c] = 0 if x is None else x
        len_tables.append(jnp.asarray(lens_np))
        elem_mats.append(jnp.asarray(mat))
        elem_dicts.append(ed)

    # per-row expansion length = max over zipped arrays (NULL array -> 0)
    row_lens = jnp.zeros((n,), dtype=jnp.int64)
    arr_lens = []
    for arr, lt in zip(arrays, len_tables):
        ln = jnp.take(lt, arr.data)
        if arr.valid is not None:
            ln = jnp.where(arr.valid, ln, 0)
        arr_lens.append(ln)
        row_lens = jnp.maximum(row_lens, ln)
    row_lens = jnp.where(live, row_lens, 0)
    pre_outer_lens = row_lens  # before the outer null-extension bump
    if outer:
        row_lens = jnp.where(live & (row_lens == 0), 1, row_lens)

    ends = jnp.cumsum(row_lens)  # inclusive scan
    total = ends[-1] if n else jnp.int64(0)
    j = jnp.arange(C, dtype=jnp.int64)
    src_c, pos = expand_rows(ends, C)
    out_live = j < total

    out_cols: list[ColumnVal] = []
    for cv in cols:
        data = jnp.take(cv.data, src_c, axis=0)
        valid = None if cv.valid is None else jnp.take(cv.valid, src_c)
        out_cols.append(ColumnVal(data, valid, cv.dict, cv.type))
    for arr, lt, mat, ed, et, ln in zip(
        arrays, len_tables, elem_mats, elem_dicts, elem_types, arr_lens
    ):
        code = jnp.take(arr.data, src_c)
        in_len = pos < jnp.take(ln, src_c)
        pos_c = jnp.clip(pos, 0, mat.shape[1] - 1)
        data = mat[code, pos_c]
        valid = out_live & in_len
        if arr.valid is not None:
            valid = valid & jnp.take(arr.valid, src_c)
        out_cols.append(ColumnVal(data, valid, ed, et))
    if with_ordinality:
        from ..data.types import BIGINT

        # outer null-extension rows carry NULL ordinality (Trino semantics)
        ord_valid = None
        if outer:
            ord_valid = out_live & (pos < jnp.take(pre_outer_lens, src_c))
        out_cols.append(ColumnVal(pos + 1, ord_valid, None, BIGINT))
    return out_cols, out_live, total
