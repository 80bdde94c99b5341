"""Fused segmented-reduction Pallas kernel (TPU group-by accumulator).

The reference aggregates through FlatHash — a Swiss-table whose SWAR probe
touches 8 control bytes per key (operator/FlatHash.java:38,59) — then runs
per-function Accumulators over the grouped rows (operator/aggregation/).
Per-row hash probing is the wrong shape for a TPU: the VPU wants 8x128
lanes of straight-line math and the MXU wants matmuls.

This kernel is the TPU-native replacement for the *accumulation* phase:
given a segment id per row (from the dictionary-code fast path or the
sort-based grouping in ops/relops.py), it computes EVERY aggregate of the
GROUP BY in ONE pass over HBM:

- all SUM/COUNT/AVG columns ride the MXU as one-hot matmuls:
  partial[a, g] = sum_k vals[a, k] * (seg[k] == g).  With
  ``precision=HIGHEST`` the bf16x6 decomposition makes integer-valued f32
  products EXACT, so the same matmul path serves both float sums and the
  limb-decomposed exact-integer sums below.
- float (DOUBLE) sums use Kahan/Neumaier compensation across row-chunks:
  TwoSum residuals accumulate in a second f32 buffer, recovering ~2x f32
  mantissa — on TPU hardware (no native f64) this is *more* accurate than
  the jnp.float64 the XLA path pretends to have (it silently computes f32).
- BIGINT sums are bit-exact: the host decomposes each value into signed
  14-bit limbs (f32-exact products; 1024-row chunk partials stay < 2^24),
  the kernel accumulates limbs in int32 with a carry-propagation sweep
  every 32 chunks, and the host recombines limbs in int64.
- MIN/MAX reduce on the VPU against the same one-hot mask, fused into the
  same HBM pass.

Grid = row chunks of 1024 (8 sublanes x 128 lanes); group axis is tiled by
512 lanes so the one-hot stays ~2MB of VMEM; accumulators live in VMEM
scratch across the (sequential) TPU grid.  Practical ceiling is G ≈ 8192
groups — beyond that the n*G one-hot work dominates and the sort-based
path in ops/relops.py wins.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SegRed", "SortedRuns", "fused_segment_reduce", "pallas_segreduce_supported",
]

_CHUNK_S = 8  # sublanes per row-chunk
_CHUNK_L = 128  # lanes per row-chunk
_CHUNK = _CHUNK_S * _CHUNK_L  # 1024 rows per exactness unit (one dot)
# Row-chunks processed per GRID STEP (inner unrolled loop).  Each 1024-row
# dot keeps its f32-exact partial-sum envelope; batching 8 of them per step
# amortizes the per-step grid overhead that dominated wall time on small
# queries (a sequential 1170-step grid cost ~100us/step of pure dispatch).
_STEP_CHUNKS = 8
_STEP_ROWS = _CHUNK * _STEP_CHUNKS
_GTILE = 512  # group-axis tile (lanes)
_LIMB_BITS = 14  # 1024 rows * (2^14-1) < 2^24: chunk partials f32-exact
_CARRY_EVERY = 32  # 32 * 2^24 < 2^31: int32 accumulators never overflow
_CARRY_EVERY_STEPS = _CARRY_EVERY // _STEP_CHUNKS
_MAX_GROUPS = 8192  # beyond this the n*G one-hot work loses to sorting

_SUM_EXACT_MAX_F32 = float(1 << 24)  # ints this small sum exactly per chunk

# Test hook: force the Pallas path (in interpreter mode) even on CPU so the
# kernel itself — not just the XLA fallback — is exercised by the suite.
INTERPRET = False


@dataclass(frozen=True)
class SegRed:
    """One requested reduction over the segmented rows.

    op: 'sum' | 'min' | 'max' | 'count'  ('count' == sum of valid 0/1) |
        'last' (the value in a group's last row: SortedRuns only — how the
        sorted group-by reads its keys)
    values: [n] array (ignored for 'count' when valid is given)
    valid: optional [n] bool — rows where the argument is non-NULL and live.
    """

    op: str
    values: Optional[jnp.ndarray]
    valid: Optional[jnp.ndarray]


def pallas_segreduce_supported(num_segments: int, backend: Optional[str] = None) -> bool:
    if num_segments > _MAX_GROUPS:
        return False
    return (backend or jax.default_backend()) == "tpu"


# --------------------------------------------------------------------------
# kernel factory (cached per static config)
# --------------------------------------------------------------------------


_I32_MAX = np.int32(np.iinfo(np.int32).max)
_I32_MIN = np.int32(np.iinfo(np.int32).min)


@functools.lru_cache(maxsize=64)
def _make_kernel(
    n_chunks: int,
    af: int,  # kahan f32 sum columns
    ai: int,  # exact int32-accumulated columns
    amn: int,  # f32 min columns
    amx: int,  # f32 max columns
    imn: int,  # native-i32 min columns (exact: dates, dict ranks, INTEGER)
    imx: int,  # native-i32 max columns
    g_pad: int,
    carry_groups: tuple,  # ((start, n_limbs), ...) within the ai block
    interpret: bool,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_tiles = g_pad // _GTILE
    gt = _GTILE
    hi = jax.lax.Precision.HIGHEST

    # scratch rows must satisfy the (8, 128) tile constraint
    def pad8(k):
        return max(8, -(-k // 8) * 8)

    counts = (af, ai, amn, amx, imn, imx)

    def kernel(*refs):
        it = iter(refs)
        seg_ref = next(it)
        f_ref, i_ref, mn_ref, mx_ref, imn_ref, imx_ref = (
            next(it) if k else None for k in counts
        )
        of_ref, oi_ref, omn_ref, omx_ref, oimn_ref, oimx_ref = (
            next(it) if k else None for k in counts
        )
        facc = next(it) if af else None
        ferr = next(it) if af else None
        iacc = next(it) if ai else None
        mnacc = next(it) if amn else None
        mxacc = next(it) if amx else None
        imnacc = next(it) if imn else None
        imxacc = next(it) if imx else None

        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            if af:
                facc[:] = jnp.zeros_like(facc)
                ferr[:] = jnp.zeros_like(ferr)
            if ai:
                iacc[:] = jnp.zeros_like(iacc)
            if amn:
                mnacc[:] = jnp.full_like(mnacc, jnp.inf)
            if amx:
                mxacc[:] = jnp.full_like(mxacc, -jnp.inf)
            if imn:
                imnacc[:] = jnp.full_like(imnacc, _I32_MAX)
            if imx:
                imxacc[:] = jnp.full_like(imxacc, _I32_MIN)

        sg_all = seg_ref[:]  # [S * STEP_CHUNKS, L] int32
        fv_all = f_ref[:] if af else None  # [af, S * STEP_CHUNKS, L]
        iv_all = i_ref[:] if ai else None

        def mm_pass(ref, acc, k, mask, sl, rows, reduce, sentinel):
            v = ref[:]
            for a in range(k):
                big = jnp.where(mask, v[a][rows][:, :, None], sentinel)
                cur = reduce(big, axis=(0, 1)).reshape(1, gt)
                merge = jnp.minimum if reduce is jnp.min else jnp.maximum
                acc[a : a + 1, sl] = merge(acc[a : a + 1, sl], cur)

        for t in range(n_tiles):
            base = t * gt
            iota = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK_S, _CHUNK_L, gt), 2)
            sl = slice(base, base + gt)
            # each 1024-row sub-chunk keeps its own dot (f32-exact partial
            # sums); batching them in one grid step amortizes step overhead
            for sc in range(_STEP_CHUNKS):
                rows = slice(sc * _CHUNK_S, (sc + 1) * _CHUNK_S)
                sg = sg_all[rows]
                mask = sg[:, :, None] == (iota + base)
                oh = mask.astype(jnp.float32)

                if af:
                    fvt = jnp.transpose(fv_all[:, rows], (1, 0, 2))  # [S, af, L]
                    part = jax.lax.dot_general(
                        fvt, oh, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32, precision=hi,
                    )  # [S, af, gt]
                    p = jnp.sum(part, axis=0)
                    # Neumaier TwoSum: a + p == s + e exactly
                    a = facc[0:af, sl]
                    s = a + p
                    e = jnp.where(jnp.abs(a) >= jnp.abs(p), (a - s) + p, (p - s) + a)
                    facc[0:af, sl] = s
                    ferr[0:af, sl] += e

                if ai:
                    ivt = jnp.transpose(iv_all[:, rows], (1, 0, 2))
                    part = jax.lax.dot_general(
                        ivt, oh, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32, precision=hi,
                    )
                    iacc[0:ai, sl] += jnp.sum(part, axis=0).astype(jnp.int32)

                if amn:
                    mm_pass(mn_ref, mnacc, amn, mask, sl, rows, jnp.min, jnp.float32(jnp.inf))
                if amx:
                    mm_pass(mx_ref, mxacc, amx, mask, sl, rows, jnp.max, jnp.float32(-jnp.inf))
                if imn:
                    mm_pass(imn_ref, imnacc, imn, mask, sl, rows, jnp.min, _I32_MAX)
                if imx:
                    mm_pass(imx_ref, imxacc, imx, mask, sl, rows, jnp.max, _I32_MIN)

        if carry_groups:

            @pl.when((i & (_CARRY_EVERY_STEPS - 1)) == (_CARRY_EVERY_STEPS - 1))
            def _carry():
                for (start, nl) in carry_groups:
                    for l in range(nl - 1):
                        row = iacc[start + l : start + l + 1, :]
                        c = row >> _LIMB_BITS
                        iacc[start + l : start + l + 1, :] = row - (c << _LIMB_BITS)
                        iacc[start + l + 1 : start + l + 2, :] += c

        @pl.when(i == pl.num_programs(0) - 1)
        def _fin():
            if af:
                # acc and err are returned separately: adding them in f32
                # would re-round and discard the compensation — the host
                # combines them in f64.
                of_ref[0:af, :] = facc[0:af, :]
                of_ref[af : 2 * af, :] = ferr[0:af, :]
            if ai:
                oi_ref[:] = iacc[0:ai, :]
            if amn:
                omn_ref[:] = mnacc[0:amn, :]
            if amx:
                omx_ref[:] = mxacc[0:amx, :]
            if imn:
                oimn_ref[:] = imnacc[0:imn, :]
            if imx:
                oimx_ref[:] = imxacc[0:imx, :]

    vmem = pltpu.VMEM
    step_s = _CHUNK_S * _STEP_CHUNKS
    in_specs = [pl.BlockSpec((step_s, _CHUNK_L), lambda i: (i, 0), memory_space=vmem)]
    out_specs, out_shape, scratch = [], [], []
    for k in counts:
        if k:
            in_specs.append(
                pl.BlockSpec((k, step_s, _CHUNK_L), lambda i: (0, i, 0), memory_space=vmem)
            )
    out_cfg = (
        (2 * af, jnp.float32),
        (ai, jnp.int32),
        (amn, jnp.float32),
        (amx, jnp.float32),
        (imn, jnp.int32),
        (imx, jnp.int32),
    )
    for k, dt in out_cfg:
        if k:
            out_specs.append(pl.BlockSpec((k, g_pad), lambda i: (0, 0), memory_space=vmem))
            out_shape.append(jax.ShapeDtypeStruct((k, g_pad), dt))
    if af:
        scratch += [pltpu.VMEM((pad8(af), g_pad), jnp.float32)] * 2
    if ai:
        scratch.append(pltpu.VMEM((pad8(ai), g_pad), jnp.int32))
    for k, dt in ((amn, jnp.float32), (amx, jnp.float32), (imn, jnp.int32), (imx, jnp.int32)):
        if k:
            scratch.append(pltpu.VMEM((pad8(k), g_pad), dt))

    return pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
        interpret=interpret,
        name="seg_reduce",
    )


def _limbs_for(dtype) -> int:
    if dtype in (jnp.int64, np.int64):
        return 5  # 70 bits
    return 3  # int32/date: 42 bits


_MAX_PLANES = 64  # input planes per kernel pass (~64 KB of VMEM each)


def _planes_for(r: SegRed) -> int:
    """Input planes one reduction costs the kernel (an exact integer sum
    travels as 14-bit limbs)."""
    if r.op == "sum" and r.values is not None and (
        jnp.issubdtype(r.values.dtype, jnp.integer) or r.values.dtype == jnp.bool_
    ):
        return _limbs_for(r.values.dtype)
    return 1


def _prep_rows(arr: jnp.ndarray, n_pad: int, fill) -> jnp.ndarray:
    out = jnp.pad(arr, (0, n_pad - arr.shape[0]), constant_values=fill)
    return out.reshape(n_pad // _CHUNK_L, _CHUNK_L)


def fused_segment_reduce(
    seg: jnp.ndarray,
    reds: Sequence[SegRed],
    num_segments: int,
    *,
    interpret: bool = False,
    force_pallas: bool = False,
    runs: Optional["SortedRuns"] = None,
) -> list[jnp.ndarray]:
    """Compute every requested reduction in one fused pass.

    seg: [n] int32 segment ids in [0, num_segments); rows with seg >=
    num_segments (the caller's dead-lane convention) fall into padding
    groups and are sliced off.  `runs`: the rows are sorted by segment and
    these are the groups' boundaries (the sort-based group-by) — beyond the
    kernel's group ceiling the reductions are then read at the group ends
    (SortedRuns.read) instead of scattered.

    Returns one array [num_segments] per red:
      sum of floats  -> float64 (Kahan-compensated on the Pallas path)
      sum of ints    -> int64, bit-exact
      count          -> int64
      min/max        -> the input dtype
    Empty groups yield 0 for sum/count and +inf/-inf (or dtype extrema)
    for min/max; the caller masks them with its count column.
    """
    n = seg.shape[0]
    G = num_segments
    interpret = interpret or INTERPRET
    use_pallas = force_pallas or interpret or pallas_segreduce_supported(G)
    if not use_pallas:
        if runs is not None:
            # high-cardinality group-by over the sort-based path (XLA scatter
            # serializes on TPU — at TPC-H SF1 Q3's ~1M groups the scatter
            # fallback cost ~36s of device time)
            return runs.read(reds, G)
        return _xla_fallback(seg, reds, G)

    kw = dict(interpret=interpret, force_pallas=force_pallas)
    if runs is not None and any(r.op == "last" for r in reds):
        # the kernel reduces; what is only read is read where the groups end
        out: list = [None] * len(reds)
        read = [i for i, r in enumerate(reds) if r.op == "last"]
        rest = [i for i, r in enumerate(reds) if r.op != "last"]
        for i, o in zip(read, runs.read([reds[i] for i in read], G)):
            out[i] = o
        if rest:
            for i, o in zip(
                rest, fused_segment_reduce(seg, [reds[i] for i in rest], G, **kw)
            ):
                out[i] = o
        return out

    if len(reds) > 1 and sum(_planes_for(r) for r in reds) > _MAX_PLANES:
        # every input plane is a double-buffered (64, 128) VMEM block per
        # grid step: q01's 34 reductions over a sharded scan decompose into
        # 111 int limb planes, 16.1 MB of scoped VMEM against the chip's
        # 16 MB limit.  Split into passes that fit; each reads `seg` again.
        mid = len(reds) // 2
        return (
            fused_segment_reduce(seg, reds[:mid], G, **kw)
            + fused_segment_reduce(seg, reds[mid:], G, **kw)
        )

    from ..kernels import record_dispatch

    record_dispatch("segment_reduce", "pallas", f"{len(reds)} reds G {G}")
    g_pad = max(_GTILE, -(-(G + 1) // _GTILE) * _GTILE)
    n_pad = -(-n // _STEP_ROWS) * _STEP_ROWS
    n_chunks = n_pad // _STEP_ROWS  # grid steps (each = _STEP_CHUNKS dots)

    seg_c = jnp.clip(seg.astype(jnp.int32), 0, g_pad - 1)
    seg_c = jnp.where(seg.astype(jnp.int32) >= G, g_pad - 1, seg_c)
    seg2 = _prep_rows(seg_c, n_pad, g_pad - 1)

    f_cols: list[jnp.ndarray] = []  # kahan f32 sum columns
    i_cols: list[jnp.ndarray] = []  # exact i32-accumulated columns
    mn_cols: list[jnp.ndarray] = []  # f32 min
    mx_cols: list[jnp.ndarray] = []  # f32 max
    imn_cols: list[jnp.ndarray] = []  # exact i32 min
    imx_cols: list[jnp.ndarray] = []  # exact i32 max
    carry_groups: list[tuple[int, int]] = []
    plan: list[tuple] = []  # (kind, payload) per red, to unpack outputs
    xla_reds: list[tuple[int, SegRed]] = []  # kernel-ineligible (int64 min/max)

    def _i32_ok(dtype) -> bool:
        return dtype in (jnp.int32, np.dtype(np.int32), jnp.int16, jnp.int8,
                         np.dtype(np.int16), np.dtype(np.int8), jnp.bool_,
                         np.dtype(np.bool_))

    for ri, r in enumerate(reds):
        if r.op == "count":
            v = (
                r.valid.astype(jnp.float32)
                if r.valid is not None
                else jnp.ones((n,), jnp.float32)
            )
            plan.append(("int", len(i_cols), 1, jnp.int64))
            i_cols.append(v)
        elif r.op == "sum":
            vals = r.values
            valid = r.valid
            if jnp.issubdtype(vals.dtype, jnp.integer) or vals.dtype == jnp.bool_:
                nl = _limbs_for(vals.dtype)
                v64 = vals.astype(jnp.int64)
                if valid is not None:
                    v64 = jnp.where(valid, v64, 0)
                sign = jnp.where(v64 < 0, jnp.int64(-1), jnp.int64(1))
                mag = jnp.abs(v64)
                start = len(i_cols)
                for l in range(nl):
                    limb = ((mag >> (_LIMB_BITS * l)) & ((1 << _LIMB_BITS) - 1)) * sign
                    i_cols.append(limb.astype(jnp.float32))
                if nl > 1:
                    carry_groups.append((start, nl))
                plan.append(("limbs", start, nl, jnp.int64))
            else:
                v = vals.astype(jnp.float32)
                if valid is not None:
                    v = jnp.where(valid, v, jnp.float32(0))
                plan.append(("float", len(f_cols), 1, jnp.float64))
                f_cols.append(v)
        elif r.op in ("min", "max"):
            vals = r.values
            valid = r.valid
            if jnp.issubdtype(vals.dtype, jnp.floating):
                v = vals.astype(jnp.float32)
                sent = jnp.float32(jnp.inf if r.op == "min" else -jnp.inf)
                if valid is not None:
                    v = jnp.where(valid, v, sent)
                if r.op == "min":
                    plan.append(("min", len(mn_cols), 1, vals.dtype))
                    mn_cols.append(v)
                else:
                    plan.append(("max", len(mx_cols), 1, vals.dtype))
                    mx_cols.append(v)
            elif _i32_ok(vals.dtype):
                v = vals.astype(jnp.int32)
                sent = _I32_MAX if r.op == "min" else _I32_MIN
                if valid is not None:
                    v = jnp.where(valid, v, sent)
                if r.op == "min":
                    plan.append(("imin", len(imn_cols), 1, vals.dtype))
                    imn_cols.append(v)
                else:
                    plan.append(("imax", len(imx_cols), 1, vals.dtype))
                    imx_cols.append(v)
            else:
                # int64 min/max: no native 64-bit lanes in the kernel and an
                # f32 round-trip would corrupt values above 2^24 — use the
                # exact XLA path for just this reduction.
                plan.append(("xla", len(xla_reds), 1, vals.dtype))
                xla_reds.append((ri, r))
        else:
            raise ValueError(f"unknown reduction {r.op}")

    counts = (
        len(f_cols), len(i_cols), len(mn_cols), len(mx_cols),
        len(imn_cols), len(imx_cols),
    )
    af, ai, amn, amx, imn, imx = counts

    def stack(cols, fill):
        return jnp.stack([_prep_rows(c, n_pad, fill) for c in cols])

    args = [seg2]
    for cols, fill in (
        (f_cols, 0.0), (i_cols, 0.0), (mn_cols, np.float32(np.inf)),
        (mx_cols, np.float32(-np.inf)), (imn_cols, _I32_MAX), (imx_cols, _I32_MIN),
    ):
        if cols:
            args.append(stack(cols, fill))

    results: tuple = ()
    if any(counts):
        call = _make_kernel(
            n_chunks, af, ai, amn, amx, imn, imx, g_pad, tuple(carry_groups), interpret
        )
        # Mosaic requires i32 grid indices; under the engine's global x64 mode
        # the BlockSpec index maps trace to i64 and fail to legalize.  All
        # kernel operands/outputs are f32/i32, so scoped-disabling x64 is sound.
        with jax.enable_x64(False):
            results = call(*args)
        if not isinstance(results, (tuple, list)):
            results = (results,)
    it = iter(results)
    of = next(it) if af else None
    oi = next(it) if ai else None
    omn = next(it) if amn else None
    omx = next(it) if amx else None
    oimn = next(it) if imn else None
    oimx = next(it) if imx else None
    xla_out = _xla_fallback(seg, [r for _, r in xla_reds], G) if xla_reds else []

    out: list[jnp.ndarray] = []
    for kind, idx, width, dtype in plan:
        if kind == "float":
            out.append(
                of[idx, :G].astype(jnp.float64) + of[af + idx, :G].astype(jnp.float64)
            )
        elif kind == "int":
            out.append(oi[idx, :G].astype(jnp.int64))
        elif kind == "limbs":
            total = jnp.zeros((G,), jnp.int64)
            for l in range(width):
                total = total + (
                    oi[idx + l, :G].astype(jnp.int64) << (_LIMB_BITS * l)
                )
            out.append(total)
        elif kind == "min":
            out.append(omn[idx, :G].astype(dtype))
        elif kind == "max":
            out.append(omx[idx, :G].astype(dtype))
        elif kind == "imin":
            out.append(oimn[idx, :G].astype(dtype))
        elif kind == "imax":
            out.append(oimx[idx, :G].astype(dtype))
        else:  # xla
            out.append(xla_out[idx])
    return out


# --------------------------------------------------------------------------
# Beyond the one-hot ceiling: sorted runs read at their ends, or XLA's
# segment ops (CPU tests)
# --------------------------------------------------------------------------


def _seg_scan_extreme(vals, flag, is_min):
    """Per-row running min/max within each contiguous segment (flag marks
    segment starts).  The segmented-combine operator is associative, so the
    whole pass is one log-depth associative_scan — no scatter."""

    def op(a, b):
        va, fa = a
        vb, fb = b
        combined = jnp.minimum(va, vb) if is_min else jnp.maximum(va, vb)
        return jnp.where(fb, vb, combined), fa | fb

    pv, _ = jax.lax.associative_scan(op, (vals, flag))
    return pv


class SortedRuns:
    """Where the groups of a SORTED page stand: every group is one run of
    adjacent rows, the runs are packed from lane 0 and dead lanes come last
    (the sort-based group-by's output order).  `start` / `end` flag a group's
    first and last row, `live` the rows that belong to a group at all;
    `n_groups` is the true group count.  The boundaries are these flags — no
    searchsorted asks for them again.  `ends_words` tallies the 32-bit words
    `read` has carried to the front of a frame (the dispatch event's
    detail)."""

    def __init__(self, start: jnp.ndarray, live: jnp.ndarray):
        n = live.shape[0]
        self.start = start
        self.live = live
        self.end = live & jnp.concatenate(
            [start[1:] | ~live[1:], jnp.ones((min(n, 1),), jnp.bool_)]
        )
        self.n_groups = jnp.sum(start.astype(jnp.int32))
        self.ends_words = 0

    def read(self, reds: Sequence[SegRed], G: int) -> list[jnp.ndarray]:
        """The one sorted-segment reducer: every reduction is a running value
        over the sorted rows (an inclusive cumsum, exact in int64; a segmented
        running min/max; a row's own value for 'last') READ WHERE EACH GROUP
        ENDS, and ONE compaction — a sort on the end lanes' positions with the
        running values as operands that are not keys — brings the G first
        ends to the front of the frame.  Sums are then differences between
        neighbours in the G-lane frame, a group's size the difference of its
        end positions.  Measured on the v5e (PERF.md section 6, PR 41): the
        running values ride that sort several times cheaper than a gather of
        each through a permutation, and the two searchsorted passes this
        replaces cost two sorts and two scatters of n + G lanes each."""
        if not reds:
            return []
        n = self.end.shape[0]
        words: list[jnp.ndarray] = []
        plan: list[tuple] = []

        def word(arr) -> int:
            words.append(arr)
            return len(words) - 1

        for r in reds:
            if r.op == "last":
                plan.append(("at", word(r.values), None))
            elif r.op == "count":
                if r.valid is None or r.valid is self.live:
                    plan.append(("size",))
                else:  # n < 2^31 rows: an int32 running count is exact
                    c = jnp.cumsum(r.valid.astype(jnp.int32), dtype=jnp.int32)
                    plan.append(("diff", word(c), jnp.int64))
            elif r.op == "sum":
                vals = r.values
                if jnp.issubdtype(vals.dtype, jnp.integer) or vals.dtype == jnp.bool_:
                    acc = vals.astype(jnp.int64)
                else:
                    acc = vals.astype(jnp.float64)
                if r.valid is not None:
                    acc = jnp.where(r.valid, acc, jnp.zeros_like(acc))
                plan.append(("diff", word(jnp.cumsum(acc)), acc.dtype))
            elif r.op in ("min", "max"):
                sel = r.values
                if jnp.issubdtype(sel.dtype, jnp.floating):
                    sent = jnp.asarray(jnp.inf if r.op == "min" else -jnp.inf, sel.dtype)
                else:
                    info = jnp.iinfo(sel.dtype)
                    sent = jnp.asarray(info.max if r.op == "min" else info.min, sel.dtype)
                if r.valid is not None:
                    sel = jnp.where(r.valid, sel, sent)
                run = _seg_scan_extreme(sel, self.start, r.op == "min")
                plan.append(("at", word(run), sent))
            else:
                raise NotImplementedError(r.op)

        self.ends_words += sum(max(1, w.dtype.itemsize // 4) for w in words)
        # end lanes sort first, by position: the keys among them are unique,
        # so the sort needs no stability and no iota beside it
        pos = jnp.arange(n, dtype=jnp.int32)
        front = jax.lax.sort(
            [jnp.where(self.end, pos, jnp.int32(n))] + words,
            num_keys=1, is_stable=False,
        )
        front = [
            f[:G] if n >= G else jnp.pad(f, (0, G - n)) for f in front
        ]
        in_frame = jnp.arange(G, dtype=jnp.int32) < jnp.minimum(self.n_groups, G)

        def since_prev(c, first):
            prev = jnp.concatenate([jnp.full((1,), first, c.dtype), c[:-1]])
            return jnp.where(in_frame, c - prev, jnp.zeros((), c.dtype))

        out = []
        for p in plan:
            if p[0] == "size":
                out.append(since_prev(front[0], -1).astype(jnp.int64))
            elif p[0] == "diff":
                out.append(since_prev(front[1 + p[1]], 0).astype(p[2]))
            elif p[2] is None:  # a row's own value: dead groups keep garbage
                out.append(front[1 + p[1]])
            else:
                out.append(jnp.where(in_frame, front[1 + p[1]], p[2]))
        return out


def _xla_fallback(seg, reds, G):
    n = seg.shape[0]
    num = G + 1  # overflow bucket for dead lanes
    seg_c = jnp.minimum(seg.astype(jnp.int32), G)
    out = []
    for r in reds:
        if r.op == "count":
            v = (
                r.valid.astype(jnp.int64)
                if r.valid is not None
                else jnp.ones((n,), jnp.int64)
            )
            out.append(jax.ops.segment_sum(v, seg_c, num_segments=num)[:G])
        elif r.op == "sum":
            vals = r.values
            if jnp.issubdtype(vals.dtype, jnp.integer) or vals.dtype == jnp.bool_:
                acc = vals.astype(jnp.int64)
            else:
                acc = vals.astype(jnp.float64)
            if r.valid is not None:
                acc = jnp.where(r.valid, acc, jnp.zeros_like(acc))
            out.append(jax.ops.segment_sum(acc, seg_c, num_segments=num)[:G])
        elif r.op == "min":
            sel = r.values
            if jnp.issubdtype(sel.dtype, jnp.floating):
                sent = jnp.asarray(jnp.inf, sel.dtype)
            else:
                sent = jnp.iinfo(sel.dtype).max
            if r.valid is not None:
                sel = jnp.where(r.valid, sel, sent)
            out.append(jax.ops.segment_min(sel, seg_c, num_segments=num)[:G])
        elif r.op == "max":
            sel = r.values
            if jnp.issubdtype(sel.dtype, jnp.floating):
                sent = jnp.asarray(-jnp.inf, sel.dtype)
            else:
                sent = jnp.iinfo(sel.dtype).min
            if r.valid is not None:
                sel = jnp.where(r.valid, sel, sent)
            out.append(jax.ops.segment_max(sel, seg_c, num_segments=num)[:G])
        else:
            raise ValueError(r.op)
    return out
