"""Pallas linear-probing hash table over VMEM — the group-by/join build pass.

Replaces the full-array sort at the head of the sort-based group-by and
equi-join (ops/relops.py) with ONE streaming pass over HBM: every row probes
a VMEM-resident open-addressing table keyed on its encoded key words and
either matches an existing entry (getting that entry's dense group id) or
claims an empty slot (allocating the next id).  The table never leaves VMEM
until the final grid step, so the pass is bandwidth-bound on the row stream
— the reference engine's BigintGroupByHash / hash-build idea mapped onto the
TPU's memory hierarchy.

Layout and idioms follow ops/pallas/segreduce.py: rows stream in 8192-row
grid steps of eight (8, 128) sub-chunks; all table reads and writes are
one-hot matmuls on the MXU (TPU vector memory has no scattered addressing —
a one-hot dot IS the gather/scatter); f32 is made exact by splitting every
32-bit key word into two 16-bit halves (integers < 2^24 are exact in f32).

Table: [16, T] f32 in VMEM scratch, T a multiple of 512 (tiled so each
one-hot stays ~2 MB).  Channels: 0 = used flag, 1 = group id, 2.. = the
lo16/hi16 halves of each key word.  Collision handling is textbook linear
probing with a bounded probe budget: a sub-chunk's rows retry a
claimed-but-lost slot before advancing (two equal new keys in one sub-chunk
must converge on one entry), and any row that exhausts the budget — or a
table that runs past its group capacity — raises the kernel's overflow flag,
which the caller turns into its deterministic overflow-to-sort fallback.

Exactness: key words round-trip the f32 table exactly (16-bit halves), row
positions and group ids stay below 2^24, and every matmul runs at HIGHEST
precision — matches and ids are exact, never probabilistic.  A 64-bit mixed
hash picks only the START slot; equality is decided on the full key words.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# test hook: force interpret-mode execution on CPU (tests/test_pallas_relops)
INTERPRET = False

_CHUNK_S = 8
_CHUNK_L = 128
_SUB_ROWS = _CHUNK_S * _CHUNK_L  # 1024 rows per probing sub-chunk
_STEP_CHUNKS = 8
_STEP_ROWS = _SUB_ROWS * _STEP_CHUNKS  # 8192 rows per grid step
_TTILE = 512  # table lanes per one-hot tile (~2MB of VMEM per intermediate)
_PROBE_LIMIT = 64  # probe-round budget before the overflow flag trips; the
# round loop is a while_loop that exits as soon as every row in the
# sub-chunk resolved, so typical cost is 1-3 rounds

MAX_WORDS = 6  # i32 key words per row the 16-channel table can hold
_CHANNELS = 16  # used, gid, up to 2*MAX_WORDS halves, padding

_MAX_ROWS_EXACT = 1 << 24  # row positions must stay exact in f32


def _pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def table_size(cap: int) -> int:
    """Slots for `cap` distinct keys: load factor <= 0.5, tile-aligned."""
    return max(2 * _pow2(max(cap, 1)), _TTILE)


_MAX_TILES = 16  # table slots cap (8192): VMEM + compile size stay sane


def shape_supported(n: int, n_words: int, cap: int) -> bool:
    """Limits independent of backend — also enforced under interpret mode."""
    if n_words < 1 or n_words > MAX_WORDS or n >= _MAX_ROWS_EXACT:
        return False
    return table_size(cap) <= _MAX_TILES * _TTILE


def hash_table_supported(n: int, n_words: int, cap: int, backend=None) -> bool:
    backend = backend or jax.default_backend()
    return shape_supported(n, n_words, cap) and backend == "tpu"


def hash_words(words, live) -> jnp.ndarray:
    """Combine encoded key words into a 64-bit start-slot hash (the
    _combined_hash splitmix chain from ops/relops.py over i32 words)."""
    from ..relops import _mix64

    h = jnp.zeros(live.shape, dtype=jnp.uint64)
    for w in words:
        h = _mix64(h ^ _mix64(w.astype(jnp.uint32).astype(jnp.uint64)))
    return h


def _halves_f32(w: jnp.ndarray):
    wu = w.astype(jnp.int32).astype(jnp.uint32)
    return (
        (wu & jnp.uint32(0xFFFF)).astype(jnp.float32),
        (wu >> jnp.uint32(16)).astype(jnp.float32),
    )


def _prep(arr: jnp.ndarray, n_pad: int, fill) -> jnp.ndarray:
    # the fill must carry the array's exact dtype: a weak python scalar
    # picks up the ambient x64 default, which differs between this
    # function's jax.enable_x64(False) scope and an enclosing fragment trace
    out = jnp.pad(
        arr, (0, n_pad - arr.shape[0]),
        constant_values=jnp.asarray(fill, arr.dtype),
    )
    return out.reshape(n_pad // _CHUNK_L, _CHUNK_L)


def _any_f32(mask: jnp.ndarray):
    """`jnp.any` of a bool vector as an explicit f32 max.  Mosaic lowers a
    bool reduction through a python-float `where`, and the kernel is lowered
    when the enclosing fragment jit compiles — with x64 back on — so that
    proxy would be f64, which the TPU compiler refuses to make a scalar."""
    return jnp.max(jnp.where(mask, jnp.float32(1.0), jnp.float32(0.0))) > 0.5


def _sub_prefix(wf: jnp.ndarray):
    """Row-major exclusive prefix count of a (8, 128) 0/1 mask + its total:
    lanes via a strict-lower-triangular matmul (exact f32 — counts < 2^24),
    sublanes via a statically unrolled running sum."""
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (_CHUNK_L, _CHUNK_L), 0)
        < jax.lax.broadcasted_iota(jnp.int32, (_CHUNK_L, _CHUNK_L), 1)
    ).astype(jnp.float32)
    pre_lane = jax.lax.dot_general(
        wf, tri, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    rows = []
    run = jnp.float32(0.0)
    for s in range(_CHUNK_S):
        rows.append(jnp.broadcast_to(run.reshape(1, 1), (1, _CHUNK_L)))
        run = run + jnp.sum(wf[s : s + 1, :])
    pre_sub = jnp.concatenate(rows, axis=0)
    return pre_lane + pre_sub, run


def _gather_channels(tbl, cur, active, T):
    """One-hot MXU gather: per active row the 16 table channels at slot
    `cur`.  Returns (channels (8,128,16) f32, a per-tile one-hot rebuilder
    used by callers that scatter)."""
    from jax.experimental import pallas as pl  # lazy: a ~1 s import

    # the row mask rides in the slot number (-1 matches no lane): Mosaic
    # cannot reshape an i1 (8, 128) vector to (8, 128, 1) for a 3-D `&`
    cur = jnp.where(active, cur, -1)
    iota = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK_S, _CHUNK_L, _TTILE), 2)

    def _tile(q, g):
        base = pl.multiple_of(q * _TTILE, _TTILE)
        oh = ((cur - base)[:, :, None] == iota).astype(jnp.float32)
        tile = jnp.broadcast_to(
            tbl[:, pl.ds(base, _TTILE)][None], (_CHUNK_S, _CHANNELS, _TTILE)
        )
        return g + jax.lax.dot_general(
            oh, tile, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # (8, 128, 16)

    return jax.lax.fori_loop(
        0, T // _TTILE, _tile,
        jnp.zeros((_CHUNK_S, _CHUNK_L, _CHANNELS), jnp.float32),
    )


@functools.lru_cache(maxsize=64)
def _build_kernel(n_words: int, T: int, n_chunks: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_half = 2 * n_words

    def kernel(slot_ref, live_ref, planes_ref, gid_ref, table_ref, stats_ref,
               tbl, ngid, over):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            tbl[...] = jnp.zeros((_CHANNELS, T), jnp.float32)
            ngid[0] = jnp.int32(0)
            over[0] = jnp.int32(0)

        posf = (
            jax.lax.broadcasted_iota(jnp.int32, (_CHUNK_S, _CHUNK_L), 0)
            * _CHUNK_L
            + jax.lax.broadcasted_iota(jnp.int32, (_CHUNK_S, _CHUNK_L), 1)
        ).astype(jnp.float32)

        iota3 = jax.lax.broadcasted_iota(
            jnp.int32, (_CHUNK_S, _CHUNK_L, _TTILE), 2
        )
        n_tiles = T // _TTILE

        # sub-chunks and table tiles are fori_loops, not python loops: the
        # unrolled kernel was 0.8M MLIR ops and took minutes to compile
        def _sub_chunk(c, _):
            rows = pl.ds(pl.multiple_of(c * _CHUNK_S, _CHUNK_S), _CHUNK_S)
            sl = slot_ref[rows, :]
            lv = live_ref[rows, :] > 0
            vals = [planes_ref[w, rows, :] for w in range(n_half)]

            off0 = jnp.zeros(sl.shape, jnp.int32)
            gid0 = jnp.full(sl.shape, -1, jnp.int32)

            # `resolved` is carried as 0/1 int32: Mosaic cannot legalize an
            # i1 vector in a while_loop carry
            def _round(carry):
                r, off, done, gid = carry
                resolved = done > 0
                cur = sl + off
                cur = jnp.where(cur >= T, cur - T, cur)
                active = ~resolved
                g = _gather_channels(tbl, cur, active, T)
                used = g[..., 0] > 0.5
                eq = used
                for w in range(n_half):
                    eq = eq & (g[..., 2 + w] == vals[w])
                match = active & eq
                gid = jnp.where(match, g[..., 1].astype(jnp.int32), gid)
                resolved = resolved | match

                # claim empty slots: one winner per slot (min row position,
                # exact in f32), losers retry the same slot next round so
                # equal new keys in one sub-chunk converge on one entry
                cand = active & ~used
                cur_cand = jnp.where(cand, cur, -1)

                def _claim(q, winpos):
                    ohb = (cur_cand - q * _TTILE)[:, :, None] == iota3
                    masked = jnp.where(
                        ohb, posf[:, :, None], jnp.float32(2 * _SUB_ROWS)
                    )
                    m = jnp.min(jnp.min(masked, axis=1), axis=0, keepdims=True)
                    m8 = jnp.broadcast_to(m[None], (_CHUNK_S, 1, _TTILE))
                    return winpos + jax.lax.dot_general(
                        ohb.astype(jnp.float32), m8,
                        (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                    )[..., 0]

                winpos = jax.lax.fori_loop(
                    0, n_tiles, _claim, jnp.zeros(sl.shape, jnp.float32)
                )
                winner = cand & (winpos == posf)

                wf = winner.astype(jnp.float32)
                rank, n_new = _sub_prefix(wf)
                base = ngid[0]
                newgid = base + rank.astype(jnp.int32)
                gid = jnp.where(winner, newgid, gid)
                resolved = resolved | winner
                ngid[0] = base + n_new.astype(jnp.int32)

                # scatter winners into their claimed slots (one per slot)
                cur_win = jnp.where(winner, cur, -1)
                upd = jnp.stack(
                    [wf, newgid.astype(jnp.float32) * wf]
                    + [v * wf for v in vals]
                    + [jnp.zeros(sl.shape, jnp.float32)]
                    * (_CHANNELS - 2 - n_half),
                    axis=1,
                )  # (8, 16, 128)

                def _scatter(q, _):
                    tbase = pl.multiple_of(q * _TTILE, _TTILE)
                    ohw = (
                        (cur_win - tbase)[:, :, None] == iota3
                    ).astype(jnp.float32)
                    delta = jax.lax.dot_general(
                        upd, ohw, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                    )  # (8, 16, 512)
                    ts = pl.ds(tbase, _TTILE)
                    tbl[:, ts] = tbl[:, ts] + jnp.sum(delta, axis=0)
                    return 0

                jax.lax.fori_loop(0, n_tiles, _scatter, 0)

                off = off + (active & used & ~eq).astype(jnp.int32)
                return r + 1, off, resolved.astype(jnp.int32), gid

            def _unresolved(carry):
                r, _off, done, _gid = carry
                return (r < _PROBE_LIMIT) & _any_f32(done == 0)

            _, _, done, gid = jax.lax.while_loop(
                _unresolved, _round,
                (jnp.int32(0), off0, (~lv).astype(jnp.int32), gid0),
            )

            over[0] = jnp.maximum(
                over[0], _any_f32(done == 0).astype(jnp.int32)
            )
            gid_ref[rows, :] = gid
            return 0

        jax.lax.fori_loop(0, _STEP_CHUNKS, _sub_chunk, 0)

        @pl.when(i == n_chunks - 1)
        def _flush():
            table_ref[...] = tbl[...]
            r0 = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK_S, _CHUNK_L), 0)
            c0 = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK_S, _CHUNK_L), 1)
            zero = jnp.int32(0)  # bare 0 is weak-typed: it picks up the
            # ambient x64 default, which may be on in an enclosing trace
            stats_ref[...] = jnp.where(
                (r0 == 0) & (c0 == 0), ngid[0], zero
            ) + jnp.where((r0 == 0) & (c0 == 1), over[0], zero)

    vmem = pltpu.VMEM
    step_s = _STEP_ROWS // _CHUNK_L
    return pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((step_s, _CHUNK_L), lambda i: (i, 0), memory_space=vmem),
            pl.BlockSpec((step_s, _CHUNK_L), lambda i: (i, 0), memory_space=vmem),
            pl.BlockSpec(
                (2 * n_words, step_s, _CHUNK_L),
                lambda i: (0, i, 0),
                memory_space=vmem,
            ),
        ],
        out_specs=(
            pl.BlockSpec((step_s, _CHUNK_L), lambda i: (i, 0), memory_space=vmem),
            pl.BlockSpec((_CHANNELS, T), lambda i: (0, 0), memory_space=vmem),
            pl.BlockSpec((_CHUNK_S, _CHUNK_L), lambda i: (0, 0), memory_space=vmem),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_chunks * step_s, _CHUNK_L), jnp.int32),
            jax.ShapeDtypeStruct((_CHANNELS, T), jnp.float32),
            jax.ShapeDtypeStruct((_CHUNK_S, _CHUNK_L), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((_CHANNELS, T), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
        name="hash_agg",
    )


def build_hash_table(words, live, cap: int, *, interpret: bool = False):
    """Insert every live row's key into a fresh table.

    words: up to MAX_WORDS i32 arrays [n] encoding the key columns.
    Returns (gid [n] int32 — dense group id in claim order, -1 for dead or
    unresolved rows; table [16, T] f32 for a subsequent probe pass;
    n_groups int32; overflow bool — probe budget exhausted or more than
    `cap` distinct keys, i.e. the caller must take its sort fallback).
    """
    interpret = bool(interpret or INTERPRET)
    n = live.shape[0]
    T = table_size(cap)
    h = hash_words(words, live)
    slot0 = (h % jnp.uint64(T)).astype(jnp.int32)

    n_pad = -(-max(n, 1) // _STEP_ROWS) * _STEP_ROWS
    n_chunks = n_pad // _STEP_ROWS
    planes = []
    for w in words:
        lo, hi = _halves_f32(w)
        planes.append(_prep(lo, n_pad, 0.0))
        planes.append(_prep(hi, n_pad, 0.0))
    call = _build_kernel(len(words), T, n_chunks, interpret)
    with jax.enable_x64(False):
        gid_b, table, stats = call(
            _prep(slot0, n_pad, 0),
            _prep(live.astype(jnp.int32), n_pad, 0),
            jnp.stack(planes),
        )
    gid = gid_b.reshape(-1)[:n]
    n_groups = stats[0, 0]
    overflow = (stats[0, 1] > 0) | (n_groups > cap)
    return gid, table, n_groups, overflow
