"""Pallas hash-join probe pass.

The build side of a hash equi-join reuses the group-by build kernel
(ops/pallas/hashagg.build_hash_table): build-side rows insert their encoded
key words into the VMEM table and get dense ids 0..n_build_groups-1 per
DISTINCT build key.  This module is the probe side: every probe row walks
the same linear-probing sequence over the (now read-only) table and either
matches an entry — returning that entry's dense id — or hits an empty slot,
which proves the key is absent (miss, id -1).  One streaming HBM pass over
the probe side, no sort of either side.

The caller (ops/relops.py equi_join) turns the dense id into the legacy
(lo, hi) row-range form by small per-group offset arrays over the build
side, so the existing match-expansion/semi/anti/outer tail is shared
verbatim between the hash and sort paths.

Probe rows that exhaust the probe budget set an `unresolved` flag; together
with the build kernel's overflow flag it diverts the whole join to the sort
path at runtime (the results of an unresolved probe are unusable).  With
the table's <= 0.5 load factor a probe walk is bounded by the longest build
cluster + 1, so the flag only trips when the build pass itself was
borderline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .hashagg import (
    _CHANNELS,
    _CHUNK_L,
    _CHUNK_S,
    _PROBE_LIMIT,
    _STEP_CHUNKS,
    _STEP_ROWS,
    _any_f32,
    _gather_channels,
    _prep,
    hash_words,
)
from . import hashagg as _hashagg


@functools.lru_cache(maxsize=64)
def _probe_kernel(n_words: int, T: int, n_chunks: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_half = 2 * n_words

    def kernel(slot_ref, live_ref, planes_ref, table_ref, gid_ref, stats_ref,
               over):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            over[0] = jnp.int32(0)

        def _sub_chunk(c, _):
            rows = pl.ds(pl.multiple_of(c * _CHUNK_S, _CHUNK_S), _CHUNK_S)
            sl = slot_ref[rows, :]
            lv = live_ref[rows, :] > 0
            vals = [planes_ref[w, rows, :] for w in range(n_half)]

            off0 = jnp.zeros(sl.shape, jnp.int32)
            gid0 = jnp.full(sl.shape, -1, jnp.int32)

            def _round(carry):
                r, off, done, gid = carry  # done: 0/1 int32, see hashagg
                resolved = done > 0
                cur = sl + off
                cur = jnp.where(cur >= T, cur - T, cur)
                active = ~resolved
                g = _gather_channels(table_ref, cur, active, T)
                used = g[..., 0] > 0.5
                eq = used
                for w in range(n_half):
                    eq = eq & (g[..., 2 + w] == vals[w])
                match = active & eq
                gid = jnp.where(match, g[..., 1].astype(jnp.int32), gid)
                # an empty slot on the probe walk proves the key is absent
                resolved = resolved | match | (active & ~used)
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
            r0 = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK_S, _CHUNK_L), 0)
            c0 = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK_S, _CHUNK_L), 1)
            # jnp.int32: a weak 0 would pick up an enclosing trace's x64
            stats_ref[...] = jnp.where(
                (r0 == 0) & (c0 == 0), over[0], jnp.int32(0)
            )

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
            pl.BlockSpec((_CHANNELS, T), lambda i: (0, 0), memory_space=vmem),
        ],
        out_specs=(
            pl.BlockSpec((step_s, _CHUNK_L), lambda i: (i, 0), memory_space=vmem),
            pl.BlockSpec((_CHUNK_S, _CHUNK_L), lambda i: (0, 0), memory_space=vmem),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_chunks * step_s, _CHUNK_L), jnp.int32),
            jax.ShapeDtypeStruct((_CHUNK_S, _CHUNK_L), jnp.int32),
        ),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
        name="hash_join_probe",
    )


def probe_hash_table(words, live, table, *, interpret: bool = False):
    """Look up every live row's key in `table` (from build_hash_table).

    Returns (gid [n] int32 — the matched entry's dense id, -1 for a proven
    miss or a dead row; unresolved bool — some row exhausted the probe
    budget, results must not be used).
    """
    interpret = bool(interpret or _hashagg.INTERPRET)
    n = live.shape[0]
    T = table.shape[1]
    h = hash_words(words, live)
    slot0 = (h % jnp.uint64(T)).astype(jnp.int32)

    n_pad = -(-max(n, 1) // _STEP_ROWS) * _STEP_ROWS
    n_chunks = n_pad // _STEP_ROWS
    planes = []
    for w in words:
        lo, hi = _hashagg._halves_f32(w)
        planes.append(_prep(lo, n_pad, 0.0))
        planes.append(_prep(hi, n_pad, 0.0))
    call = _probe_kernel(len(words), T, n_chunks, interpret)
    with jax.enable_x64(False):
        gid_b, stats = call(
            _prep(slot0, n_pad, 0),
            _prep(live.astype(jnp.int32), n_pad, 0),
            jnp.stack(planes),
            table.astype(jnp.float32),
        )
    gid = gid_b.reshape(-1)[:n]
    return gid, stats[0, 0] > 0
