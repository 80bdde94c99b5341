"""Microbenchmarks of the q18 hot kernels in isolation on the default device.

Each case is jitted on its own so device time attributes exactly; timing uses
back-to-back dispatch with one final block (the per-call sync amortized away).
"""
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

from trino_tpu.utils.compilecache import enable_persistent_cache
enable_persistent_cache(_REPO)

import jax.numpy as jnp
import numpy as np

import trino_tpu  # noqa: F401  (enables x64)
from trino_tpu.data.types import BIGINT
from trino_tpu.ops.expr import ColumnVal
from trino_tpu.ops import relops

N = 8_388_608  # 8M lanes (q18 join frame capacity)
rng = np.random.default_rng(0)


def timeit(name, fn, *args, iters=4):
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    print(f"{name:44s} {dt*1e3:9.1f} ms   (first {compile_s:.1f}s)", flush=True)
    return dt


def col(arr):
    return ColumnVal(jnp.asarray(arr), None, None, BIGINT)


# ---- raw building blocks -------------------------------------------------
keys5 = [rng.integers(0, 1_500_000, N).astype(np.int64) for _ in range(5)]
vals = rng.integers(0, 50, N).astype(np.int64)
live = np.ones((N,), bool)

j_keys5 = [jnp.asarray(k) for k in keys5]
j_vals = jnp.asarray(vals)
j_live = jnp.asarray(live)

iota = jnp.arange(N, dtype=jnp.int32)


@jax.jit
def sort12(ks, live):
    ops = [(~live).astype(jnp.int8)]
    for k in ks:
        ops.append(jnp.zeros((N,), jnp.bool_))
        ops.append(k)
    return jax.lax.sort(ops + [iota], num_keys=len(ops))[-1]


@jax.jit
def sort2(k, live):
    ops = [(~live).astype(jnp.int8), k]
    return jax.lax.sort(ops + [iota], num_keys=2)[-1]


G4 = 4_194_304
G2 = 2_097_152


@jax.jit
def boundary(seg):
    gids = jnp.arange(G4, dtype=jnp.int32)
    starts = relops.searchsorted_tpu(seg, gids, side="left")
    ends = relops.searchsorted_tpu(seg, gids, side="right")
    return starts.sum() + ends.sum()


@jax.jit
def cumsum_diff(vals64, seg):
    ce = jnp.concatenate([jnp.zeros((1,), jnp.int64), jnp.cumsum(vals64)])
    gids = jnp.arange(G4, dtype=jnp.int32)
    starts = relops.searchsorted_tpu(seg, gids, side="left")
    ends = relops.searchsorted_tpu(seg, gids, side="right")
    return (jnp.take(ce, ends) - jnp.take(ce, starts)).sum()


seg_sorted = jnp.sort(rng.integers(0, G4, N).astype(np.int32))

timeit("lax.sort 11-operand 8M (5-key grouped sort)", sort12, j_keys5, j_live)
timeit("lax.sort 2-operand 8M (1-key sort)", sort2, j_keys5[0], j_live)
timeit("boundary searchsorted x2 (G=4M, n=8M)", boundary, seg_sorted)
timeit("cumsum+boundary diff sum (G=4M)", cumsum_diff, j_vals, seg_sorted)


# ---- full group_aggregate shapes ----------------------------------------
@jax.jit
def agg5(ks, v, live):
    kcols = [ColumnVal(k, None, None, BIGINT) for k in ks]
    out_keys, out_aggs, out_live, ng = relops.group_aggregate(
        kcols, [ColumnVal(v, None, None, BIGINT)],
        [relops.AggSpec("sum")], live, G4,
    )
    return out_aggs[0][0].sum() + ng


@jax.jit
def agg1(k, v, live):
    out_keys, out_aggs, out_live, ng = relops.group_aggregate(
        [ColumnVal(k, None, None, BIGINT)], [ColumnVal(v, None, None, BIGINT)],
        [relops.AggSpec("sum")], live, G2,
    )
    return out_aggs[0][0].sum() + ng


N6 = 6_291_456
k6 = jnp.asarray(rng.integers(0, 1_500_000, N6).astype(np.int64))
v6 = jnp.asarray(rng.integers(0, 50, N6).astype(np.int64))
l6 = jnp.ones((N6,), jnp.bool_)

timeit("group_aggregate 5 keys G=4M n=8M", agg5, j_keys5, j_vals, j_live)
timeit("group_aggregate 1 key G=2M n=6M", agg1, k6, v6, l6)


# ---- semi join shape -----------------------------------------------------
@jax.jit
def semi(probe_k, probe_live, build_k, build_live):
    cols, new_live, req = relops.equi_join(
        "semi",
        [ColumnVal(probe_k, None, None, BIGINT)], probe_live,
        [ColumnVal(build_k, None, None, BIGINT)], build_live,
        [ColumnVal(probe_k, None, None, BIGINT)],
        [ColumnVal(build_k, None, None, BIGINT)],
        None, 8_388_608,
    )
    return new_live.sum() + req


NP_, NB = 2_097_152, 4_194_304
pk = jnp.asarray(rng.integers(0, 1_500_000, NP_).astype(np.int64))
pl = jnp.asarray(np.arange(NP_) < 1_500_000)
bk = jnp.asarray(rng.integers(0, 1_500_000, NB).astype(np.int64))
bl = jnp.asarray(np.arange(NB) < 60)  # HAVING output: tiny live build

timeit("equi_join semi probe=2M build=4M C=8M", semi, pk, pl, bk, bl)
print("done", flush=True)
