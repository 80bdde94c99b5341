"""Chipless TPU compiles of the main-path Pallas kernels at TPC-H SF1 shapes.

Interpret mode (tests/test_pallas*.py) proves what the kernels compute; it
cannot show what the chip's compiler refuses.  Before PR 22 the fused scan
kernel aborted the compiler on a keyless recipe and a wide segment reduce
outgrew VMEM (and two hash kernels, gone since PR 45, were refused by Mosaic
outright) — all invisible to every interpreted test.  The TPU compiler is
installed here and compiles for a chip that is described, not attached, so
these tests guard every later PR at no chip time.  Nothing runs: results are
the interpreted tests' business.

The topology is described inside a module-scoped fixture (never at import,
in a skipif or in parametrize: the TPU library belongs to one process, and
every xdist worker imports every test file), and all of these tests live in
this one file so one worker holds the library.
"""

import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N_SF1 = 6_001_215  # lineitem rows at SF1
N_SF10 = 60_000_466  # the generator's lineitem at SF10 (`served_sf10_scan`)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chipless compile is written to the persistent cache but cannot be
    # read back without a chip: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes, kernel):
    """AOT-compile for the described chip -> (seconds, the compiled program);
    the kernel must be in the program as a Mosaic custom call that carries
    its name (`name=` on the pallas_call: a profiler trace then says
    `%seg_reduce.1`, not `%call.130`)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*shapes).compile()
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"%{kernel}" in text, kernel
    return seconds, compiled


def _col(one_chip, dtype, n=N_SF1):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)


def test_segment_reduce_compiles_for_v5e(one_chip):
    from trino_tpu.ops.pallas.segreduce import SegRed, fused_segment_reduce

    def reduce5(seg, f64, i64, i32, valid):
        return fused_segment_reduce(
            seg,
            [SegRed("sum", f64, valid), SegRed("sum", i64, valid),
             SegRed("count", None, valid), SegRed("min", i32, valid),
             SegRed("max", f64, valid)],
            4, force_pallas=True,
        )

    _compile(
        reduce5, _col(one_chip, jnp.int32), _col(one_chip, jnp.float64),
        _col(one_chip, jnp.int64), _col(one_chip, jnp.int32),
        _col(one_chip, jnp.bool_), kernel="seg_reduce",
    )


def test_wide_segment_reduce_fits_vmem(one_chip):
    """q01 over a sharded scan asks for 34 reductions, 111 limb planes:
    one pass wanted 16.1 MB of scoped VMEM (limit 16 MB) and was refused;
    the reduction now splits into passes that fit."""
    from trino_tpu.ops.pallas.segreduce import SegRed, fused_segment_reduce

    n = N_SF1 // 4

    def reduce34(seg, i64, f64, valid):
        reds = [SegRed("sum", i64, valid)] * 22 + [
            SegRed("sum", f64, valid), SegRed("count", None, valid),
        ] * 6
        return fused_segment_reduce(seg, reds, 6, force_pallas=True)

    _compile(
        reduce34, _col(one_chip, jnp.int32, n), _col(one_chip, jnp.int64, n),
        _col(one_chip, jnp.float64, n), _col(one_chip, jnp.bool_, n),
        kernel="seg_reduce",
    )


def test_radix_topk_compiles_for_v5e(one_chip, monkeypatch):
    from trino_tpu.ops.pallas import segreduce

    # the histogram passes ask jax.default_backend(), which is the CPU here:
    # steer them to the kernel in the test, as the chip would
    monkeypatch.setattr(
        segreduce, "pallas_segreduce_supported", lambda g, backend=None: True
    )
    from trino_tpu.ops.pallas import topk

    _compile(
        lambda u, live: topk.radix_topk_threshold(u, live, 10),
        _col(one_chip, jnp.uint32), _col(one_chip, jnp.bool_),
        kernel="seg_reduce",  # the histogram passes are segment reductions
    )


@pytest.fixture(scope="module")
def fused_recipes():
    """The q01 and q06 recipes as the engine plans them (captured at
    SF0.01 under interpret mode; dictionaries, so domains, equal SF1's), and
    q06's as a prepared statement plans it: five `?` sites, five scalars."""
    import json

    from tests.tpch_queries import QUERIES
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.exec.compilesvc import CompileService
    from trino_tpu.ops import kernels
    from trino_tpu.ops.pallas import fused
    from trino_tpu.runtime.engine import Engine

    captured = {}
    orig = fused.run

    def spy(recipe, scan_cols, live, **kw):
        captured["last"] = (recipe, list(scan_cols))
        return orig(recipe, scan_cols, live, **kw)

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    eng.session.set("pallas_interpret", "true")
    # its own service: the process's may hold these programs already (another
    # test file's, in this worker), and a joined program is never traced
    eng.executor.compile_service = eng._local_fallback.compile_service = CompileService()
    fused.run = spy
    try:
        out = {}
        for name in ("q01", "q06"):
            eng.query(QUERIES[name])
            out[name] = captured.pop("last")
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "benchmarks", "templates", "q06.json")) as f:
            prepared = "\n".join(json.load(f)["prepared_text"])
        eng.execute("PREPARE q06 FROM " + prepared)
        eng.execute("EXECUTE q06 USING DATE '1994-01-01', DATE '1995-01-01', 0.05, 0.07, 24")
        out["q06-prepared"] = captured.pop("last")
    finally:
        fused.run = orig
        kernels.set_policy(kernels.KernelPolicy())  # the engine set the process's
    return out


def _fused_run_shapes(one_chip, recipe, cols, n, live_mask=False):
    """`fused.run` over `n`-row columns of the dtypes the engine holds them
    in -> (function, its argument shapes).  `live_mask`: the page has a mask
    of its own; else it hands over its row count, as a TPC-H scan does."""
    from trino_tpu.ops.expr import ColumnVal
    from trino_tpu.ops.pallas import fused

    used = {i for i, _ in recipe.cols}

    def run(live, params, *arrays):
        it = iter(arrays)
        scan = []
        for i, cv in enumerate(cols):
            if i not in used:
                scan.append(None)
                continue
            data = next(it)
            valid = next(it) if cv.valid is not None else None
            scan.append(ColumnVal(data, valid, cv.dict, cv.type, None))
        return fused.run(recipe, scan, live if live_mask else n, params=params)

    shapes = [_col(one_chip, jnp.bool_, n if live_mask else 1), tuple(
        jax.ShapeDtypeStruct((), jnp.dtype(p.type.np_dtype), sharding=one_chip)
        for p in recipe.params)]
    for i, cv in enumerate(cols):
        if i in used:
            shapes.append(_col(one_chip, cv.data.dtype, n))
            if cv.valid is not None:
                shapes.append(_col(one_chip, jnp.bool_, n))
    return run, shapes


@pytest.mark.parametrize("name", ["q01", "q06", "domain-129", "q06-prepared"])
def test_fused_scan_compiles_for_v5e(one_chip, fused_recipes, name):
    """q06 (keyless) and q01 (6 groups) take the select-and-add scatter;
    `domain-129` is q01's recipe with 65 return flags (130 groups: one more
    lane tile than 128), so the one-hot matmul form, at two lane tiles, is
    still compiled somewhere; `q06-prepared` is `EXECUTE q06`'s recipe, its
    bindings scalar operands in SMEM (two int32 dates, three f32 pairs).  The
    columns are int32 (money narrowed on upload, dates, dictionary codes), as
    the engine hands them: (n,) operands in (8192,) blocks, the last ragged."""
    import dataclasses

    from trino_tpu.ops.pallas import fused

    recipe, cols = fused_recipes["q01" if name == "domain-129" else name]
    assert {str(cols[i].data.dtype) for i, _ in recipe.cols} == {"int32"}
    if name == "domain-129":
        (c0, _, s0), (c1, d1, s1) = recipe.keys
        recipe = dataclasses.replace(
            recipe, keys=((c0, 65, s0), (c1, d1, s1)), domain=65 * d1
        )
        assert fused.scatter_form(recipe) == ("mxu", 256)
    else:
        assert fused.scatter_form(recipe) == ("vpu", 128)
    assert len(recipe.params) == (5 if name == "q06-prepared" else 0)
    run, shapes = _fused_run_shapes(one_chip, recipe, cols, N_SF1)
    _compile(run, *shapes, kernel="fused_scan")


@pytest.mark.parametrize("name,rows,live_mask", [
    ("q01", N_SF10, False), ("q06", N_SF10, False),
    ("q01", N_SF10, True), ("q06", 100, True),
], ids=["q01-sf10", "q06-sf10", "q01-sf10-masked", "q06-100-rows-masked"])
def test_fused_scan_reads_resident_columns_in_place(
        one_chip, fused_recipes, name, rows, live_mask):
    """`served_sf10_scan`'s two programs at its 60,000,466 rows: the kernel's
    operands are the resident columns themselves, so `fused.run` may hold no
    temporary the size of a column — the f64 casts, hi/lo planes, pads and
    stacks it made before were 5.28 GB (q01) and 2.88 GB (q06).  A page with a
    mask of its own pays for that mask's cast to int32 and nothing else; a
    page of 512 rows or fewer, which the TPU compiler lays out in smaller
    tiles than the kernel's blocks are read in, compiles too (it is copied
    into one sub-chunk's length)."""
    recipe, cols = fused_recipes[name]
    run, shapes = _fused_run_shapes(one_chip, recipe, cols, rows, live_mask)
    _, compiled = _compile(run, *shapes, kernel="fused_scan")
    temp = compiled.memory_analysis().temp_size_in_bytes
    # ISSUE 36 allowed a pad a column (4 bytes x rows x referenced columns);
    # in place it is, the mask's cast aside, under a megabyte
    assert temp <= 4 * rows * live_mask + (1 << 20), temp
