"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding (pjit /
shard_map / all_to_all exchanges) is exercised without TPU hardware -- the
same trick the reference uses with DistributedQueryRunner launching N servers
in one JVM over loopback (testing/trino-testing/.../DistributedQueryRunner.java:107):
the full stack runs, only the transport is local.

Env vars MUST be set before jax initializes its backends, hence here.
"""

import os

# Force CPU: on a host with a TPU chip JAX picks the chip by default, but
# correctness tests need (a) true float64 — TPU silently computes f64 at
# f32 precision — and (b) 8 virtual devices for the multi-chip exchange
# tests.  Hence a hard override, not setdefault.  It also keeps this
# (parent) process off the chip, which belongs to one process at a time.
# Stash the hardware platform before forcing CPU so the on-TPU differential
# tier (tests/test_tpch_tpu.py) can re-enable it in a subprocess.  An unset
# JAX_PLATFORMS means "autodetect" — stash "auto" (not ""), so the tier still
# probes for hardware on plain TPU VMs where nothing was exported.
os.environ.setdefault("TRINO_TPU_HW_PLATFORM", os.environ.get("JAX_PLATFORMS") or "auto")
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# something may have imported jax already; the config update still wins
# as long as no backend has been used yet.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: the suite's dominant cost is compiling
# the same fragment programs run after run (an 8-device shard_map plan at
# SF0.01 compiles in 1-6 s); the on-disk cache makes re-runs hit warm
# compiles.
_repo_root = os.path.dirname(os.path.dirname(__file__))
import sys  # noqa: E402

sys.path.insert(0, _repo_root)
from trino_tpu.utils.compilecache import enable_persistent_cache  # noqa: E402

# host-fingerprinted dir: XLA:CPU AOT entries from another machine fail to
# load (and recompile) on hosts with different CPU features
enable_persistent_cache(_repo_root)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# ---------------------------------------------------------------- CI tiers
# Two tiers (reference: fast PR checks vs nightly product tests,
# testing/trino-product-tests/):
#   smoke — `pytest -m smoke`, 5 min on one core, cold cache (PR 29: 300 s
#           pinned to one core, 186 tests): data plane, Pallas
#           interpreter kernels, a few TPC-H locals, ONE 8-device
#           distributed query, multihost control-plane basics.
#   full  — everything (the default; what the driver runs with
#           `-m 'not slow'` on six workers: 4 min on 8 cores, cold cache).
_SMOKE = {
    "tests/test_data_plane.py": None,  # None = whole module
    "tests/test_native_serde.py": None,
    "tests/test_pallas.py": None,
    "tests/test_tpch.py": {"q01", "q06", "q03"},
    "tests/test_tpch_distributed.py": {"q01"},
    "tests/test_multihost.py": {
        "test_client_protocol",
        "test_discovery_and_heartbeat",
        "test_task_level_retry",
    },
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "smoke: fast CI tier; run with -m smoke"
    )
    config.addinivalue_line(
        "markers", "tpu: requires real TPU hardware (skipped on CPU-only hosts)"
    )
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 gate (`-m 'not slow'`)"
    )
    config.addinivalue_line(
        "markers",
        "chaos: randomized-fault resilience tier; run with scripts/chaos_tier.sh",
    )


def pytest_collection_modifyitems(config, items):
    for item in items:
        rel = os.path.relpath(str(item.fspath), os.path.dirname(os.path.dirname(__file__)))
        sel = _SMOKE.get(rel)
        if sel is None and rel not in _SMOKE:
            continue
        if sel is None:
            item.add_marker(pytest.mark.smoke)
        else:
            name = item.name
            base = name.split("[")[0]
            param = name[len(base) + 1 : -1] if "[" in name else None
            if base in sel or (param is not None and param in sel):
                item.add_marker(pytest.mark.smoke)


@pytest.fixture(scope="session")
def tpch_tiny():
    """TPC-H tiny (SF 0.01) tables as numpy dicts, generated once per session."""
    from trino_tpu.connectors.tpch import tpch_data
    from trino_tpu.connectors.tpch.generator import TPCH_SCHEMAS

    return {t: tpch_data(t, 0.01) for t in TPCH_SCHEMAS}


@pytest.fixture(scope="session")
def oracle(tpch_tiny):
    """sqlite differential oracle over the same generated data (the
    reference's H2QueryRunner analogue)."""
    from tests.oracle import SqliteOracle

    return SqliteOracle(tpch_tiny)
