"""Run a snippet on the hardware platform in a child process.

The CPU suite's process is pinned to the CPU (tests/conftest.py), which also
keeps it off the chip: a chip belongs to one process at a time, so the
on-hardware tiers (test_tpch_tpu.py, test_tpcds_tpu.py) hand the chip to one
child each.  The child reports what it found before it runs anything:

- no accelerator (`jax.default_backend() == "cpu"`) -> the tier skips;
- an accelerator, and the snippet then fails -> the tier FAILS with the
  child's stderr.  Hardware that was found and did not work is the finding
  the tier exists for; it is never a skip.
"""

import json
import os
import subprocess
import sys

import pytest

_HW = os.environ.get("TRINO_TPU_HW_PLATFORM", "")

_PREAMBLE = r"""
import json, os, sys
sys.path.insert(0, {repo!r})
import jax
if jax.default_backend() == "cpu":
    print("NO_HARDWARE")
    sys.exit(0)
print("HARDWARE:" + jax.devices()[0].device_kind, flush=True)
from trino_tpu.utils.compilecache import enable_persistent_cache
enable_persistent_cache({repo!r})
"""


def run_on_hardware(body: str) -> dict:
    """Run `body` (python source that ends by printing "RESULT:<json>")
    after the preamble, on the stashed hardware platform."""
    if not _HW or _HW == "cpu":
        pytest.skip("no TPU platform available (explicitly CPU)")
    env = dict(os.environ)
    if _HW == "auto":
        env.pop("JAX_PLATFORMS", None)  # let jax autodetect the accelerator
    else:
        env["JAX_PLATFORMS"] = _HW
    env.pop("XLA_FLAGS", None)  # drop the CPU suite's virtual-device forcing
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _PREAMBLE.format(repo=repo) + body],
        env=env, cwd=repo, capture_output=True, text=True, timeout=3600,
    )
    if "NO_HARDWARE" in proc.stdout.splitlines():
        pytest.skip("jax found no accelerator")
    if proc.returncode != 0:
        pytest.fail(
            f"hardware subprocess failed (rc {proc.returncode}):\n"
            f"{proc.stdout[-1000:]}\n{proc.stderr[-3000:]}"
        )
    payload = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert payload, f"no RESULT line in subprocess output:\n{proc.stdout[-2000:]}"
    return json.loads(payload[-1][len("RESULT:"):])
