"""`correct` has to be able to come out false in the four-chip cell too.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

The harness itself, past its look for chips, on four virtual CPU devices
(the way in takes jax.devices()[:layout.chips]) at the rehearsal scale: a
sound run, then one with the timed path broken underneath — a count of
q12's answer altered where the engine turns its page into rows — which must
report `correct` false and name the statement.  test_correct.py's control
already walks every cell of `workloads`, this one among them.

q01's AVGs come out of the rehearsal's interpreted float32 kernels 5e-8 off
(PERF.md section 2: a property of the rehearsal, on every path that declines
the fused scan), so a sound rehearsal of this cell is not `correct` either;
what tells the two runs apart is q12: exact integers, no mismatch allowed.
"""

import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    # before any test of this directory first asks JAX for its devices
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import pytest  # noqa: E402
import run  # noqa: E402

CELL = "spmd_q12_q01"


@pytest.fixture(autouse=True)
def learn_as_sf1_does(monkeypatch):
    """SF1's inputs are past the eager-sizing limit: capacities come from
    the compiled program's overflow retries, here too (the eager shard_map
    with interpreted kernels sizes q01 for three minutes)."""
    from trino_tpu.exec import compiler

    monkeypatch.setattr(compiler, "_EAGER_SIZING_LIMIT", 0)


def rehearse(capsys) -> str:
    rc = run.main(["--cpu-rehearsal", "--workload", CELL, "--seconds", "1",
                   "--seed", str(2 ** 31 + 27), "--trace", "0"])
    assert rc == run.EXIT_REHEARSAL
    out = capsys.readouterr().out
    assert "CPU rehearsal only" in out.strip().splitlines()[-1]
    return out


def numbers(out: str) -> dict:
    line = next(ln for ln in out.splitlines() if "compared" in ln and "exact_mismatches" in ln)
    return {"compared": int(re.search(r"compared (\d+) answers", line).group(1)),
            "exact_mismatches": int(re.search(r"exact_mismatches (\d+)", line).group(1)),
            "decimal_rel_err": float(re.search(r"decimal_rel_err (\S+)", line).group(1))}


def test_sound_run_walks_the_flow_on_four_devices(capsys):
    out = rehearse(capsys)
    assert re.search(r"warm q12 .*round 0: .* [1-9]\d* program\(s\) built", out)
    assert re.search(r"warm q12 .*round 1: .* 0 program\(s\) built", out)
    assert "0 program(s) built in the window" in out
    n = numbers(out)
    assert n["compared"] > 0 and n["exact_mismatches"] == 0 and n["decimal_rel_err"] <= 1e-8
    assert "WRONG q12" not in out


def test_altered_count_in_the_engine_is_not_correct(capsys, monkeypatch):
    from trino_tpu.data.page import Page

    real = Page.to_pylist

    def altered(self):
        rows = real(self)
        if rows and len(rows[0]) == 3:  # q12: (l_shipmode, high, low)
            rows = [tuple(rows[0][:1]) + (rows[0][1] + 1,) + tuple(rows[0][2:])] + rows[1:]
        return rows

    monkeypatch.setattr(Page, "to_pylist", altered)
    out = rehearse(capsys)
    last = out.strip().splitlines()[-1]
    assert '"correct": false' in last
    assert numbers(out)["exact_mismatches"] > 0
    assert "WRONG q12" in out
