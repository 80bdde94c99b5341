"""`correct` has to be able to come out false.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

Two tests a size a test run can hold (CPU, interpreted kernels):

* the precision control — every cell's answers recomputed in float32 — is
  reported not correct (control.py; at SF1 in PERF.md; here at SF0.1, the
  smallest round scale at which q18 finds an order above its quantity);
* a run of the harness itself, past its look for a chip, with the timed path
  broken underneath — one cell of one answer altered where the client
  receives it, or where the engine turns its page into rows — reports
  `correct` false and counts the wrong answers as failed.
"""

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import control  # noqa: E402
import loader  # noqa: E402
import run  # noqa: E402

CELLS = [w["name"] for w in loader.benchmark()["workloads"]]


@pytest.fixture(scope="module")
def small_data():
    from trino_tpu.connectors.tpch import tpch_data

    return {t: tpch_data(t, 0.1) for t in ("lineitem", "orders", "customer")}


@pytest.mark.parametrize("cell", CELLS)
def test_lowered_precision_is_not_correct(cell, small_data):
    out = control.control_cell(cell, seed=2 ** 31 + 7, data=small_data)
    assert out["answers"] > 0
    assert not out["correct"], out
    assert out["decimal_rel_err"] > 1e-8  # money summed in float32


def rehearse(capsys, cell: str) -> dict:
    rc = run.main(["--cpu-rehearsal", "--workload", cell, "--seconds", "1",
                   "--seed", str(2 ** 31 + 11), "--trace", "0"])
    assert rc == run.EXIT_REHEARSAL
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert "CPU rehearsal only" in last
    return json.loads(last[last.index("{"):])


def test_sound_run_is_correct(capsys):
    out = rehearse(capsys, "served_q06")
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0


def test_altered_answer_on_the_wire_is_not_correct(capsys, monkeypatch):
    from trino_tpu.client.client import StatementClient

    real = StatementClient.execute
    calls = [0]

    def altered(self, sql, timeout=600.0):
        cols, rows = real(self, sql, timeout=timeout)
        calls[0] += 1
        if calls[0] > 3 and rows and rows[0][0] is not None:  # past the warm-up
            first = str(rows[0][0])  # the seventh digit: what float32 would lose
            digit = "1" if first[6] != "1" else "2"
            rows = [[first[:6] + digit + first[7:]] + list(rows[0][1:])] + list(rows[1:])
        return cols, rows

    monkeypatch.setattr(StatementClient, "execute", altered)
    out = rehearse(capsys, "served_q06")
    assert not out["correct"]
    assert out["failed"] == out["attempted"] > 0


def test_dropped_row_in_the_engine_is_not_correct(capsys, monkeypatch):
    from trino_tpu.data.page import Page

    real = Page.to_pylist

    def short(self):
        return real(self)[:-1]  # the answer loses its last row

    monkeypatch.setattr(Page, "to_pylist", short)
    out = rehearse(capsys, "served_q06")
    assert not out["correct"] and out["failed"] > 0
