"""The multiway cell's `correct` has to be able to come out false too.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_correct_multiway.py -q

`embedded_sf10_multiway` (Q5 and Q9 through Engine(), all eight tables) in the
CPU rehearsal (SF0.01, interpreted kernels): the harness walks to its end and
says correct; with the last row of every answer dropped where the engine
turns its page into rows it says not correct; and the precision control — the
references computed in float32 over the same eight tables — comes out not
correct.  (test_correct.py's control case builds lineitem, orders and
customer only, so it cannot reach this cell's references: PERF.md, Open
questions.)
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

CELL = "embedded_sf10_multiway"


def rehearse(capsys) -> tuple[dict, str]:
    rc = run.main(["--cpu-rehearsal", "--workload", CELL, "--seconds", "1",
                   "--seed", str(2 ** 31 + 11), "--trace", "0"])
    assert rc == run.EXIT_REHEARSAL
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert "CPU rehearsal only" in last
    return json.loads(last[last.index("{"):]), out


def test_multiway_cell_walks_to_its_end_and_is_correct(capsys):
    out, text = rehearse(capsys)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 2
    assert sorted(out["metric_names"]) == ["query_geomean_ms", "setup_s", "throughput_qps"]
    assert " 0 program(s) built in the window" in text
    assert "exact_mismatches 0 (limit 0), decimal_rel_err 0.000e+00" in text


def test_dropped_row_in_the_engine_is_not_correct(capsys, monkeypatch):
    from trino_tpu.data.page import Page

    real = Page.to_pylist
    monkeypatch.setattr(Page, "to_pylist", lambda self: real(self)[:-1])
    out, _text = rehearse(capsys)
    assert not out["correct"] and out["failed"] == out["attempted"] > 0


@pytest.mark.parametrize("scale", [0.01, 0.1])
def test_lowered_precision_is_not_correct(scale):
    from trino_tpu.connectors.tpch import tpch_data

    _cell, config, _mix, templates = loader.cell(CELL)
    data = {t: tpch_data(t, scale) for tm in templates.values() for t in tm["columns"]}
    out = control.control_cell(CELL, seed=2 ** 31 + 7, data=data)
    assert out["answers"] == 2
    assert not out["correct"], out
    for per in out["by_template"].values():  # each statement is caught alone
        assert per["decimal_rel_err"][1] > config["limits"]["decimal_rel_err"]
    assert out["exact_mismatches"] == 0
