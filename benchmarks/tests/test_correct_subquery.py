"""The subquery cell's `correct` has to be able to come out false too.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_correct_subquery.py -q

`embedded_sf10_subquery` (Q16, Q20, Q21 and Q22 through Engine(), seven
tables) in the CPU rehearsal (SF0.01, interpreted kernels): the harness walks
to its end and says correct; with a count altered (Q21's first `numwait`, Q16's
first `supplier_cnt`) or the last row of every answer dropped where the
engine turns its page into rows it says not correct; and the precision
control — the references computed in float32 over the same seven tables —
comes out not correct, by Q22's decimal sum.  (test_correct.py's control case
builds lineitem, orders and customer only, so it cannot reach this cell's
references either: its case for this cell ends in KeyError, and conftest.py
beside it is an accepted file that names only the multiway cell's — PERF.md,
Open questions row 6.)
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import control  # noqa: E402
import loader  # noqa: E402
import run  # noqa: E402

CELL = "embedded_sf10_subquery"


def rehearse(capsys) -> tuple[dict, str]:
    rc = run.main(["--cpu-rehearsal", "--workload", CELL, "--seconds", "1",
                   "--seed", str(2 ** 31 + 13), "--trace", "0"])
    assert rc == run.EXIT_REHEARSAL
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert "CPU rehearsal only" in last
    return json.loads(last[last.index("{"):]), out


def test_subquery_cell_walks_to_its_end_and_is_correct(capsys):
    out, text = rehearse(capsys)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 4
    assert sorted(out["metric_names"]) == ["query_geomean_ms", "setup_s", "throughput_qps"]
    assert " 0 program(s) built in the window" in text
    assert "exact_mismatches 0 (limit 0), decimal_rel_err 0.000e+00" in text


def test_altered_count_in_the_engine_is_not_correct(capsys, monkeypatch):
    from trino_tpu.data.page import Page

    real = Page.to_pylist

    def altered(self):
        rows = real(self)
        if rows and isinstance(rows[0][-1], int):  # q21: numwait; q16: supplier_cnt
            rows = [tuple(rows[0][:-1]) + (rows[0][-1] + 1,)] + rows[1:]
        return rows

    monkeypatch.setattr(Page, "to_pylist", altered)
    out, text = rehearse(capsys)
    assert not out["correct"] and out["failed"] > 0
    assert "WRONG q21" in text and "WRONG q16" in text
    assert "WRONG q20" not in text and "WRONG q22" not in text  # strings; a decimal last


def test_dropped_row_in_the_engine_is_not_correct(capsys, monkeypatch):
    from trino_tpu.data.page import Page

    real = Page.to_pylist
    monkeypatch.setattr(Page, "to_pylist", lambda self: real(self)[:-1])
    out, _text = rehearse(capsys)
    assert not out["correct"] and out["failed"] == out["attempted"] > 0


def test_lowered_precision_is_not_correct():
    """At SF0.1: at SF0.01 a code's six or eight balances sum exactly in
    float32's 24 bits, so the control has nothing to lose there."""
    from trino_tpu.connectors.tpch import tpch_data

    _cell, config, _mix, templates = loader.cell(CELL)
    data = {t: tpch_data(t, 0.1) for tm in templates.values() for t in tm["columns"]}
    out = control.control_cell(CELL, seed=2 ** 31 + 7, data=data)
    assert out["answers"] == 4
    assert not out["correct"], out
    per = out["by_template"]
    assert per["q22"]["decimal_rel_err"][1] > config["limits"]["decimal_rel_err"]
    for name in ("q16", "q20", "q21"):  # counts and strings: float32 moves nothing
        assert per[name]["decimal_rel_err"] == [0.0, 0.0]
    assert out["exact_mismatches"] == 0 and out["double_rel_err"] == 0.0
