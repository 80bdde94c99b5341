"""The prepared cell's `correct` has to be able to come out false too.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_correct_prepared.py -q

`served_sf10_prepared_scan` in the CPU rehearsal (SF0.01, interpreted
kernels): the harness walks to its end — client-held prepared statements,
EXECUTE with drawn bindings, the seeded sample against the plain reference —
and says correct; with one cell of every prepared answer altered where the
client receives it, it says not correct and counts the compared answers as
failed.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import run  # noqa: E402

CELL = "served_sf10_prepared_scan"


def rehearse(capsys) -> tuple[dict, str]:
    rc = run.main(["--cpu-rehearsal", "--workload", CELL, "--seconds", "1",
                   "--seed", str(2 ** 31 + 11), "--trace", "0"])
    assert rc == run.EXIT_REHEARSAL
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert "CPU rehearsal only" in last
    return json.loads(last[last.index("{"):]), out


def test_prepared_cell_walks_to_its_end_and_is_correct(capsys):
    out, text = rehearse(capsys)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 2
    assert sorted(out["metric_names"]) == ["query_geomean_ms", "setup_s", "throughput_qps"]
    # one program per prepared statement, none for a new binding
    assert " 0 program(s) built in the window" in text
    assert text.count(" 1 program(s) built") == 2  # q06's and q01's first execution


def test_altered_prepared_answer_is_not_correct(capsys, monkeypatch):
    from trino_tpu.client.client import StatementClient

    real = StatementClient.execute

    def altered(self, sql, timeout=600.0):
        cols, rows = real(self, sql, timeout=timeout)
        if sql.startswith("EXECUTE q06") and rows and rows[0][0] is not None:
            first = str(rows[0][0])  # the seventh digit: what float32 would lose
            digit = "1" if first[6] != "1" else "2"
            rows = [[first[:6] + digit + first[7:]]] + list(rows[1:])
        return cols, rows

    monkeypatch.setattr(StatementClient, "execute", altered)
    out, _text = rehearse(capsys)
    assert not out["correct"]
    assert 0 < out["failed"] <= out["attempted"]
