#!/usr/bin/env python3
"""Checks of the yardstick itself, by hand on the CPU (under two minutes):

    JAX_PLATFORMS=cpu python3 benchmarks/selftest.py

1. every numpy reference equals tests/oracle.SqliteOracle at SF0.01 — the
   validation binding and, for the parameterised templates, three seeded
   bindings (sqlite holds decimals as doubles, so here decimals are compared
   at rtol 1e-9; q18's threshold is lowered, the validation value selects
   no row at this scale);
2. the trace reduction reproduces the busy time, idle share and per-operation
   sums recorded beside testdata/served_q06_slice.xplane.pb;
3. the traffic generator gives the same streams for the same seed, other
   ones for another, and stays inside clause 2.4's domains.
"""

from __future__ import annotations

import decimal
import math
import os
import random
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import loader  # noqa: E402
import tracered  # noqa: E402
import traffic  # noqa: E402

SCALE = 0.01
TEMPLATES = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "templates")))


def close(got, want) -> bool:
    if isinstance(want, (decimal.Decimal, float)):
        return math.isclose(float(got), float(want), rel_tol=1e-9, abs_tol=1e-9)
    if hasattr(want, "isoformat"):
        return str(got) == want.isoformat()
    return got == want


def same_rows(got, want, ordered) -> bool:
    got, want = [tuple(r) for r in got], [tuple(r) for r in want]
    if not ordered:
        got, want = sorted(got, key=repr), sorted(want, key=repr)
    return len(got) == len(want) and all(
        len(g) == len(w) and all(close(a, b) for a, b in zip(g, w))
        for g, w in zip(got, want))


def check_references() -> None:
    from tests.oracle import SqliteOracle
    from trino_tpu.connectors.tpch import tpch_data

    templates = {n: loader.load_json("templates", n + ".json") for n in TEMPLATES}
    tables = sorted({t for tm in templates.values() for t in tm["columns"]})
    data = {t: tpch_data(t, SCALE) for t in tables}
    oracle = SqliteOracle(data)
    for name, t in templates.items():
        mod = loader.load_module("reference", t["reference"])
        text = loader.sql_text(t)
        if hasattr(mod, "QUANTITY"):  # q18: 300 selects nothing at SF0.01
            mod.QUANTITY = 150
            text = text.replace("> 300", "> 150")
        cases = [(text, traffic.validation(t))]
        for i in range(3 if "sites" in t else 0):
            b = traffic.draw(t, random.Random(f"selftest/{i}"))
            sql = loader.sql_text(t, "prepared_text")
            for lit in b.literals:
                sql = sql.replace("?", lit.replace("DATE ", ""), 1)
            cases.append((sql, b))
        for sql, b in cases:
            want = mod.reference(data, *b.args)
            got = oracle.query(sql)
            assert want, f"{name} {b.params}: the reference selected no row"
            assert same_rows(got, want, t["ordered"]), (name, b.params, got[:2], want[:2])
            low = mod.reference(data, *b.args, lowered=True)
            assert len(low) == len(want), (name, "lowered reference lost rows")
        print(f"selftest: reference {name} equals sqlite on {len(cases)} binding(s)")


def check_trace_reduction() -> None:
    exp = loader.load_json("testdata", "served_q06_slice.expected.json")
    loaded = tracered.load(os.path.join(HERE, "testdata", "served_q06_slice.xplane.pb"))
    s0, s1 = exp["window_ns"]
    red = tracered.reduce(loaded, s0, s1)
    assert loaded["modules"] == exp["modules"], loaded["modules"]
    assert sum(1 for n, *_ in loaded["host"] if n.startswith("bench:")) == exp["annotations"]
    # the expected numbers were summed in picoseconds from the protobuf; the
    # profiler's reader hands out whole nanoseconds, so agree to 1e-4
    assert math.isclose(red["busy_s"] * 1e9, exp["busy_ns"], rel_tol=1e-4), red["busy_s"]
    idle = 1.0 - red["busy_s"] / red["window_s"]
    assert math.isclose(idle, 1.0 - exp["busy_ns"] / (s1 - s0), rel_tol=1e-4)
    for name, ns in exp["top_ops_ns"]:
        assert math.isclose(red["ops"][name] * 1e9, ns, rel_tol=1e-4), name
    half = tracered.reduce(loaded, s0, (s0 + s1) / 2)  # clipping
    assert 0 < half["busy_s"] < red["busy_s"]
    assert tracered.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tracered.covered([(0, 3), (5, 6)], 2, 5.5) == 1.5
    print(f"selftest: trace reduction: busy {red['busy_s'] * 1e3:.3f} ms of "
          f"{red['window_s'] * 1e3:.0f} ms, {len(red['ops'])} op names")


def check_traffic() -> None:
    for f in sorted(os.listdir(os.path.join(HERE, "traffic"))):
        w = {"name": f[:-5]}
        mix, templates = loader.mix(w["name"])

        def sent(seed):
            out = []
            for s in traffic.streams(mix, templates, seed):
                out.append((tuple(s.order), round(s.stagger_s, 9), tuple(
                    s.next_binding(n).key for _ in range(20) for n in s.order)))
            return out

        big = 2 ** 31 + 12345
        assert sent(big) == sent(big), w["name"]
        assert sorted(sent(big)[0][0]) == sorted(mix["pass"])
        if mix["bindings"] == "drawn" or mix.get("order") == "permuted":
            assert any(sent(big) != sent(big + k) for k in range(1, 6)), w["name"]
        for s in traffic.streams(mix, templates, big):
            assert 0 <= s.stagger_s <= mix.get("stagger_ms", 0) / 1e3
            for _ in range(200):
                for n in s.order:
                    b = s.next_binding(n)
                    for p, dom in templates[n].get("parameters", {}).items():
                        assert dom["lo"] <= b.params[p] <= dom["hi"], (n, b.params)
    q06 = loader.load_json("templates", "q06.json")
    b = traffic.validation(q06)
    assert b.literals == ("DATE '1994-01-01'", "DATE '1995-01-01'", "0.05", "0.07", "24")
    assert b.args == (8766, 9131, 5, 7, 24), b.args
    q01 = loader.load_json("templates", "q01.json")
    assert traffic.validation(q01).literals == ("DATE '1998-09-02'",)
    print("selftest: traffic generator is deterministic and inside the domains")


def main() -> int:
    check_traffic()
    check_trace_reduction()
    check_references()
    print("selftest: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
