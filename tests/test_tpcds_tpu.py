"""On-hardware differential tier: TPC-DS subset on the real TPU chip.

Same mechanism as tests/test_tpch_tpu.py (hardware subprocess, oracle diff
in the parent) over twelve TPC-DS queries spanning star joins, date-dim
filters, demographic cross joins, returns anti-joins, rollup, and
rank-over-aggregate — the shapes where TPU numerics (f32 Kahan floors,
emulated f64, limb-exact int64) could diverge from the CPU suite.

Reference: the per-connector on-hardware variants of the engine suites
(testing/trino-testing/.../AbstractTestQueries.java subclasses).
"""

import pytest

from tests.hw_runner import run_on_hardware
from tests.oracle import SqliteOracle, assert_rows_equal
from tests.tpcds_queries import ORDERED, QUERIES

_SCALE = 0.002

_TPU_QUERIES = [
    "q03", "q07", "q19", "q42", "q52", "q55", "q65", "q68", "q79", "q85",
    "q96", "q98",
]

_RUNNER = r"""
from tests.tpcds_queries import QUERIES
from trino_tpu.connectors.tpcds import TpcdsConnector
from trino_tpu.runtime.engine import Engine

eng = Engine(default_catalog="tpcds")
eng.register_catalog("tpcds", TpcdsConnector({scale}))
out = {{}}
for name in {names!r}:
    rows = eng.query(QUERIES[name])
    out[name] = [list(r) for r in rows]
print("\nRESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def tpcds_tpu_results():
    return run_on_hardware(_RUNNER.format(scale=_SCALE, names=_TPU_QUERIES))


@pytest.fixture(scope="module")
def tpcds_oracle_small():
    from trino_tpu.connectors.tpcds import TPCDS_SCHEMAS, tpcds_data

    needed = set()
    for q in _TPU_QUERIES:
        for t in TPCDS_SCHEMAS:
            if t in QUERIES[q]:
                needed.add(t)
    return SqliteOracle(
        {t: tpcds_data(t, _SCALE) for t in sorted(needed)},
        schemas=TPCDS_SCHEMAS,
    )


@pytest.mark.parametrize("name", _TPU_QUERIES)
def test_tpcds_on_tpu(name, tpcds_tpu_results, tpcds_oracle_small):
    got = [tuple(r) for r in tpcds_tpu_results[name]]
    want = tpcds_oracle_small.query(QUERIES[name])
    assert_rows_equal(got, want, ordered=ORDERED[name], rtol=1e-6)
