"""The capacity protocol's step down (exec/compiler.py `_tighten`,
exec/capcache.py): after the run that first converges a plan, EVERY sized
node whose need lies far under its tier gets `_pow2(2 * need + 1024)`; the
second run is the same rows from a program at those tiers, the third builds
nothing, and from then on tiers only grow.
"""

import jax
import numpy as np
import pytest

from trino_tpu.exec import capcache
from trino_tpu.exec.compiler import _node_ids, _pow2
from trino_tpu.exec.compilesvc import CompileService
from trino_tpu.utils.tracing import InMemorySpanExporter, Tracer

N = 20_000  # under the optimizer's 64k gate: no Compact beside the node under test


@pytest.fixture
def own_caps_file(tmp_path, monkeypatch):
    """A capacity file of the test's own, and nothing settled."""
    monkeypatch.setenv("TRINO_TPU_CAPS_CACHE", str(tmp_path / "caps_cache.json"))
    monkeypatch.setattr(capcache, "_mem", None)
    return tmp_path / "caps_cache.json"


def _memory_engine(**kwargs):
    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.connectors.spi import ColumnSchema
    from trino_tpu.data.types import BIGINT
    from trino_tpu.runtime.engine import Engine

    rng = np.random.default_rng(30)
    conn = MemoryConnector()
    for name in ("a", "b"):
        conn.create_table(name, [ColumnSchema("k", BIGINT), ColumnSchema("v", BIGINT)])
        conn.insert(name, {
            "k": rng.permutation(N).astype(np.int64),  # unique
            "v": rng.integers(0, 100, N).astype(np.int64),
        })
    eng = Engine(default_catalog="mem", **kwargs)
    eng.register_catalog("mem", conn)
    return eng


def _tpch_engine(**kwargs):
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    eng = Engine(**kwargs)
    eng.register_catalog("tpch", TpchConnector(0.01))
    return eng


KINDS = ("Aggregate", "Compact", "Distinct", "Exchange", "Join", "TopN", "Unnest")


def _counted() -> dict:
    """trino_tpu_capacity_tightened_total by node kind."""
    return {kind: capcache.TIGHTENED.value(kind) for kind in KINDS}


def _flat(spans):
    for s in spans:
        yield s
        yield from _flat(s.children)


def _instrument(ex):
    """A service of the executor's own, its `compile` spans, and what every
    call of `_run` was given and reported."""
    ex.compile_service = CompileService()
    ex.tracer, exporter = Tracer(), InMemorySpanExporter()
    ex.tracer.add_exporter(exporter)
    runs = []
    real = ex._run

    def spied(plan, inputs, caps, *args, **kwargs):
        out = real(plan, inputs, caps, *args, **kwargs)
        runs.append((dict(caps), dict(out[1])))
        return out

    ex._run = spied

    def causes():
        return [s.attributes["cause"] for s in _flat(exporter.snapshot())
                if s.name == "compile"]

    return runs, causes


# kind -> (engine, statement, {node kind: how many of it must tighten})
CASES = {
    "aggregate_over_selective_filter": (
        _memory_engine, "select k, sum(v) from a where v + k < 100 group by k",
        {"Aggregate": 1}),
    "distinct": (
        _memory_engine, "select distinct k from a where v + k < 100", {"Distinct": 1}),
    # a filtering join that still builds a frame: a residual of two conjuncts
    # (one comparison, or none, is answered off the rank: no frame, no tier)
    "semi_join": (
        _memory_engine,
        "select count(*) from a where v < 1 and exists"
        " (select 1 from b where b.k = a.k and b.v <> a.v and b.v < a.v + 50)",
        {"Join": 1}),
    "inner_join_loose_stats_frame": (
        _memory_engine,
        "select count(*), sum(b.v) from a join b on a.k = b.k where a.v < 1",
        {"Join": 1}),
    "repartition_exchange_and_both_aggregates": (
        lambda: _tpch_engine(distributed=True, devices=jax.devices()[:4]),
        "select l_returnflag, l_linestatus, sum(l_quantity), count(*) from lineitem"
        " group by l_returnflag, l_linestatus order by 1, 2",
        {"Aggregate": 2, "Exchange": 1}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_every_sized_node_tightens_once(case, own_caps_file):
    make, sql, kinds = CASES[case]
    eng = make()
    ex = eng.executor
    runs, causes = _instrument(ex)
    plan = eng.plan(sql)
    nodes = _node_ids(plan)
    before = _counted()

    rows1 = eng.execute_page(sql).to_pylist()
    assert len(runs) == 1 and causes() == ["new_plan"]  # sized loosely, not wrongly
    loose, need = runs[0]
    learned = dict(ex._learned_caps[plan])
    tightened = {nid for nid in loose if learned[nid] < loose[nid]}
    assert tightened, (loose, need)
    for nid in tightened:
        assert learned[nid] == _pow2(2 * need[nid] + 1024), (nid, need[nid])
    by_kind: dict = {}
    for nid in tightened:
        kind = type(nodes[nid]).__name__
        by_kind[kind] = by_kind.get(kind, 0) + 1
    assert {k: by_kind.get(k, 0) for k in kinds} == kinds, (by_kind, loose, learned)
    after = _counted()
    assert {k: after[k] - before[k] for k in KINDS if after[k] != before[k]} == by_kind

    # the second run: the same rows from ONE new program, at the tight tiers
    rows2 = eng.execute_page(sql).to_pylist()
    assert rows2 == rows1
    assert len(runs) == 2 and runs[1][0] == learned
    assert causes() == ["new_plan", "caps_tightened"]
    assert ex.compile_service.builds == 2
    assert ex._learned_caps[plan] == learned

    # the third builds nothing, tightens nothing
    assert eng.execute_page(sql).to_pylist() == rows1
    assert ex.compile_service.builds == 2 and len(runs) == 3
    assert _counted() == after
    assert capcache.load_caps(plan, ex._load_inputs(nodes, None), ex._caps_scope) == (
        learned, True)


@pytest.mark.parametrize("sql,form", [
    ("select count(*) from a where v < 1 and k in (select k from b)", "rank"),
    ("select count(*) from a where v < 1 and k not in (select k from b)", "rank"),
    ("select count(*) from a where v < 1 and exists"
     " (select 1 from b where b.k = a.k and b.v <> a.v)", "minmax"),
])
def test_a_join_that_builds_no_frame_reports_no_need(sql, form, own_caps_file):
    """A filtering join answered off its rank (ops/relops.py `filter_form`)
    is sized like any join but reads no tier: it reports nothing, so nothing
    tightens it, no frame of it is counted, and its one program stays."""
    from trino_tpu.ops import kernels

    eng = _memory_engine()
    ex = eng.executor
    runs, causes = _instrument(ex)
    ex.tracer.add_exporter(exporter := InMemorySpanExporter())
    plan = eng.plan(sql)
    (nid,) = [i for i, n in _node_ids(plan).items() if type(n).__name__ == "Join"]
    before = _counted()["Join"]
    rows = eng.execute_page(sql).to_pylist()
    assert f"{form} join_filter (" in "; ".join(kernels.describe(plan))
    loose, need = runs[0]
    assert nid in loose and nid not in need
    assert ex._learned_caps[plan][nid] == loose[nid] and _counted()["Join"] == before
    (wait,) = [s for s in _flat(exporter.snapshot()) if s.name == "device_wait"]
    assert f"Join#{nid}" not in wait.attributes["frames"]
    # whatever else tightened, the join's tier is in every later program's key, unchanged
    for _ in range(2):
        assert eng.execute_page(sql).to_pylist() == rows
    assert all(caps[nid] == loose[nid] for caps, _ in runs)
    assert ex.compile_service.builds <= 2 and "caps_tier" not in causes()


def test_top_n_keeps_its_floor(own_caps_file):
    eng = _memory_engine()
    runs, _ = _instrument(eng.executor)
    sql = "select k, v from a order by v desc, k limit 5"
    plan = eng.plan(sql)
    eng.execute_page(sql)
    (nid,) = [i for i, n in _node_ids(plan).items() if type(n).__name__ == "TopN"]
    assert eng.executor._learned_caps[plan][nid] == runs[0][0][nid] == 16384


def test_alternating_bindings_do_not_oscillate(own_caps_file):
    """One prepared plan, bindings small -> large -> small -> large: the
    first convergence tightens, the large binding grows the tier once, and
    nothing shrinks it again."""
    eng = _memory_engine()
    ex = eng.executor
    runs, causes = _instrument(ex)
    eng.execute("prepare p from select k, sum(v) from a where v < ? group by k")
    counts = {}
    builds = []
    for bound in (1, 60, 1, 60):
        rows = eng.execute(f"execute p using {bound}")
        counts.setdefault(bound, len(rows))
        assert len(rows) == counts[bound]
        builds.append(ex.compile_service.builds)
    assert counts[1] < 512 and counts[60] > 4096
    (plan,) = ex._learned_caps
    (nid,) = [i for i, n in _node_ids(plan).items() if type(n).__name__ == "Aggregate"]
    # loose; then tightened and, in the same call, grown for the large binding
    assert causes() == ["new_plan", "caps_tightened", "caps_tier"]
    assert builds == [1, 3, 3, 3]
    tiers = [caps[nid] for caps, _ in runs]
    assert tiers[1] == _pow2(2 * counts[1] + 1024) < tiers[0]
    assert tiers[2:] == [_pow2(counts[60])] * 3  # grown, and grown it stays
    assert ex._learned_caps[plan][nid] == tiers[-1]
    assert _counted()["Aggregate"] >= 1 and _counted()["TopN"] == 0


@pytest.mark.parametrize("scope", ["", "|spmd4"])
def test_new_executor_starts_at_the_tight_tier(scope, own_caps_file, monkeypatch):
    """Through exec/capcache.py and back: what one executor tightened, a new
    one (another task's, a restarted process's) compiles first and only."""
    import json

    if scope:
        eng = _tpch_engine(distributed=True, devices=jax.devices()[:4])
        sql = CASES["repartition_exchange_and_both_aggregates"][1]
    else:
        eng = _memory_engine()
        sql = CASES["semi_join"][1]
    old = eng.executor
    assert old._caps_scope == scope
    plan = eng.plan(sql)
    want = eng.execute_page(sql).to_pylist()
    learned = dict(old._learned_caps[plan])
    stored = json.loads(own_caps_file.read_text())
    assert [{int(k): v for k, v in e.items()} for e in stored.values()] == [learned]

    for restarted in (False, True):
        if restarted:  # the file is all a new process has: nothing is settled
            monkeypatch.setattr(capcache, "_mem", None)
        new = type(old)(eng.catalogs, eng.default_catalog,
                        *((old.devices,) if scope else ()))
        runs, causes = _instrument(new)
        assert new.execute(plan).to_pylist() == want
        assert [caps for caps, _ in runs] == [learned]
        assert new.compile_service.builds == 1 and causes() == ["new_plan"]
        assert new._learned_caps[plan] == learned
