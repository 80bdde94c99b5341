"""Profiling & query-history plane: the compile profiler's per-signature
ledger (utils/profiler.py), the phase ledger on the query state machine,
the bounded persistent history store (runtime/history.py) with its
/v1/query surface and post-expiry fallback, and the scripts an operator
reads them with (scripts/trace_dump.py, scripts/metrics_lint.py)."""

import importlib.util
import json
import os
import sys
import urllib.error
import urllib.request

import pytest

from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.runtime.history import QueryHistoryStore
from trino_tpu.runtime.statemachine import QueryStateMachine
from trino_tpu.testing import DistributedQueryRunner
from trino_tpu.utils.profiler import CompileProfiler

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------- history store


def test_history_ring_evicts_oldest_first():
    store = QueryHistoryStore(capacity=3)
    for i in range(5):
        store.record({"query_id": f"q{i}", "state": "FINISHED"})
    assert len(store) == 3
    assert store.get("q0") is None and store.get("q1") is None
    assert [r["query_id"] for r in store.list()] == ["q4", "q3", "q2"]


def test_history_merge_refreshes_ring_position():
    store = QueryHistoryStore(capacity=2)
    store.record({"query_id": "a", "state": "FINISHED"})
    store.record({"query_id": "b", "state": "FINISHED"})
    # merging 'a' makes it the freshest entry, so the next insert evicts 'b'
    store.record({"query_id": "a", "wall_s": 1.5})
    store.record({"query_id": "c", "state": "FAILED"})
    assert store.get("b") is None
    merged = store.get("a")
    assert merged["state"] == "FINISHED" and merged["wall_s"] == 1.5


def test_history_jsonl_restart_round_trip(tmp_path):
    path = str(tmp_path / "history.jsonl")
    store = QueryHistoryStore(capacity=10, path=path)
    store.record({"query_id": "q1", "state": "FINISHED", "wall_s": 0.5})
    store.record({"query_id": "q2", "state": "FAILED", "error": "boom"})
    store.record({"query_id": "q1", "rows": 42})  # later merge line
    with open(path, "a") as f:
        f.write('{"query_id": "torn')  # crash mid-append
    reborn = QueryHistoryStore(capacity=10, path=path)
    assert len(reborn) == 2
    q1 = reborn.get("q1")
    assert q1["state"] == "FINISHED" and q1["rows"] == 42
    assert reborn.get("q2")["error"] == "boom"


def test_history_list_filters_state_and_limit():
    store = QueryHistoryStore(capacity=10)
    for i in range(4):
        store.record({
            "query_id": f"q{i}",
            "state": "FAILED" if i % 2 else "FINISHED",
        })
    failed = store.list(state="failed")
    assert [r["query_id"] for r in failed] == ["q3", "q1"]
    assert len(store.list(limit=2)) == 2


def test_history_as_event_listener():
    from trino_tpu.runtime.events import QueryEvent

    store = QueryHistoryStore(capacity=10)
    store(QueryEvent(kind="created", query_id="q1", sql="select 1"))
    assert len(store) == 0  # only terminal events are recorded
    store(
        QueryEvent(
            kind="completed", query_id="q1", sql="select 1",
            wall_s=0.1, rows=1, cpu_ms=5.0,
        )
    )
    rec = store.get("q1")
    assert rec["state"] == "FINISHED" and rec["cpu_ms"] == 5.0


# ----------------------------------------------------------- phase ledger


def test_statemachine_phase_seconds():
    sm = QueryStateMachine("q")
    for s in ("PLANNING", "STARTING", "RUNNING", "FINISHING", "FINISHED"):
        sm.transition(s)
    phases = sm.phase_seconds()
    assert set(phases) == {
        "QUEUED", "PLANNING", "STARTING", "RUNNING", "FINISHING"
    }
    assert all(v >= 0.0 for v in phases.values())
    # terminal time does not accrue: the ledger sums to created->finished
    total = sum(phases.values())
    assert abs(total - (sm.finished_at - sm.created_at)) < 1e-6


# ------------------------------------------------------- compile profiler


def test_compile_profiler_hit_miss_counters():
    prof = CompileProfiler()
    prof.record_compile("sigA", 0.2, "miss", {"flops": 100.0})
    prof.record_compile("sigA", 0.05, "hit")
    prof.record_compile("sigB", 0.01, "uncached")
    prof.record_execute("sigA", 0.003)
    counts = prof.cache_counts()
    assert counts == {"hit": 1, "miss": 1, "uncached": 1}
    snap = prof.snapshot("sigA")
    assert snap["compiles"] == 2
    assert snap["cache"] == {"hit": 1, "miss": 1, "uncached": 0}
    assert snap["executes"] == 1 and snap["execute_s"] > 0
    assert snap["flops"] == 100.0
    full = prof.snapshot()
    assert set(full) == {"sigA", "sigB"}
    prof.reset()
    assert prof.snapshot() == {}


def test_signature_of_is_stable_and_distinguishes_caps():
    from trino_tpu.utils.profiler import signature_of

    eng_plan = _tiny_plan()
    a = signature_of(eng_plan, {1: 64})
    b = signature_of(eng_plan, {1: 64})
    c = signature_of(eng_plan, {1: 128})
    assert a == b  # deterministic across calls (sha1, not salted hash())
    assert a != c  # overflow-retry tier gets its own signature


def _tiny_plan():
    from trino_tpu.runtime.engine import Engine

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    return eng.plan("select count(*) from region")


def test_local_executor_records_compile_events():
    from trino_tpu.runtime.engine import Engine

    from trino_tpu.exec.compilesvc import CompileService

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    # a service of its own: the process-global one may already hold this
    # program from another test of the same worker (a `joined` event
    # carries a wait, not a compile)
    eng.executor.compile_service = CompileService()
    assert eng.execute("select count(*) from region") == [(5,)]
    ev = eng.executor.compile_events
    assert ev, "cold execute must record a compile event"
    assert ev[0]["signature"] and ev[0]["compile_s"] > 0
    assert ev[0]["cache"] in ("hit", "miss", "uncached")
    # second run may recompile once (adaptive compaction tightens tiers);
    # after that the jit cache is steady — no new compile events
    eng.execute("select count(*) from region")
    n = len(eng.executor.compile_events)
    eng.execute("select count(*) from region")
    assert len(eng.executor.compile_events) == n
    assert eng.executor.last_compile_ms == 0.0
    assert eng.executor.last_execute_ms > 0.0


def test_local_explain_analyze_profile_footer():
    from trino_tpu.runtime.engine import Engine

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    rows = eng.execute("explain analyze select count(*) from nation")
    text = "\n".join(r[0] for r in rows)
    assert "-- phases: compile" in text
    assert "-- compile: " in text  # named jit signature attribution


# ------------------------------------------------------------ trace dump


def _spans():
    """A coordinator's `query` root with a `schedule` child, a worker's
    `task` root whose parent is that child, and a root of another trace."""
    query = {"trace_id": "t1", "span_id": "q", "parent_id": None, "name": "query",
             "duration_ms": 100.0, "attributes": {"query_id": "q_1"},
             "children": [{"trace_id": "t1", "span_id": "s", "parent_id": "q",
                           "name": "schedule", "duration_ms": 40.0}]}
    task = {"trace_id": "t1", "span_id": "k", "parent_id": "s", "name": "task",
            "duration_ms": 30.0, "attributes": {"task_id": "q_1.0.0", "worker": "w1"}}
    other = {"trace_id": "t2", "span_id": "o", "parent_id": "gone", "name": "query",
             "duration_ms": 5.0}
    return query, task, other


def test_trace_dump_nests_a_remote_span_under_its_parent():
    dump = _load_script("trace_dump")
    query, task, other = _spans()
    traces = dump.stitch([task, query, other])  # the worker's line came first
    assert set(traces) == {"t1", "t2"}
    (root,) = traces["t1"]["spans"]
    assert root is query and traces["t1"]["total_ms"] == 100.0
    (schedule,) = root["children"]
    assert schedule["children"] == [task]  # under the nested child, by parent_id
    # a parent no exported line holds: the span stays a root of its trace
    assert traces["t2"]["spans"] == [other]


def test_trace_dump_skips_torn_lines(tmp_path):
    dump = _load_script("trace_dump")
    query, task, _other = _spans()
    path = tmp_path / "trace.jsonl"
    path.write_text(json.dumps(query) + "\n\n" + json.dumps(task)[:25] + "\n"
                    + json.dumps(task) + "\n")
    assert dump.load_roots(str(path)) == [query, task]


def test_trace_dump_prints_one_flame_a_trace(tmp_path, capsys, monkeypatch):
    dump = _load_script("trace_dump")
    path = tmp_path / "trace.jsonl"
    path.write_text("".join(json.dumps(s) + "\n" for s in _spans()))
    monkeypatch.setattr(sys, "argv", ["trace_dump.py", str(path), "--trace", "t1"])
    assert dump.main() == 0
    out = capsys.readouterr().out
    assert "=== trace t1  (1 root span(s), 100.0 ms wall)" in out and "t2" not in out
    lines = [l for l in out.splitlines() if " ms " in l and "===" not in l]
    # query, then schedule under it, then the worker's task under schedule
    assert [l.split("# ")[-1].strip() for l in lines] == [
        "query q_1", "schedule", "task q_1.0.0 w1"]
    assert [l.index(" ms ") for l in lines] == [10, 12, 14]  # two columns a level
    assert " 30.0%" in lines[2]  # of the trace's wall, not of its parent
    monkeypatch.setattr(sys, "argv", ["trace_dump.py", str(path), "--trace", "none"])
    assert dump.main() == 1
    assert "no traces found" in capsys.readouterr().err


# ----------------------------------------------------------- metrics lint


def test_metrics_lint_brace_expansion_and_help(tmp_path):
    mlint = _load_script("metrics_lint")
    assert sorted(mlint._expand("trino_tpu_x_{a,b}_total")) == [
        "trino_tpu_x_a_total", "trino_tpu_x_b_total",
    ]
    assert mlint._expand('trino_tpu_y_total{state="x"}') == ["trino_tpu_y_total"]
    readme = tmp_path / "README.md"
    readme.write_text("uses `trino_tpu_a_total` and `trino_tpu_{b,c}_total`")
    good = tmp_path / "good.prom"
    good.write_text(
        "# HELP trino_tpu_a_total a\n# TYPE trino_tpu_a_total counter\n"
        "# HELP trino_tpu_b_total b\n# TYPE trino_tpu_b_total counter\n"
        "# HELP trino_tpu_c_total c\n# TYPE trino_tpu_c_total counter\n"
    )
    assert mlint.lint([str(good)], str(readme)) == []
    bad = tmp_path / "bad.prom"
    bad.write_text(
        "# HELP trino_tpu_a_total\n# TYPE trino_tpu_a_total counter\n"
        "# HELP trino_tpu_b_total b\n# TYPE trino_tpu_b_total counter\n"
    )
    failures = mlint.lint([str(bad)], str(readme))
    assert any("no HELP" in f for f in failures)
    assert any("trino_tpu_c_total" in f for f in failures)


def test_metrics_lint_exit_codes_and_undocumented_family(tmp_path, capsys):
    """`main`'s contract: 0 and an ok line over a clean scrape, 2 and a FAIL
    line a problem — here the third class, a family the README omits."""
    mlint = _load_script("metrics_lint")
    readme = tmp_path / "README.md"
    readme.write_text('counts `trino_tpu_a_total{state="x"}`')
    a = "# HELP trino_tpu_a_total a\n# TYPE trino_tpu_a_total counter\n"
    good = tmp_path / "good.prom"
    good.write_text(a + "# HELP process_cpu_seconds_total cpu\n")
    assert mlint.main(["--readme", str(readme), str(good)]) == 0
    assert "metrics_lint: ok (1 target(s))" in capsys.readouterr().out
    extra = tmp_path / "extra.prom"
    extra.write_text(a + "# HELP trino_tpu_z_total z\n# TYPE trino_tpu_z_total counter\n")
    assert mlint.main(["--readme", str(readme), str(good), str(extra)]) == 2
    out = capsys.readouterr().out
    assert "FAIL trino_tpu_z_total is exposed but the README does not document it" in out
    assert "metrics_lint: 1 problem(s)" in out
    assert mlint.main(["--readme", str(readme), str(tmp_path / "missing.prom")]) == 2


# ------------------------------------------------- cluster integration


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("hist") / "history.jsonl")
    runner = DistributedQueryRunner(num_workers=2)
    runner.register_catalog("tpch", TpchConnector(0.01))
    # the coordinator is built by start(); route its history to a temp file
    os.environ["TRINO_TPU_HISTORY_FILE"] = path
    try:
        runner.start()
    finally:
        os.environ.pop("TRINO_TPU_HISTORY_FILE", None)
    runner.history_path = path
    yield runner
    runner.stop()


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def test_query_listing_and_history_fallback_after_expiry(cluster):
    cluster.query("select count(*) from orders")
    coord = cluster.coordinator
    with coord._lock:
        qid = list(coord.queries)[-1]

    listing = _get(f"{coord.url}/v1/query")["queries"]
    assert any(q["query_id"] == qid and q["source"] == "live" for q in listing)

    info = _get(f"{coord.url}/v1/query/{qid}")
    ledger = info.get("phase_ledger") or {}
    assert "compiling_ms" in ledger and "executing_ms" in ledger
    assert ledger.get("queued_ms", -1.0) >= 0.0
    assert info.get("compile_signatures"), "expected named jit signatures"

    # expiry drops the live record; the endpoint falls back to history
    coord.expire_query(qid)
    with coord._lock:
        assert qid not in coord.queries
    info2 = _get(f"{coord.url}/v1/query/{qid}")
    assert info2["expired"] and info2["state"] == "FINISHED"
    assert info2.get("phase_ledger")
    listing2 = _get(f"{coord.url}/v1/query")["queries"]
    assert any(
        q["query_id"] == qid and q["source"] == "history" for q in listing2
    )
    # unknown ids still 404
    with pytest.raises(urllib.error.HTTPError):
        _get(f"{coord.url}/v1/query/q_nonexistent")


def test_history_survives_coordinator_restart(cluster):
    from trino_tpu.runtime.coordinator import Coordinator

    cluster.query("select count(*) from region")
    coord = cluster.coordinator
    with coord._lock:
        qid = list(coord.queries)[-1]
    # a second coordinator over the same JSONL replays the ring on boot
    reborn = Coordinator(
        coord.catalogs, coord.default_catalog,
        history_path=cluster.history_path,
    )
    rec = reborn.history.get(qid)
    assert rec is not None and rec["state"] == "FINISHED"
    assert rec.get("phase_ledger")


def test_distributed_analyze_shows_ledger_and_signatures(cluster):
    rows = cluster.query(
        "explain analyze select count(*) from lineitem where l_quantity < 10"
    )
    text = "\n".join(r[0] for r in rows)
    assert "-- phases: " in text
    assert "compiling" in text and "exchange_wait" in text
    assert "-- compile: " in text  # per-signature attribution


def test_ui_history_table(cluster):
    cluster.query("select count(*) from nation")
    with urllib.request.urlopen(f"{cluster.coordinator.url}/ui") as r:
        page = r.read().decode()
    assert "history (" in page
