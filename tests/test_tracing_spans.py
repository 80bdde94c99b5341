"""The spans inside a query and the names on device ops (ISSUE 24).

CPU, TPC-H SF0.01.  (a) a served text query leaves every span of the table in
PERF.md section 3, nested as it says and tied by `query_id`; (b) `scan_load`
counts the bytes it puts on the device; (c) a `compile` span whose cause is
not `joined` appears exactly when the compile service builds; (d)
`Tracer.record` and handler-thread roots; (e) an executor without a tracer;
(f) plan-node scopes and kernel names in the lowered fragment, and nothing
else changed by them; (g) the benchmark's span readers against a recorded
fixture.
"""

import contextlib
import json
import math
import os
import re
import sys
import threading
import time
import urllib.request

import jax
import pytest

from tests.tpch_queries import QUERIES
from trino_tpu.utils.tracing import InMemorySpanExporter, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCALE = 0.01


def _flat(span, depth=0):
    yield span, depth
    for c in span.children:
        yield from _flat(c, depth + 1)


def _engine():
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(SCALE))
    exporter = InMemorySpanExporter()
    engine.tracer.add_exporter(exporter)
    return engine, exporter


# ------------------------------------------------------------ (a) served


@pytest.fixture(scope="module")
def served():
    """One q06 text through client -> coordinator (one worker): the roots
    its query id left behind, by name."""
    from trino_tpu.client.client import StatementClient
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.testing.runner import DistributedQueryRunner

    runner = DistributedQueryRunner(num_workers=1)
    runner.register_catalog("tpch", TpchConnector(SCALE))
    runner.start()
    try:
        coord = runner.coordinator
        coord.session.set("result_cache_enabled", "false")
        exporter = InMemorySpanExporter()
        coord.tracer.add_exporter(exporter)
        client = StatementClient(runner.client_url)
        _cols, rows = client.execute(QUERIES["q06"])
        qid = client.last_query_id
        deadline = time.time() + 5.0
        while time.time() < deadline:  # finalize and the last poll end later
            roots = [s for s in exporter.snapshot()
                     if s.attributes.get("query_id") == qid]
            names = [s.name for s in roots]
            if "finalize" in names and any(
                    s.name == "http.get" and s.attributes["served"] for s in roots):
                break
            time.sleep(0.01)
        with urllib.request.urlopen(f"{coord.url}/metrics", timeout=10) as r:
            metrics = r.read().decode()
        yield {"rows": rows, "qid": qid, "roots": roots,
               "all": exporter.snapshot(), "metrics": metrics,
               "client": client, "exporter": exporter}
    finally:
        runner.stop()


def test_served_query_leaves_the_four_kinds_of_root(served):
    assert len(served["rows"]) == 1
    names = sorted({s.name for s in served["roots"]})
    assert names == ["finalize", "http.get", "http.post", "query"]
    for s in served["roots"]:
        assert s.attributes["query_id"] == served["qid"]
        assert s.parent_id == ""


def test_served_query_tree_has_every_span_nested_as_documented(served):
    query = next(s for s in served["roots"] if s.name == "query")
    assert [c.name for c in query.children] == [
        "queued", "planner", "schedule", "root_fragment", "to_rows", "query_info"]
    root_fragment = query.find("root_fragment")
    assert [c.name for c in root_fragment.children] == [
        "scan_load", "compile", "dispatch", "device_wait", "operator_stats"]
    assert query.find("planner").attributes == {"preplanned": False, "fragments": 1}
    assert query.find("schedule").attributes == {"stages": 0, "tasks": 0}
    assert root_fragment.attributes == {"fragment_id": 0}
    assert query.find("to_rows").attributes["rows"] == 1
    compile_ = query.find("compile")
    assert compile_.attributes["signature"] == query.find("dispatch").attributes["signature"]
    assert compile_.attributes["cause"] in ("new_plan", "caps_tier", "new_avals", "joined")
    assert query.find("device_wait").attributes["d2h_bytes"] > 0


def test_served_children_lie_inside_their_parents(served):
    query = next(s for s in served["roots"] if s.name == "query")
    for span, _depth in _flat(query):
        assert span.trace_id == query.trace_id
        before = span.start_s
        for child in span.children:
            if child.name == "queued":
                continue
            assert span.start_s <= child.start_s <= child.end_s <= span.end_s, child.name
            assert child.start_s >= before, child.name  # in order, never overlapping
            before = child.end_s
    # `queued` began on the handler's thread, before `query` opened, and
    # ends where `query` starts
    queued = query.find("queued")
    post = next(s for s in served["roots"] if s.name == "http.post")
    assert post.start_s <= queued.start_s <= queued.end_s == query.start_s


def test_served_http_spans_say_what_the_metrics_read(served):
    post = next(s for s in served["roots"] if s.name == "http.post")
    assert post.attributes["body_bytes"] == len(QUERIES["q06"].encode())
    gets = [s for s in served["roots"] if s.name == "http.get"]
    last = [s for s in gets if s.attributes["served"]]
    assert len(last) == 1 and last[0].attributes["since_finished_ms"] >= 0.0
    assert last[0].attributes["body_bytes"] > 0
    query = next(s for s in served["roots"] if s.name == "query")
    # the answer lay finished from inside `query` to the instant the handler
    # stopped waiting for it (`held_ms` after the poll came in)
    attrs = last[0].attributes
    finished_s = last[0].start_s + attrs["held_ms"] / 1e3 - attrs["since_finished_ms"] / 1e3
    assert query.start_s < finished_s <= query.end_s


def test_served_query_is_taken_by_one_held_poll(served):
    """The client polls at once and the coordinator holds the poll: one GET a
    request, woken by the terminal transition, so the finished answer lies
    for the handler's switch-in and no longer."""
    gets = [s for s in served["roots"] if s.name == "http.get"]
    assert len(gets) == 1 and gets[0].attributes["served"]
    attrs = gets[0].attributes
    assert attrs["held_ms"] > 0.0 and attrs["since_finished_ms"] >= 0.0
    # the span starts at the handler's entry and so contains the hold
    assert (gets[0].end_s - gets[0].start_s) * 1e3 >= attrs["held_ms"]
    polls = {
        m.group(1): float(m.group(2)) for m in re.finditer(
            r'trino_tpu_statement_polls_total\{result="(\w+)"\} (\S+)',
            served["metrics"])
    }
    assert polls == {"held": 1.0}
    # a few more, so that one late wake-up on a shared machine proves nothing
    client = served["client"]
    lay = [attrs["since_finished_ms"]]
    for _ in range(5):
        client.execute(QUERIES["q06"])
        qid = client.last_query_id
        deadline = time.time() + 5.0
        while time.time() < deadline:  # the handler records after it sent
            polls = [s for s in served["exporter"].snapshot()
                     if s.name == "http.get" and s.attributes["query_id"] == qid]
            if polls:
                break
            time.sleep(0.01)
        assert len(polls) == 1 and polls[0].attributes["served"]
        lay.append(polls[0].attributes["since_finished_ms"])
    assert min(lay) >= 0.0 and min(lay) < 5.0, lay


def test_http_spans_never_land_in_another_threads_tree(served):
    for root in served["all"]:
        below = [s.name for s, depth in _flat(root) if depth]
        assert not [n for n in below if n.startswith("http.")], (root.name, below)
        if root.name.startswith("http."):
            assert not below


def test_prepared_request_leaves_the_spans_of_a_text_request():
    """EXECUTE of a client-held prepared statement through client ->
    coordinator: the fast path's `planner` says how the plan was come by,
    the executor's span is the coordinator's `root_fragment`, its `dispatch`
    names the fused scan with the bindings as kernel scalars, and the roots
    are a text request's."""
    from trino_tpu.client.client import StatementClient
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.ops import kernels
    from trino_tpu.testing.runner import DistributedQueryRunner

    runner = DistributedQueryRunner(num_workers=1)
    runner.register_catalog("tpch", TpchConnector(SCALE))
    runner.start()
    try:
        coord = runner.coordinator
        coord.session.set("pallas_interpret", "true")  # the kernel, on the CPU
        exporter = InMemorySpanExporter()
        coord.tracer.add_exporter(exporter)
        client = StatementClient(runner.client_url)
        client.prepared["q06"] = (
            "select sum(l_extendedprice * l_discount) as revenue from lineitem "
            "where l_shipdate >= ? and l_shipdate < ? "
            "and l_discount between ? and ? and l_quantity < ?")
        queries = []
        for using in ("DATE '1994-01-01', DATE '1995-01-01', 0.05, 0.07, 24",
                      "DATE '1996-01-01', DATE '1997-01-01', 0.02, 0.04, 25"):
            _cols, rows = client.execute("EXECUTE q06 USING " + using)
            assert len(rows) == 1
            qid = client.last_query_id
            deadline = time.time() + 5.0
            while time.time() < deadline:  # finalize and the poll end later
                roots = [s for s in exporter.snapshot()
                         if s.attributes.get("query_id") == qid]
                if {"finalize", "http.get"} <= {s.name for s in roots}:
                    break
                time.sleep(0.01)
            assert sorted({s.name for s in roots}) == [
                "finalize", "http.get", "http.post", "query"]
            queries.append(next(s for s in roots if s.name == "query"))
    finally:
        runner.stop()
        kernels.set_policy(kernels.KernelPolicy())  # the process's, not the session's
    first, second = queries
    assert [c.name for c in first.children] == [
        "queued", "planner", "root_fragment", "to_rows"]
    assert [c.name for c in first.find("root_fragment").children] == [
        "scan_load", "compile", "dispatch", "device_wait"]
    assert first.find("planner").attributes == {"preplanned": False, "plan_cache": "miss"}
    # a new binding: the cached plan, the compiled program, the same kernel
    assert second.find("planner").attributes == {"preplanned": True, "plan_cache": "hit"}
    assert [c.name for c in second.find("root_fragment").children] == [
        "scan_load", "dispatch", "device_wait"]
    for q in queries:
        kernels = q.find("dispatch").attributes["kernels"]
        assert re.fullmatch(
            r"pallas fused_pipeline \(5 filters 3 streams domain 1 scatter vpu "
            r"tile 128 operands 4 resident 4 params 5\)", kernels), kernels
        assert q.find("to_rows").attributes["rows"] == 1
    assert (first.find("dispatch").attributes["signature"]
            == second.find("dispatch").attributes["signature"])


# ------------------------------------------------- (b) scan_load's counter


def test_scan_load_counts_bytes_once():
    engine, exporter = _engine()
    engine.execute_page(QUERIES["q06"])
    engine.execute_page(QUERIES["q06"])
    first, second = [s.find("scan_load") for s in exporter.snapshot()
                     if s.name == "execute"]
    resident = engine.resident.nbytes  # the columns live in the engine's store
    assert not engine.executor._table_cols
    # where the columns came from, and the host's part of loading them
    # (read + code + narrow, the uploads left out)
    assert first.attributes.pop("source") in ("generated", "file")
    assert 0 < first.attributes.pop("host_prepare_ms") < 1e3 * (first.end_s - first.start_s)
    assert first.attributes == {"h2d_bytes": resident, "columns": 4, "columns_cached": 0}
    assert resident > 0
    assert second.attributes == {"h2d_bytes": 0, "columns": 4, "columns_cached": 4,
                                 "source": "resident", "host_prepare_ms": 0.0}


# ----------------------------------------------------- (c) compile's cause


def _compiles(exporter):
    return [s for root in exporter.snapshot() for s, _d in _flat(root)
            if s.name == "compile"]


def test_compile_span_appears_when_the_service_builds():
    from trino_tpu.exec.compiler import LocalExecutor
    from trino_tpu.exec.compilesvc import CompileService

    engine, exporter = _engine()
    service = CompileService()  # this test's own: nobody else builds in it
    engine.executor.compile_service = service
    sql = ("select n_regionkey, count(*) + 240924 from nation "
           "where n_nationkey < 23 group by n_regionkey")
    rows = engine.query(sql)
    built = service.builds
    spans = _compiles(exporter)
    assert built >= 1 and len(spans) == built
    assert spans[0].attributes["cause"] == "new_plan"
    assert all(s.attributes["cause"] in ("new_plan", "caps_tier") for s in spans)
    assert all(re.fullmatch(r"\w+\+\d+n#[0-9a-f]{6}@[0-9a-f]{4}", s.attributes["signature"])
               for s in spans)
    assert all(s.attributes["status"] == "ready" and s.attributes["compile_s"] > 0
               for s in spans)

    engine.query(sql)  # the executor's own cache answers: no miss, no span
    assert service.builds == built and len(_compiles(exporter)) == built

    # another executor misses its own cache and is handed the service's
    # program: a span, cause `joined`, and no build
    other = LocalExecutor(engine.catalogs, engine.default_catalog)
    other.compile_service, other.tracer = service, engine.tracer
    plan = engine.plan(sql)
    with engine.tracer.span("execute"):
        assert other.execute(plan).to_pylist() == rows
    joined = _compiles(exporter)[built:]
    assert [s.attributes["cause"] for s in joined] == ["joined"]
    assert joined[0].attributes["signature"] == spans[-1].attributes["signature"]
    assert service.builds == built

    # a capacity tier that overflows: the plan again at another tier
    learned = engine.executor._learned_caps[plan]
    engine.executor._learned_caps[plan] = {nid: 1 for nid in learned}
    before = len(_compiles(exporter))
    assert sorted(engine.query(sql)) == sorted(rows)
    tiers = _compiles(exporter)[before:]
    assert service.builds == built + len(tiers) and tiers
    assert {s.attributes["cause"] for s in tiers} == {"caps_tier"}


# ------------------------------------------------------ (d) Tracer.record


def test_record_adds_a_finished_child_with_the_given_times():
    tracer = Tracer()
    exporter = InMemorySpanExporter()
    tracer.add_exporter(exporter)
    with tracer.span("query", query_id="q1") as query:
        child = tracer.record("queued", 1.5, 2.5, why="test")
        open_end = tracer.record("schedule", 3.0)
    assert query.children == [child, open_end]
    assert (child.start_s, child.end_s, child.attributes) == (1.5, 2.5, {"why": "test"})
    assert child.duration_ms == 1000.0
    assert child.trace_id == query.trace_id and child.parent_id == query.span_id
    assert child.span_id and child.span_id != open_end.span_id
    assert open_end.end_s >= time.perf_counter() - 1.0  # None == now
    assert exporter.snapshot() == [query]  # children ride their root


def test_record_on_a_thread_without_a_span_exports_a_root():
    tracer = Tracer()
    exporter = InMemorySpanExporter()
    tracer.add_exporter(exporter)
    with tracer.span("query") as query:
        # a handler thread records while this thread's `query` is open
        t = threading.Thread(
            target=lambda: tracer.record("http.get", 1.0, 2.0, query_id="q1"))
        t.start()
        t.join()
        assert query.children == []
        (root,) = exporter.snapshot()
    assert root.name == "http.get" and root.parent_id == ""
    assert root.trace_id and root.trace_id != query.trace_id


# ------------------------------------------- (e) an executor with no tracer


def test_executor_without_a_tracer_opens_nothing():
    from trino_tpu.exec.compiler import LocalExecutor

    engine, _exporter = _engine()
    bare = LocalExecutor(engine.catalogs, engine.default_catalog)
    assert bare.tracer is None
    plan = engine.plan(QUERIES["q06"])
    with engine.tracer.span("outer") as outer:
        rows = bare.execute(plan).to_pylist()
    assert outer.children == []
    assert rows == engine.query(QUERIES["q06"])


# ------------------------------------------ (f) names in the lowered program


def _lower(engine, sql):
    """-> (plan, lowered text with debug info, scopes of the nodes that were
    emitted, capacity key, service key, rows)."""
    from trino_tpu.exec.capcache import _key as caps_key
    from trino_tpu.exec.compiler import _node_ids, _trace_plan
    from trino_tpu.exec.compilesvc import CompileService
    from trino_tpu.ops import kernels as _kernels
    from trino_tpu.plan.nodes import TableScan

    policy = _kernels.get_policy()
    try:
        engine._apply_compile_props()  # the session's kernel policy, as a statement sets it
        ex = engine.executor
        ex.compile_service = CompileService()  # no program from another run
        plan = engine.plan(sql)
        rows = ex.execute(plan).to_pylist()
        caps = dict(ex._learned_caps[plan])
        inputs = {
            str(i): ex.table_page(n.catalog, n.table, n.column_names,
                                  n.output_types, scan_id=i)
            for i, n in _node_ids(plan).items() if isinstance(n, TableScan)}
        emitted = []

        def call(pages):
            page, _required = _trace_plan(
                plan, pages, dict(caps),
                node_hook=lambda nid, node, _stage: emitted.append(
                    f"{type(node).__name__}#{nid}"))
            return page

        text = jax.jit(call).lower(inputs).as_text(debug_info=True)
        _cache_key, treedef, avals = ex._cache_key(plan, inputs, caps)
        return plan, text, emitted, caps_key(plan, inputs), (treedef, avals), rows
    finally:
        _kernels.set_policy(policy)


@pytest.mark.parametrize("query", ["q01", "q03", "q06", "q12", "q18"])
def test_lowered_fragment_names_its_nodes_and_kernels(query, monkeypatch):
    sql = QUERIES[query]
    if query == "q18":  # the validation value selects no order at SF0.01
        sql = sql.replace("> 300", "> 150")
    engine, _exporter = _engine()
    engine.session.set("pallas_interpret", "true")  # the kernels, on the CPU
    plan, text, emitted, caps_key, service_key, rows = _lower(engine, sql)
    assert emitted and rows
    found = set(re.findall(r"\b([A-Z][A-Za-z]*#\d+)\b", text))
    assert found == set(emitted)  # every node that was emitted, and no other
    # the kernels these fragments take at this scale, by their names (each
    # kernel's name on the chip's own lowering: tests/test_chip_compile.py)
    for kernel in {"q01": ["fused_scan"], "q06": ["fused_scan"], "q12": [],
                   "q03": ["hash_agg", "hash_join_probe"],
                   "q18": ["hash_agg", "hash_join_probe"]}[query]:
        assert kernel in text, kernel

    # the same with the scopes patched out: names only, nothing else moved
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare, _exporter = _engine()
    bare.session.set("pallas_interpret", "true")
    plan2, text2, emitted2, caps_key2, service_key2, rows2 = _lower(bare, sql)
    assert not re.findall(r"\b[A-Z][A-Za-z]*#\d+\b", text2)
    assert plan2 == plan and emitted2 == emitted
    assert caps_key2 == caps_key
    assert service_key2[1] == service_key[1]  # avals; treedefs hold dictionaries by identity
    assert rows2 == rows


# ------------------------------------- (g) the benchmark's readers, recorded

READERS = ["queue_wait_ms", "result_poll_wait_ms", "plan_ms", "scan_load_ms",
           "h2d_bytes_per_query", "root_fragment_host_ms", "bookkeeping_ms",
           "query_unattributed_share", "device_attributed_share"]


@pytest.fixture(scope="module")
def recorded():
    bench = os.path.join(REPO, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import loader

    ctx = loader.load_json("testdata", "served_q06_spans.json")
    ctx["trace"]["path"] = os.path.join(bench, "testdata", ctx["trace"]["path"])
    ctx["trace"]["busy"] = [tuple(b) for b in ctx["trace"]["busy"]]
    ctx["trace"]["slice"] = tuple(ctx["trace"]["slice"])
    return loader, ctx, loader.load_json("testdata", "served_q06_spans.expected.json")


@pytest.mark.parametrize("metric", READERS)
def test_reader_against_the_recorded_slice(recorded, metric, capsys):
    loader, ctx, expected = recorded
    value = loader.layer_reader(metric)(ctx)
    assert math.isclose(value, expected[metric], rel_tol=1e-6), (value, expected[metric])
    # a program without the spans (the parent commit) leaves nothing to read
    old = dict(ctx, spans=[s for s in ctx["spans"] if s["name"] == "query"])
    if metric != "device_attributed_share":
        assert loader.layer_reader(metric)(old) is None
    else:
        table = json.loads(capsys.readouterr().out.split(
            "bench: device time by operator: ")[1].splitlines()[0])
        want = {k: v / 1e12 for k, v in expected["device_time_by_operator_ps"].items()}
        assert table.keys() == want.keys()
        assert all(math.isclose(table[k], want[k], rel_tol=1e-6) for k in want)
        # a trace without names (PR 23's recording): busy, nothing attributed
        unnamed = dict(ctx, trace=dict(ctx["trace"], path=os.path.join(
            REPO, "benchmarks", "testdata", "served_q06_slice.xplane.pb")))
        assert loader.layer_reader(metric)(unnamed) == 0.0
    assert loader.layer_reader(metric)(dict(ctx, trace=None)) is None
