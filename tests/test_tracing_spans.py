"""The spans inside a query and the names on device ops (ISSUE 24).

CPU, TPC-H SF0.01.  (a) a served text query leaves every span of the table in
PERF.md section 3, nested as it says and tied by `query_id`; (b) `scan_load`
counts the bytes it puts on the device; (c) a `compile` span whose cause is
not `joined` appears exactly when the compile service builds; (d)
`Tracer.record` and handler-thread roots; (e) an executor without a tracer;
(f) plan-node scopes and kernel names in the lowered fragment, and nothing
else changed by them; (g) the benchmark's span readers against a recorded
fixture; (h) the thread's CPU clock on every span (ISSUE 37); (i)
benchmarks/hostpath.py: a request cut into pieces that add up to it, by hand
and against a slice recorded on the chip.
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


def _attrs(span):
    """A span's attributes without its CPU clock."""
    return {k: v for k, v in span.attributes.items() if k != "cpu_ms"}


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
        coord.tracer._cpu = time.thread_time  # whatever `_cpu_clock` made of a busy machine
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
        "queued", "parse", "planner", "schedule", "root_fragment", "to_rows",
        "query_info", "cleanup", "commit"]
    root_fragment = query.find("root_fragment")
    assert [c.name for c in root_fragment.children] == [
        "scan_load", "size", "program_lookup", "compile", "dispatch",
        "device_wait", "settle", "operator_stats"]
    assert _attrs(query.find("parse")) == {
        "sql_bytes": len(QUERIES["q06"]), "statement": "QueryStmt"}
    assert _attrs(query.find("planner")) == {"preplanned": False, "fragments": 1}
    assert _attrs(query.find("schedule")) == {"stages": 0, "tasks": 0}
    assert _attrs(root_fragment) == {"fragment_id": 0}
    # a new executor has learned nothing: its capacities come from the
    # capacity cache or from statistics, its own program cache misses, and
    # what the run converged on is kept
    assert _attrs(query.find("size"))["source"] in ("cached", "initial")
    assert _attrs(query.find("program_lookup")) == {"jit_cache": "miss"}
    assert _attrs(query.find("settle")) == {"stored": True}
    assert _attrs(query.find("cleanup")) == {"tasks": 0}
    assert _attrs(query.find("commit")) == {"state": "FINISHED"}
    to_rows = query.find("to_rows")
    assert to_rows.attributes["rows"] == 1
    # the live mask, and the sum's data, validity and upper limb
    assert to_rows.attributes["d2h_arrays"] == 4
    assert 0.0 < to_rows.attributes["fetch_ms"] <= to_rows.duration_ms
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
            if child.name == "compile":
                # recorded from the miss, which `program_lookup` found: the
                # one child that begins inside its elder sibling
                lookup = span.find("program_lookup")
                assert lookup.start_s <= child.start_s <= lookup.end_s <= child.end_s
            else:
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
    assert 0.0 < post.attributes["admit_ms"] <= post.duration_ms
    assert 0.0 <= post.attributes["cpu_ms"] <= post.duration_ms + 1.0
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
    # the answer's way out, after the hold: `json.dumps`, then the socket
    assert attrs["encode_ms"] > 0.0 and attrs["write_ms"] > 0.0
    after_hold_ms = last[0].duration_ms - attrs["held_ms"]
    assert attrs["encode_ms"] + attrs["write_ms"] <= after_hold_ms + 1e-6
    assert 0.0 <= attrs["cpu_ms"] <= last[0].duration_ms + 1.0


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
        "queued", "parse", "planner", "bind", "root_fragment", "to_rows", "commit"]
    assert [c.name for c in first.find("root_fragment").children] == [
        "scan_load", "size", "program_lookup", "compile", "dispatch",
        "device_wait", "settle"]
    assert _attrs(first.find("planner")) == {"preplanned": False, "plan_cache": "miss"}
    assert _attrs(first.find("program_lookup")) == {"jit_cache": "miss"}
    assert _attrs(first.find("size"))["source"] in ("cached", "initial")
    # a new binding: the cached plan, the compiled program, the same kernel,
    # and an executor that is kept: its own capacities, its own program
    assert _attrs(second.find("planner")) == {"preplanned": True, "plan_cache": "hit"}
    assert [c.name for c in second.children] == [c.name for c in first.children]
    assert [c.name for c in second.find("root_fragment").children] == [
        "scan_load", "size", "program_lookup", "dispatch", "device_wait"]
    assert _attrs(second.find("size")) == {"source": "learned"}
    assert _attrs(second.find("program_lookup")) == {"jit_cache": "hit"}
    for q in queries:
        assert _attrs(q.find("parse")) == {
            "sql_bytes": len("EXECUTE q06 USING DATE '1994-01-01', DATE '1995-01-01', "
                             "0.05, 0.07, 24"), "statement": "ExecuteStmt"}
        assert _attrs(q.find("bind")) == {"params": 5}
        assert _attrs(q.find("commit")) == {"state": "FINISHED"}
        assert q.find("to_rows").attributes["d2h_arrays"] == 4
        assert q.find("to_rows").attributes["fetch_ms"] > 0.0
        for span, _depth in _flat(q):  # every span inside its parent
            for child in span.children:
                if child.name != "queued":
                    assert span.start_s <= child.start_s <= child.end_s <= span.end_s
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
    first.attributes.pop("cpu_ms"), second.attributes.pop("cpu_ms")
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
    # — the joins and keyed group-bys of q03, q12 and q18 sort (PR 45)
    kernels = {"q01": ["fused_scan"], "q06": ["fused_scan"], "q12": [],
               "q03": [], "q18": []}[query]
    for kernel in ("fused_scan", "hash_agg", "hash_join_probe"):
        assert (kernel in text) == (kernel in kernels), kernel

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


# ------------------------------------------- (h) the thread's CPU clock


def test_every_with_span_carries_the_threads_cpu_clock(served):
    seen = set()
    for root in served["all"]:
        for span, _depth in _flat(root):
            if span.name == "queued":  # begins on the handler's thread, ends on the query's
                assert "cpu_ms" not in span.attributes
                continue
            seen.add(span.name)
            # at most its wall, and a clock tick
            assert 0.0 <= span.attributes["cpu_ms"] <= span.duration_ms + 1.0, span.name
    assert {"http.post", "http.get", "query", "finalize", "parse", "planner", "schedule",
            "root_fragment", "scan_load", "size", "program_lookup", "compile", "dispatch",
            "device_wait", "settle", "operator_stats", "to_rows", "query_info", "cleanup",
            "commit"} <= seen


def test_cpu_clock_tells_a_busy_thread_from_a_waiting_one():
    tracer = Tracer()
    tracer._cpu = time.thread_time
    with tracer.span("busy") as busy:
        until = time.perf_counter() + 0.05
        while time.perf_counter() < until:
            pass
    with tracer.span("asleep") as asleep:
        time.sleep(0.05)
    # other tests share the machine: a busy thread may be held off its core
    assert 0.5 * busy.duration_ms <= busy.attributes["cpu_ms"] <= busy.duration_ms + 1.0
    assert asleep.duration_ms >= 50.0 and asleep.attributes["cpu_ms"] < 5.0


def test_record_takes_the_recording_threads_cpu_start():
    """The upper bound holds every time.  The lower one (half of a 20 ms spin
    on the thread's CPU clock) is asked up to five times: under six xdist
    workers that clock ticks every 10 ms and the thread may be held off its
    core, so one spin can read a single tick."""
    tracer = Tracer()
    tracer._cpu = time.thread_time
    for _attempt in range(5):
        with tracer.span("query") as query:
            t0, cpu0 = time.perf_counter(), tracer.cpu_now()
            while time.perf_counter() < t0 + 0.02:
                pass
            worked = tracer.record("http.get", t0, cpu_start_s=cpu0, served=True)
            plain = tracer.record("queued", t0)
        assert query.children == [worked, plain]
        assert worked.attributes["served"] is True
        assert worked.attributes["cpu_ms"] <= worked.duration_ms + 1.0
        assert "cpu_ms" not in plain.attributes  # no start, no clock
        if 0.5 * worked.duration_ms <= worked.attributes["cpu_ms"]:
            break
    else:
        raise AssertionError(f"five spins, none read half its time on the CPU clock: {worked}")


def test_a_host_whose_cpu_clock_is_unfit_gets_no_cpu_ms(monkeypatch):
    """The chip tool's machines serve `thread_time()` in 6 us from a counter
    that ticks every 10 ms (PERF.md, PR 37): there the tracer reads no CPU
    clock at all, and a span is what it was."""
    from trino_tpu.utils import tracing

    assert tracing._cpu_clock() in (None, time.thread_time)  # measured once, kept
    slow = iter(range(10 ** 6))  # a clock that never moves while the thread spins
    monkeypatch.setattr(tracing.time, "thread_time", lambda: next(slow) * 0.0)
    assert tracing._cpu_clock.__wrapped__() is None
    monkeypatch.undo()

    def dear():  # a clock that is right and costs 20 us a read
        until = time.perf_counter() + 20e-6
        while time.perf_counter() < until:
            pass
        return time.process_time()

    monkeypatch.setattr(tracing.time, "thread_time", dear)
    assert tracing._cpu_clock.__wrapped__() is None
    monkeypatch.undo()

    tracer = Tracer()
    tracer._cpu = None
    assert tracer.cpu_now() is None
    with tracer.span("query") as query:
        with tracer.span("planner"):
            pass
        recorded = tracer.record("http.get", 1.0, 2.0, cpu_start_s=tracer.cpu_now(), served=True)
    assert query.attributes == {} and query.children[0].attributes == {}
    assert recorded.attributes == {"served": True}


# ---------------------------------------- (i) a request cut into its pieces


@pytest.fixture(scope="module")
def hostpath():
    bench = os.path.join(REPO, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import hostpath
    import loader
    import tracered

    return hostpath, loader, tracered


def _span(name, t0, t1, depth=0, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "depth": depth, "attrs": attrs}


def _by_hand():
    """One request of three threads, in seconds: the POST's handler, the
    query's thread, and a poll that came while the query was queued and was
    held over all of it; the device busy for 2 ms of `device_wait`'s 4."""
    spans = [
        _span("http.post", 10.001, 10.004, query_id="q1", cpu_ms=0.6, admit_ms=0.9),
        _span("query", 10.005, 10.0165, query_id="q1", cpu_ms=5.0),
        _span("queued", 10.003, 10.005, 1),
        _span("parse", 10.005, 10.006, 1, cpu_ms=1.0),
        _span("root_fragment", 10.006, 10.014, 1, cpu_ms=3.0),
        _span("program_lookup", 10.0065, 10.0075, 2, cpu_ms=1.0),
        _span("compile", 10.007, 10.008, 2, cpu_ms=0.5),  # from the miss, inside the lookup
        _span("dispatch", 10.008, 10.009, 2, cpu_ms=0.5),
        _span("device_wait", 10.009, 10.013, 2, cpu_ms=0.4),
        _span("to_rows", 10.014, 10.0148, 1, cpu_ms=0.5, fetch_ms=0.6, d2h_arrays=22),
        _span("commit", 10.015, 10.016, 1, cpu_ms=0.1),  # the terminal transition inside it
        _span("finalize", 10.0165, 10.0170, query_id="q1", cpu_ms=0.5),
        # terminal at 10.0158, the handler runs again at 10.0163, then 1.2 ms
        # of its own, 1.0 ms of json.dumps and 0.5 ms of socket
        _span("http.get", 10.0045, 10.019, query_id="q1", served=True, cpu_ms=1.35,
              held_ms=11.8, since_finished_ms=0.5, encode_ms=1.0, write_ms=0.5),
    ]
    records = [{"template": "q01", "stream": 0, "t0": 10.0, "t1": 10.02,
                "query_id": "q1", "error": None}]
    trace = {"busy": [(10.010, 10.012)], "slice": (9.9, 10.1)}
    return {"spans": spans, "records": records, "trace": trace}


def test_a_request_by_hand_is_cut_into_exact_pieces(hostpath, capsys):
    hp, loader, _tracered = hostpath
    ctx = _by_hand()
    rep = hp.report(ctx)
    ((rec, pieces, inside),) = rep["requests"]
    want = {  # label -> (wall ms, cpu ms)
        "client": (2.0, None),              # before the POST, after the answer
        "http.post (self)": (2.0, 0.4),     # until `queued` begins
        "queued": (2.0, None),
        "parse": (1.0, 1.0),
        "root_fragment (self)": (1.5, 0.6),
        "program_lookup": (1.0, 1.0),       # keeps what `compile` lies over
        "compile": (0.5, 0.25),
        "dispatch": (1.0, 0.5),
        "device": (2.0, 0.2),
        "device_wait": (2.0, 0.2),
        "to_rows": (0.8, 0.5),
        "query (self)": (0.2, 0.4 * 0.2 / 0.7),
        "commit": (0.8, 0.08),              # up to the terminal transition
        "http.get (wake)": (0.5, 0.0),
        "http.get (self)": (1.2, 0.6),
        "http.get encode": (1.0, 0.5),
        "http.get write": (0.5, 0.25),
    }
    assert pieces.keys() == want.keys()
    for label, (wall, cpu) in want.items():
        assert math.isclose(pieces[label][0] * 1e3, wall, abs_tol=1e-6), label
        if cpu is None:
            assert pieces[label][1] is None, label
        else:
            assert math.isclose(pieces[label][1] * 1e3, cpu, abs_tol=1e-6), label
    assert abs(sum(w for w, _c in pieces.values()) - (rec["t1"] - rec["t0"])) < 1e-9
    # at work in company: the handler's 0.7 ms while the query's thread
    # closes its span and finalizes
    assert math.isclose(inside["contended_ms"], 0.7, abs_tol=1e-6)
    assert {k: v for k, v in inside.items() if k != "contended_ms"} == {
        "to_rows.fetch_ms": 0.6, "to_rows.d2h_arrays": 22, "http.post.admit_ms": 0.9}
    # what the eight metrics make of it (one template: its median)
    for metric, value in {
            "to_rows_ms": 0.8, "executor_setup_ms": 3.0, "dispatch_ms": 1.0,
            "device_sync_ms": 2.0, "response_write_ms": 1.5, "client_outside_ms": 2.0,
            "host_contended_ms": 0.7,
            "host_path_unnamed_share": 100 * (0.2 + 1.5 + 2.0 + 1.2) / 20.0}.items():
        assert math.isclose(loader.layer_reader(metric)(ctx), value, abs_tol=1e-6), metric
    # printed once a run, whichever reader came first
    out = capsys.readouterr().out
    assert out.count("bench: host path by template (median ms a request, wall | cpu): ") == 1
    table = json.loads(out.split("wall | cpu): ")[1].splitlines()[0])
    assert table["q01"]["n"] == 1 and table["q01"]["client_ms"] == 20.0
    assert table["q01"]["pieces"]["device"] == [2.0, 0.2]
    walls = [wall for wall, _cpu in table["q01"]["pieces"].values()]
    assert walls == sorted(walls, reverse=True)  # the largest piece first
    assert out.count("bench: device idle by span (s of the slice): ") == 1


def test_an_idle_gap_is_split_where_the_spans_change(hostpath):
    """One idle gap of a second under `to_rows` for 0.3 s and `query_info`
    for 0.7 s: the midpoint rule hands the whole of it to the span over its
    middle, the partition cuts it at the boundary."""
    hp, _loader, tracered = hostpath
    spans = [
        _span("query", 4.0, 5.0, query_id="q1", cpu_ms=900.0),
        _span("to_rows", 4.0, 4.3, 1, cpu_ms=300.0),
        _span("query_info", 4.3, 5.0, 1, cpu_ms=600.0),
        _span("commit", 5.0, 5.0, 1, cpu_ms=0.0),  # this PR's spans: there is a host path
    ]
    records = [{"template": "q01", "stream": 0, "t0": 4.0, "t1": 5.0,
                "query_id": "q1", "error": None}]
    trace = {"busy": [(0.0, 4.0), (5.0, 10.0)], "slice": (0.0, 10.0), "ops": {}}
    rep = hp.report({"spans": spans, "records": records, "trace": trace})
    assert rep["idle"].keys() == {"q01 / to_rows", "q01 / query_info"}
    assert math.isclose(rep["idle"]["q01 / to_rows"], 0.3, abs_tol=1e-9)
    assert math.isclose(rep["idle"]["q01 / query_info"], 0.7, abs_tol=1e-9)
    whole = tracered.breakdown(trace, records, spans)["idle_gaps"]
    assert whole == [["bench:q01 / query_info", 1.0]]
    # two requests open at once share an idle instant equally
    both = spans + [_span("query", 4.0, 5.0, query_id="q2", cpu_ms=1.0)]
    records2 = records + [dict(records[0], template="q06", query_id="q2")]
    idle = hp.report({"spans": both, "records": records2, "trace": trace})["idle"]
    assert math.isclose(idle["q01 / to_rows"], 0.15, abs_tol=1e-9)
    assert math.isclose(idle["q06 / query"], 0.5, abs_tol=1e-9)


HOSTPATH_READERS = ["to_rows_ms", "executor_setup_ms", "dispatch_ms", "device_sync_ms",
                    "response_write_ms", "client_outside_ms", "host_contended_ms",
                    "host_path_unnamed_share"]
NEW_SPANS = {"parse", "bind", "size", "program_lookup", "settle", "cleanup", "commit"}
NEW_ATTRS = {"cpu_ms", "encode_ms", "write_ms", "admit_ms", "fetch_ms", "d2h_arrays"}


@pytest.fixture(scope="module")
def recorded_hostpath(hostpath):
    _hp, loader, _tracered = hostpath
    ctx = loader.load_json("testdata", "served_sf10_scan_hostpath.json")
    ctx["trace"]["busy"] = [tuple(b) for b in ctx["trace"]["busy"]]
    ctx["trace"]["slice"] = tuple(ctx["trace"]["slice"])
    return ctx, loader.load_json("testdata", "served_sf10_scan_hostpath.expected.json")


@pytest.mark.parametrize("metric", HOSTPATH_READERS)
def test_hostpath_reader_against_the_recorded_slice(hostpath, recorded_hostpath, metric):
    """~20 passes of q06 + q01 cut from a traced chip run of
    `served_sf10_scan` (ISSUE 37)."""
    hp, loader, _tracered = hostpath
    ctx, expected = recorded_hostpath
    value = loader.layer_reader(metric)(ctx)
    assert math.isclose(value, expected[metric], rel_tol=1e-6), (value, expected[metric])
    rep = hp.report(ctx)
    assert len(rep["requests"]) == expected["requests"]
    for rec, pieces, _inside in rep["requests"]:  # a partition, not a sample
        assert abs(sum(w for w, _c in pieces.values()) - (rec["t1"] - rec["t0"])) < 1e-6
    # a host without a fit CPU clock: the same pieces, no `cpu` beside them
    bare = dict(ctx, spans=[
        dict(s, attrs={k: v for k, v in s["attrs"].items() if k != "cpu_ms"})
        for s in ctx["spans"]])
    assert math.isclose(loader.layer_reader(metric)(bare), expected[metric], rel_tol=1e-6)
    assert all(cpu is None for _r, pieces, _in in hp.report(bare)["requests"]
               for _wall, cpu in pieces.values())
    # the parent commit's spans: none of the new ones, no CPU clock
    old = dict(ctx, spans=[
        dict(s, attrs={k: v for k, v in s["attrs"].items() if k not in NEW_ATTRS})
        for s in ctx["spans"] if s["name"] not in NEW_SPANS])
    assert loader.layer_reader(metric)(old) is None
    assert loader.layer_reader(metric)(dict(ctx, trace=None)) is None
