"""Operator-level observability: EXPLAIN ANALYZE per-operator stats and
query event listeners (reference: OperatorStats/ExplainAnalyzeOperator,
EventListener SPI)."""

import pytest

from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.runtime.engine import Engine


@pytest.fixture(scope="module")
def engine():
    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    return eng


def test_explain_analyze_per_operator(engine):
    rows = engine.execute(
        "explain analyze select l_returnflag, count(*) from lineitem "
        "where l_quantity < 10 group by l_returnflag"
    )
    text = "\n".join(r[0] for r in rows)
    assert "TableScan" in text and "Aggregate" in text
    # every operator line carries a row count annotation
    assert text.count("[rows:") >= 3
    assert "slowest operator:" in text
    assert "ms" in text


def test_explain_analyze_rows_are_real(engine):
    rows = engine.execute("explain analyze select count(*) from lineitem")
    text = "\n".join(r[0] for r in rows)
    # the aggregate output is exactly one row
    assert "[rows: 1" in text


def test_event_listener(engine):
    events = []
    engine.add_event_listener(events.append)
    engine.query("select count(*) from orders")
    kinds = [e.kind for e in events]
    assert kinds == ["created", "completed"]
    assert events[1].rows == 1
    assert events[1].wall_s >= 0
    engine.events._listeners.clear()


def test_event_listener_failure_isolated(engine):
    """A broken listener must not break the query (reference semantics)."""

    def bad(_ev):
        raise RuntimeError("listener bug")

    engine.add_event_listener(bad)
    try:
        rows = engine.query("select count(*) from orders")
        assert rows[0][0] > 0
    finally:
        engine.events._listeners.clear()


def test_tracing_spans(engine):
    """Query execution emits a query span with planner/execute children
    (reference: OpenTelemetry spans, SqlQueryExecution.java:473)."""
    from trino_tpu.utils.tracing import InMemorySpanExporter

    exp = InMemorySpanExporter()
    engine.tracer.add_exporter(exp)
    try:
        engine.query("select count(*) from region")
        root = exp.traces[-1]
        assert root.name == "query"
        assert root.attributes.get("rows") == 1
        assert root.find("planner") is not None
        assert root.find("execute") is not None
        assert root.duration_ms >= root.find("planner").duration_ms
        d = root.to_export_dict()
        assert d["name"] == "query"
        assert [c["name"] for c in d["children"]] == ["planner", "execute", "to_rows"]
    finally:
        engine.tracer._exporters.clear()


def test_tracing_error_recorded(engine):
    from trino_tpu.utils.tracing import InMemorySpanExporter

    exp = InMemorySpanExporter()
    engine.tracer.add_exporter(exp)
    try:
        with pytest.raises(Exception):
            engine.query("select * from no_such_table")
        assert "error" in exp.traces[-1].attributes
    finally:
        engine.tracer._exporters.clear()
