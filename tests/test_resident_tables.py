"""Tables stay on the device between queries (ISSUE 25; exec/resident.py).

CPU, TPC-H SF0.01 and small memory tables.  The store belongs to the owner
of the executors (Coordinator, Worker, Engine): a served query's executor
dies, its columns do not.  What these tests hold it to: a second query
uploads nothing; a write through the engine is read back; a catalog
registered again under the same name starts empty; a cold start uploads
each column once; the store stays inside its budget; and everything that
cannot be asked for again, or comes from a connector that cannot vouch for
a version, is read per executor as before.
"""

import sys
import threading
import time
import types

import numpy as np
import pytest

from tests.tpch_queries import QUERIES
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.spi import CatalogManager, ColumnSchema
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.data.types import BIGINT
from trino_tpu.exec.compiler import LocalExecutor
from trino_tpu.exec.resident import ResidentStore
from trino_tpu.utils.tracing import InMemorySpanExporter

SCALE = 0.01


class CountingMemoryConnector(MemoryConnector):
    """Counts read_split calls; `delay_s` holds each read open so that
    threads that start together really overlap."""

    def __init__(self, delay_s: float = 0.0):
        super().__init__()
        self.reads = 0
        self.delay_s = delay_s
        self._count_lock = threading.Lock()

    def read_split(self, split, columns):
        with self._count_lock:
            self.reads += 1
        time.sleep(self.delay_s)
        return super().read_split(split, columns)


class UnversionedMemoryConnector(CountingMemoryConnector):
    """A source whose data can change behind the engine: no scan version."""

    def scan_version(self, table):
        return None


def _fill(conn, table="t", n=100, offset=0):
    conn.create_table(table, [ColumnSchema("k", BIGINT), ColumnSchema("v", BIGINT)])
    conn.insert(table, {
        "k": np.arange(n, dtype=np.int64) + offset,
        "v": (np.arange(n, dtype=np.int64) % 7) * 10,
    })
    return conn


# ------------------------------------------------------------------ served


@pytest.fixture(scope="module")
def served():
    """Coordinator + one worker, a client, and every span they finish."""
    from trino_tpu.client.client import StatementClient
    from trino_tpu.testing.runner import DistributedQueryRunner

    runner = DistributedQueryRunner(num_workers=1)
    runner.register_catalog("tpch", TpchConnector(SCALE))
    runner.register_catalog("memory", MemoryConnector())
    runner.start()
    coord = runner.coordinator
    coord.session.set("result_cache_enabled", "false")
    exporter = InMemorySpanExporter()
    coord.tracer.add_exporter(exporter)
    client = StatementClient(runner.client_url)

    def run(sql):
        """-> (rows, the attributes of the query's `scan_load` span)."""
        _cols, rows = client.execute(sql)
        qid = client.last_query_id
        deadline = time.time() + 5.0
        while time.time() < deadline:
            for root in exporter.snapshot():
                if root.name == "query" and root.attributes.get("query_id") == qid:
                    scan = root.find("scan_load")  # DDL and DML have none
                    attrs = dict(scan.attributes) if scan is not None else {}
                    attrs.pop("cpu_ms", None)  # the span's CPU clock: not this file's
                    return rows, attrs
            time.sleep(0.01)
        raise AssertionError(f"no query span for {qid}")

    try:
        yield types.SimpleNamespace(runner=runner, coord=coord, run=run)
    finally:
        runner.stop()


def test_second_served_q06_uploads_nothing(served):
    store = served.coord.resident
    rows1, first = served.run(QUERIES["q06"])
    resident = store.nbytes
    rows2, second = served.run(QUERIES["q06"])
    assert first["columns"] == second["columns"] == 4
    # (an earlier test of this module may have made lineitem resident)
    assert first["h2d_bytes"] > 0 or first["columns_cached"] == 4
    assert first["source"] in ("generated", "file", "resident")
    assert second == {"h2d_bytes": 0, "columns": 4, "columns_cached": 4,
                      "source": "resident", "host_prepare_ms": 0.0}
    assert rows1 == rows2 and store.nbytes == resident > 0
    # ... and the executor that uploaded them is gone: the next query's has
    # an empty dictionary of its own and still finds them
    assert store.hits >= 4


def test_write_through_the_engine_is_read_back_and_uploads_again(served):
    run = served.run
    run("create table memory.w (k bigint, v bigint)")
    run("insert into memory.w values (1, 10), (2, 20)")
    rows, attrs = run("select k, v from memory.w order by k")
    assert rows == [[1, 10], [2, 20]] and attrs["h2d_bytes"] > 0
    rows, attrs = run("select k, v from memory.w order by k")
    assert rows == [[1, 10], [2, 20]] and attrs["h2d_bytes"] == 0
    run("insert into memory.w values (3, 30)")
    rows, attrs = run("select k, v from memory.w order by k")
    assert rows == [[1, 10], [2, 20], [3, 30]]
    assert attrs["h2d_bytes"] > 0 and attrs["columns_cached"] == 0
    run("delete from memory.w where k = 2")
    rows, attrs = run("select k, v from memory.w order by k")
    assert rows == [[1, 10], [3, 30]]
    assert attrs["h2d_bytes"] > 0 and attrs["columns_cached"] == 0
    # the columns of the versions before are gone from the store: one entry
    store = served.coord.resident
    conn = served.coord.catalogs.get("memory")
    entries = [k for k in store._tables if k[0] == id(conn) and k[1] == "w"]
    assert len(entries) == 1
    assert store._tables[entries[0]].version == conn.generation


def test_catalog_registered_again_is_not_answered_from_the_old_one(served):
    # both connectors have seen the same number of writes: same generation,
    # same table name, same catalog name
    served.runner.register_catalog("swap", _fill(MemoryConnector(), n=5))
    rows, attrs = served.run("select sum(k), count(*) from swap.t")
    assert rows == [[10, 5]] and attrs["h2d_bytes"] > 0
    served.runner.register_catalog("swap", _fill(MemoryConnector(), n=5, offset=100))
    rows, attrs = served.run("select sum(k), count(*) from swap.t")
    assert rows == [[510, 5]]
    assert attrs["h2d_bytes"] > 0 and attrs["columns_cached"] == 0


def test_connector_without_a_scan_version_is_read_per_executor(served):
    conn = _fill(UnversionedMemoryConnector())
    served.runner.register_catalog("files", conn)
    store = served.coord.resident
    before = (store.nbytes, store.hits, store.misses)
    for _ in range(2):
        rows, attrs = served.run("select sum(v) from files.t")
        assert rows == [[int(((np.arange(100) % 7) * 10).sum())]]
        assert attrs["h2d_bytes"] > 0 and attrs["columns_cached"] == 0
    assert conn.reads == 2
    assert (store.nbytes, store.hits, store.misses) == before


def test_worker_reports_resident_bytes_in_heartbeat_and_metrics(served):
    import json
    import urllib.request

    worker = served.runner.workers[0]
    with urllib.request.urlopen(f"{worker.url}/v1/info", timeout=10) as r:
        info = json.loads(r.read())
    assert info["resident_bytes"] == worker.resident.nbytes
    served.run(QUERIES["q06"])
    served.run(QUERIES["q06"])  # the second finds the columns: a hit
    text = served.coord.metrics_text()
    for name in ("trino_tpu_resident_columns_total", "trino_tpu_resident_bytes",
                 "trino_tpu_resident_evictions_total"):
        assert f"# HELP {name} " in text
    assert f"trino_tpu_resident_bytes {served.coord.resident.nbytes}" in text
    assert 'trino_tpu_resident_columns_total{result="hit"}' in text


def test_two_workers_hold_their_own_halves():
    from trino_tpu.testing.runner import DistributedQueryRunner

    runner = DistributedQueryRunner(num_workers=2)
    runner.register_catalog("tpch", TpchConnector(SCALE))
    runner.start()
    try:
        runner.coordinator.session.set("result_cache_enabled", "false")
        first = runner.query(QUERIES["q06"])
        splits = []
        for w in runner.workers:
            assert w.resident.nbytes > 0
            splits += [k[2] for k in w.resident._tables if k[1] == "lineitem"]
        assert sorted(splits) == [(0, 2), (1, 2)]
        misses = [w.resident.misses for w in runner.workers]
        assert runner.query(QUERIES["q06"]) == first
        assert [w.resident.misses for w in runner.workers] == misses
        assert all(w.resident.hits >= 4 for w in runner.workers)
    finally:
        runner.stop()


# --------------------------------------------------- the store, by itself


def _executor(catalogs, store, default="memory"):
    ex = LocalExecutor(catalogs, default)
    ex.resident = store
    return ex


def _plan(catalogs, sql, default="memory"):
    from trino_tpu.plan.optimizer import optimize
    from trino_tpu.runtime.session import SessionProperties
    from trino_tpu.plan.planner import Planner

    return optimize(Planner(catalogs, default).plan(sql), catalogs, SessionProperties())


def test_cold_threads_upload_each_column_once():
    """Sixteen executors start cold together on two tables: the first to ask
    for a table reads and uploads, the others wait for its columns."""
    conn = _fill(CountingMemoryConnector(delay_s=0.05))
    _fill(conn, "u", n=50)
    catalogs = CatalogManager()
    catalogs.register("memory", conn)
    store = ResidentStore()
    plans = [_plan(catalogs, "select sum(k), sum(v) from t"),
             _plan(catalogs, "select sum(k), sum(v) from u")]
    executors = [_executor(catalogs, store) for _ in range(16)]
    barrier = threading.Barrier(len(executors))
    results, errors = [None] * len(executors), []

    def work(i):
        try:
            barrier.wait(timeout=30)
            results[i] = executors[i].execute(plans[i % 2]).to_pylist()
        except Exception as e:  # reported below, with the thread's index
            errors.append((i, repr(e)))

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert not errors, errors
    assert results[0::2] == [[(4950, int(((np.arange(100) % 7) * 10).sum()))]] * 8
    assert results[1::2] == [[(1225, int(((np.arange(50) % 7) * 10).sum()))]] * 8
    assert conn.reads == 2  # one read per table, both columns in it
    assert sum(ex.columns_loaded for ex in executors) == 4
    assert sum(ex.h2d_bytes for ex in executors) == store.nbytes > 0
    assert (store.misses, store.hits) == (4, 28)
    assert store.nbytes == sum(t.nbytes for t in store._tables.values())


def test_past_the_budget_the_least_recently_used_table_goes():
    from trino_tpu.runtime.engine import Engine

    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(SCALE))
    store = engine.resident
    sql = {t: f"select count(*), sum({c}) from {t}" for t, c in
           (("nation", "n_nationkey"), ("region", "r_regionkey"),
            ("supplier", "s_suppkey"))}
    want = {t: engine.query(q) for t, q in sql.items()}
    by_table = {k[1]: t.nbytes for k, t in store._tables.items()}
    assert set(by_table) == set(sql) and store.evictions == 0
    # room for supplier and one of the small ones
    store.budget_bytes = by_table["supplier"] + max(by_table["nation"], by_table["region"])
    engine.query(sql["nation"])    # touch: region is now the least recent
    engine.query(sql["supplier"])
    assert store.evictions == 0    # hits put nothing in
    engine.query("select count(*), sum(s_nationkey) from supplier")  # one more column
    assert store.evictions >= 1 and store.nbytes <= store.budget_bytes
    assert "region" not in {k[1] for k in store._tables}
    # the long-lived executor let go of the evicted table's pages too
    engine.query(sql["nation"])
    assert not [k for k in engine.executor._table_pages if k[1] == "region"]
    for t, q in sql.items():       # a later query still answers right
        assert engine.query(q) == want[t]
        assert store.nbytes <= store.budget_bytes
    # a table larger than the whole budget is used and not kept
    store.budget_bytes = 16
    assert engine.query("select count(*) from customer where c_custkey > 0") == [(1500,)]
    assert store.nbytes == 0 and not store._tables


def test_dynamically_filtered_scan_leaves_the_store_untouched():
    from trino_tpu.exec.dynfilter import ScanFilter
    from trino_tpu.plan.nodes import TableScan
    from trino_tpu.exec.compiler import _node_ids

    catalogs = CatalogManager()
    catalogs.register("memory", _fill(MemoryConnector()))
    store = ResidentStore()
    ex = _executor(catalogs, store)
    plan = _plan(catalogs, "select count(*), sum(v) from t")
    scan_id = next(i for i, n in _node_ids(plan).items() if isinstance(n, TableScan))
    ex.scan_filters = {scan_id: (ScanFilter("v", 10, 20),)}
    assert ex.execute(plan).to_pylist() == [(29, 15 * 10 + 14 * 20)]
    assert ex.rows_pruned == 100 - 29
    assert (store.nbytes, store.misses, store.hits, len(store._tables)) == (0, 0, 0, 0)
    assert ex._table_cols  # this query's values are in the key: the executor's own


def test_split_pad_rows_task_leaves_the_store_untouched():
    catalogs = CatalogManager()
    catalogs.register("memory", _fill(MemoryConnector()))
    store = ResidentStore()
    ex = _executor(catalogs, store)
    ex.split_pad_rows = 256
    page = ex.execute(_plan(catalogs, "select count(*), sum(k) from t"))
    assert page.to_pylist() == [(100, 4950)]
    assert (store.nbytes, store.misses, len(store._tables)) == (0, 0, 0)
    assert all(col.capacity == 256 for col in ex._table_cols.values())


def test_revoke_path_keeps_its_slices_out_of_the_store():
    """Worker._execute_sliced runs a task's split range in padded sub-slices
    to release HBM between them: its executor reads per slice, as before."""
    from trino_tpu.runtime.worker import REVOKE_SPILL_PARTS, Worker

    conn = _fill(CountingMemoryConnector())
    catalogs = CatalogManager()
    catalogs.register("memory", conn)
    worker = Worker(catalogs, "memory")
    ex = _executor(catalogs, worker.resident)
    task = types.SimpleNamespace(canceled=False, progress=lambda: None)
    req = {"part": 0, "num_parts": 1, "output_kind": "gather", "out_parts": 1}
    fragment = _plan(catalogs, "select k from t where v = 10")
    _buffers, rows_out, _ops = worker._execute_sliced(ex, fragment, {}, req, task)
    assert rows_out == 15
    assert conn.reads == REVOKE_SPILL_PARTS and worker.resident.misses == 0
    assert worker.resident.nbytes == 0 and not worker.resident._tables


def test_out_of_core_executors_have_no_store():
    from trino_tpu.runtime.engine import Engine

    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(SCALE))
    engine.session.set("query_max_memory_bytes", "3000000")
    exact = lambda rows: [r[:6] + r[-1:] for r in rows]  # not the AVGs' last digits
    rows = engine.query(QUERIES["q01"])
    assert engine.last_spill.spill_files > 0
    assert engine.resident.nbytes == 0 and not engine.resident._tables
    engine.session.set("query_max_memory_bytes", "0")
    assert exact(engine.query(QUERIES["q01"])) == exact(rows)
    assert engine.resident.nbytes > 0


def test_executor_with_no_store_keeps_its_columns_to_itself():
    catalogs = CatalogManager()
    conn = _fill(CountingMemoryConnector())
    catalogs.register("memory", conn)
    plan = _plan(catalogs, "select sum(k) from t")
    for reads in (1, 2):
        ex = LocalExecutor(catalogs, "memory")
        assert ex.resident is None
        assert ex.execute(plan).to_pylist() == [(4950,)]
        assert ex.execute(plan).to_pylist() == [(4950,)]  # its own dictionary
        assert conn.reads == reads and len(ex._table_cols) == 1


def test_empty_table_keeps_its_live_row_count_in_the_store():
    catalogs = CatalogManager()
    conn = MemoryConnector()
    conn.create_table("e", [ColumnSchema("k", BIGINT)])
    catalogs.register("memory", conn)
    store = ResidentStore()
    plan = _plan(catalogs, "select count(*), sum(k) from e")
    for _ in range(2):  # the second executor finds the padded column, and that no row of it is live
        assert _executor(catalogs, store).execute(plan).to_pylist() == [(0, None)]
    assert (store.misses, store.hits) == (1, 1)


# ---------------------------------------------------------------- iceberg


def test_iceberg_commits_are_read_back_and_snapshots_key_as_they_are(tmp_path):
    pytest.importorskip("pyarrow")
    from trino_tpu.connectors.iceberg import IcebergConnector
    from trino_tpu.runtime.engine import Engine

    engine = Engine(default_catalog="iceberg")
    conn = IcebergConnector(str(tmp_path / "wh"))
    engine.register_catalog("iceberg", conn)
    store = engine.resident
    engine.execute("create table t (k bigint)")
    engine.execute("insert into t values (1)")          # snapshot 2
    assert engine.execute("select k from t order by k") == [(1,)]
    engine.execute("insert into t values (2), (3)")     # snapshot 3
    assert engine.execute("select k from t order by k") == [(1,), (2,), (3,)]
    misses = store.misses
    assert engine.execute("select k from t order by k") == [(1,), (2,), (3,)]
    assert store.misses == misses                        # resident now
    assert engine.execute('select k from "t@2" order by k') == [(1,)]
    # a commit straight through the connector: no engine hook fires
    conn.insert("t", {"k": np.asarray([4], dtype=np.int64)})
    assert engine.execute("select k from t order by k") == [(1,), (2,), (3,), (4,)]
    # a metadata table is computed per scan: never from the store
    assert conn.scan_version("t$snapshots") is None
    assert len(engine.execute('select snapshot_id from "t$snapshots"')) == 4
    # dropped and made again: snapshot ids restart, the data is new
    engine.execute("drop table t")
    engine.execute("create table t (k bigint)")
    engine.execute("insert into t values (7)")
    assert engine.execute("select k from t order by k") == [(7,)]
