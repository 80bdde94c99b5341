"""DB-API 2.0 driver (the JDBC analogue) and faker connector tests."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def cluster(tpch_tiny):
    from trino_tpu.connectors.faker import FakerConnector
    from trino_tpu.connectors.spi import ColumnSchema
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.data.types import BIGINT, DATE, DOUBLE, VARCHAR
    from trino_tpu.testing import DistributedQueryRunner

    runner = DistributedQueryRunner(num_workers=2)
    runner.register_catalog("tpch", TpchConnector(0.01))
    faker = FakerConnector()
    faker.create_table(
        "events",
        [
            ColumnSchema("id", BIGINT),
            ColumnSchema("kind", VARCHAR),
            ColumnSchema("score", DOUBLE),
            ColumnSchema("day", DATE),
        ],
        rows=2000,
    )
    runner.register_catalog("faker", faker)
    runner.start()
    yield runner
    runner.stop()


def test_dbapi_basic(cluster):
    from trino_tpu.client.dbapi import connect

    with connect(cluster.coordinator.url) as conn:
        cur = conn.cursor()
        cur.execute("select n_name, n_regionkey from nation order by n_name limit 3")
        assert cur.rowcount == 3
        assert [d[0] for d in cur.description] == ["n_name", "n_regionkey"]
        rows = cur.fetchall()
        assert len(rows) == 3 and rows == sorted(rows)
        cur.execute("select count(*) from region")
        assert cur.fetchone() == (5,)
        assert cur.fetchone() is None


def test_dbapi_parameters_and_iteration(cluster):
    from trino_tpu.client.dbapi import connect

    conn = connect(cluster.coordinator.url)
    cur = conn.cursor()
    cur.execute(
        "select n_name from nation where n_regionkey = ? and n_name <> ?",
        (0, "doesn't-exist"),  # embedded quote exercises escaping
    )
    names = [r[0] for r in cur]
    assert len(names) == 5
    with pytest.raises(Exception):
        cur.execute("select * from nation where n_regionkey = ?", ())


def test_dbapi_errors(cluster):
    from trino_tpu.client.dbapi import DatabaseError, ProgrammingError, connect

    conn = connect(cluster.coordinator.url)
    cur = conn.cursor()
    with pytest.raises(DatabaseError):
        cur.execute("select nonexistent_col from nation")
    conn.close()
    with pytest.raises(ProgrammingError):
        conn.cursor()


def test_faker_deterministic_and_split_stable(cluster):
    from trino_tpu.connectors.faker import FakerConnector
    from trino_tpu.connectors.spi import ColumnSchema
    from trino_tpu.data.types import BIGINT

    conn = FakerConnector()
    conn.create_table("t", [ColumnSchema("x", BIGINT)], rows=100)
    whole = conn.read_split(conn.get_splits("t", 1)[0], ["x"])["x"]
    parts = [conn.read_split(s, ["x"])["x"] for s in conn.get_splits("t", 4)]
    assert np.array_equal(np.concatenate(parts), whole)
    again = FakerConnector()
    again.create_table("t", [ColumnSchema("x", BIGINT)], rows=100)
    assert np.array_equal(
        again.read_split(again.get_splits("t", 1)[0], ["x"])["x"], whole
    )


def test_faker_queries(cluster):
    rows = cluster.query("select count(*), count(distinct kind) from faker.events")
    assert rows[0][0] == 2000 and 1 < rows[0][1] <= 32  # vocab size
    rows = cluster.query(
        "select kind, count(*) c from faker.events group by kind order by c desc limit 3"
    )
    assert len(rows) == 3 and rows[0][1] >= rows[2][1]
    rows = cluster.query(
        "select count(*) from faker.events where day >= date '2021-01-01'"
    )
    assert 0 < rows[0][0] < 2000


def test_spooled_client_protocol(tmp_path):
    """SPOOLED result protocol (reference: server/protocol/spooling +
    client/spooling SegmentLoader): with a client spool configured and the
    client advertising support, results come back via on-disk segment URIs
    — the response carries no inline data, the coordinator drops the rows
    from RAM, and the client's segment ack deletes the files."""
    import glob
    import json as _json
    import urllib.request

    from trino_tpu.client.client import StatementClient
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.testing import DistributedQueryRunner

    runner = DistributedQueryRunner(num_workers=2)
    runner.register_catalog("tpch", TpchConnector(0.01))
    runner.start()
    try:
        runner.coordinator.session.set("client_spool_dir", str(tmp_path))
        sql = "select n_nationkey, n_name from nation order by n_nationkey"
        plain = StatementClient(runner.coordinator.url).execute(sql)
        cols, rows = StatementClient(
            runner.coordinator.url, spooled=True
        ).execute(sql)
        assert rows == plain[1]
        assert len(rows) == 25
        # inline protocol response for the spooled query had segments only
        qid = [
            q for q, rec in runner.coordinator.queries.items()
            if rec.get("segments") is not None
        ]
        assert qid, "no spooled query recorded"
        rec = runner.coordinator.queries[qid[0]]
        assert rec["result"] == []  # rows left coordinator RAM
        # acked segments were deleted from the spool dir
        assert glob.glob(str(tmp_path / f"{qid[0]}_seg*")) == []
    finally:
        runner.stop()


# ------------------------------------------------------------ the long poll
#
# A GET of an unfinished statement is held by the coordinator until the
# query's state machine turns terminal (or STATEMENT_MAX_WAIT_S passes), and
# the client polls at once.  Order is proven by events — a gate in the
# connector, an event where the handler begins to wait — and by lower bounds
# on the clock, never by upper ones: six xdist workers share the machine.


def _long_poll_cluster(journal_path=None):
    """-> (runner, conn): one worker, a `memory` catalog whose table `t` is
    read anew by every query (no scan version: never resident) and whose
    read blocks on `conn.gate`."""
    import threading

    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.connectors.spi import ColumnSchema
    from trino_tpu.data.types import BIGINT
    from trino_tpu.testing import DistributedQueryRunner

    class GatedConnector(MemoryConnector):
        def __init__(self):
            super().__init__()
            self.gate = threading.Event()
            self.entered = threading.Event()  # a read reached the gate
            self.fail_reads = False

        def scan_version(self, table):
            return None

        def read_split(self, split, columns):
            self.entered.set()
            assert self.gate.wait(timeout=120), "test gate never opened"
            if self.fail_reads:
                raise RuntimeError("gated read failed")
            return super().read_split(split, columns)

        def reset(self, open_gate: bool):
            self.entered.clear()
            self.fail_reads = False
            (self.gate.set if open_gate else self.gate.clear)()

    conn = GatedConnector()
    conn.create_table("t", [ColumnSchema("v", BIGINT)])
    conn.insert("t", {"v": np.arange(100, dtype=np.int64)})
    runner = DistributedQueryRunner(
        num_workers=1, default_catalog="memory", heartbeat_interval=0.2,
        journal_path=journal_path,
    )
    runner.register_catalog("memory", conn)
    runner.start()
    runner.coordinator.session.set("result_cache_enabled", "false")
    return runner, conn


LONG_POLL_SQL = "select sum(v) from t"
LONG_POLL_ROWS = [[sum(range(100))]]


@pytest.fixture(scope="module")
def long_poll():
    runner, conn = _long_poll_cluster()
    yield runner, conn
    conn.gate.set()
    runner.stop()


@pytest.fixture()
def hold_seen(monkeypatch):
    """An event set each time a handler begins to hold a statement GET; and
    the hold made long, so that a slow machine cannot turn it into a timeout."""
    import threading

    from trino_tpu.runtime import coordinator as coordinator_module
    from trino_tpu.runtime.statemachine import QueryStateMachine

    monkeypatch.setattr(coordinator_module, "STATEMENT_MAX_WAIT_S", 120.0)

    seen = threading.Event()
    wait_done = QueryStateMachine.wait_done

    def traced(self, timeout):
        seen.set()
        return wait_done(self, timeout)

    monkeypatch.setattr(QueryStateMachine, "wait_done", traced)
    return seen


def _post(url, sql):
    import json
    import urllib.request

    req = urllib.request.Request(f"{url}/v1/statement", data=sql.encode())
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


class _Get:
    """One GET on its own thread: `.done` is set when it came back, with the
    body in `.state` or the exception in `.error`."""

    def __init__(self, uri):
        import threading

        self.uri, self.state, self.error = uri, None, None
        self.done = threading.Event()
        self.t0 = self.seconds = None
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        import json
        import time
        import urllib.request

        self.t0 = time.monotonic()
        try:
            with urllib.request.urlopen(self.uri, timeout=60) as r:
                self.state = json.loads(r.read())
        except Exception as e:
            self.error = e
        self.seconds = time.monotonic() - self.t0
        self.done.set()


def _polls(coord):
    return {k: coord._m_polls.value(k) for k in ("ready", "held", "timeout")}


def _polls_since(coord, before):
    return {k: v - before[k] for k, v in _polls(coord).items()}


def test_get_on_a_running_query_returns_when_it_finishes_with_the_data(
        long_poll, hold_seen):
    runner, conn = long_poll
    coord = runner.coordinator
    conn.reset(open_gate=False)
    before = _polls(coord)
    posted = _post(coord.url, LONG_POLL_SQL)
    assert "data" not in posted and posted["nextUri"]
    assert conn.entered.wait(60), "the query never reached its scan"
    get = _Get(posted["nextUri"])
    assert hold_seen.wait(30), "the handler never held the poll"
    # the query stands at the gate: the poll stays out for as long as it does
    assert not get.done.wait(0.3)
    assert not coord.queries[posted["id"]]["sm"].done
    conn.gate.set()
    assert get.done.wait(60) and get.error is None, get.error
    assert get.state["data"] == LONG_POLL_ROWS
    assert get.state["columns"] and get.state["stats"]["state"] == "FINISHED"
    assert "nextUri" not in get.state
    assert _polls_since(coord, before) == {"ready": 0, "held": 1, "timeout": 0}
    # a poll of a query that is already terminal is answered at once
    again = _Get(posted["nextUri"])
    assert again.done.wait(30) and again.state["data"] == LONG_POLL_ROWS
    assert _polls_since(coord, before) == {"ready": 1, "held": 1, "timeout": 0}


def test_query_longer_than_max_wait_gets_the_same_next_uri_and_the_client_goes_on(
        long_poll):
    import threading
    import time

    from trino_tpu.client.client import StatementClient
    from trino_tpu.runtime.coordinator import STATEMENT_MAX_WAIT_S

    assert STATEMENT_MAX_WAIT_S == 1.0  # the reference's maxWait
    runner, conn = long_poll
    coord = runner.coordinator
    conn.reset(open_gate=False)
    before = _polls(coord)
    posted = _post(coord.url, LONG_POLL_SQL)
    get = _Get(posted["nextUri"])
    assert get.done.wait(60) and get.error is None, get.error
    assert get.seconds >= 0.9 * STATEMENT_MAX_WAIT_S
    assert get.state == {
        "id": posted["id"], "stats": {"state": get.state["stats"]["state"]},
        "nextUri": posted["nextUri"],
    }
    assert get.state["stats"]["state"] not in ("FINISHED", "FAILED")
    # that poll was counted as one that timed out (under xdist a straggler
    # of another test may add to any kind: no exact counts by kind here)
    assert _polls_since(coord, before)["timeout"] >= 1
    conn.gate.set()
    last = _Get(posted["nextUri"])
    assert last.done.wait(60) and last.state["data"] == LONG_POLL_ROWS

    # the client: its polls time out while the gate is shut and it goes on
    # polling; the answer then comes with a poll that was held, or, where
    # the query finished between two polls, with one that found it ready
    conn.reset(open_gate=False)
    before = _polls(coord)
    out = {}
    client = StatementClient(coord.url)
    t = threading.Thread(
        target=lambda: out.update(result=client.execute(LONG_POLL_SQL)),
        daemon=True)
    t.start()
    deadline = time.monotonic() + 60
    while _polls_since(coord, before)["timeout"] < 2:
        assert time.monotonic() < deadline, "the client stopped polling"
        time.sleep(0.05)
    assert t.is_alive()
    conn.gate.set()
    t.join(60)
    assert not t.is_alive() and out["result"][1] == LONG_POLL_ROWS
    after = _polls_since(coord, before)
    assert after["timeout"] >= 2 and after["held"] + after["ready"] >= 1


@pytest.mark.parametrize(
    "how", ["delete_queued", "queued_time_limit", "failure", "rejected"])
def test_a_held_poll_is_woken_with_the_typed_error(
        long_poll, hold_seen, how):
    """DELETE, a failure and the resource group's refusals end a hold with
    the failure body; a query the group rejected at its POST never was
    running, so its poll is answered at once."""
    import threading

    from trino_tpu.client.client import StatementClient
    from trino_tpu.runtime.resourcegroups import (
        ResourceGroupConfig, ResourceGroupManager,
    )

    runner, conn = long_poll
    coord = runner.coordinator
    conn.reset(open_gate=(how != "failure"))
    groups = coord.resource_groups
    before = _polls(coord)
    try:
        if how == "rejected":
            coord.resource_groups = ResourceGroupManager(
                ResourceGroupConfig(max_concurrency=1, max_queued=0))
        elif how != "failure":
            coord.resource_groups = ResourceGroupManager(
                ResourceGroupConfig(max_concurrency=1, max_queued=5))
        if how != "failure":
            # occupy the only slot: the statement queues behind it
            admitted = threading.Event()
            coord.resource_groups.submit("global", "hog", 0, admitted.set)
            assert admitted.is_set()
        posted = _post(coord.url, LONG_POLL_SQL)
        get = _Get(posted["nextUri"])
        if how == "rejected":
            assert get.done.wait(30) and get.error is None, get.error
            assert "max_queued" in get.state["error"]
            want = {"ready": 1, "held": 0, "timeout": 0}
        else:
            assert hold_seen.wait(30), "the handler never held the poll"
            assert not get.done.wait(0.3)
            if how == "delete_queued":
                assert StatementClient(coord.url).cancel(posted["id"])
            elif how == "queued_time_limit":
                # the heartbeat's deadline sweep sheds the group's backlog
                coord.session.set("query_max_queued_time_s", "0.01")
            else:
                assert conn.entered.wait(60)
                conn.fail_reads = True
                conn.gate.set()
            assert get.done.wait(60) and get.error is None, get.error
            want = {"ready": 0, "held": 1, "timeout": 0}
        assert get.state["stats"] == {"state": "FAILED"}
        assert "data" not in get.state and "nextUri" not in get.state
        if how == "delete_queued":
            assert get.state["error"] == "Query was canceled"
        elif how == "queued_time_limit":
            assert get.state["errorCode"] == "EXCEEDED_QUEUED_TIME_LIMIT"
        elif how == "failure":
            assert "gated read failed" in get.state["error"]
        assert _polls_since(coord, before) == want
    finally:
        coord.session.set("query_max_queued_time_s", "600")
        coord.resource_groups = groups
        conn.gate.set()


def test_stop_releases_the_holders(hold_seen):
    runner, conn = _long_poll_cluster()
    try:
        coord = runner.coordinator
        posted = _post(coord.url, LONG_POLL_SQL)
        assert conn.entered.wait(60)
        get = _Get(posted["nextUri"])
        assert hold_seen.wait(30) and not get.done.wait(0.3)
        coord.stop()
        # the query still stands at the gate; the poll does not
        assert get.done.wait(30) and get.error is None, get.error
        assert not coord.queries[posted["id"]]["sm"].done
        assert get.state["nextUri"] == posted["nextUri"]
        assert "data" not in get.state
    finally:
        conn.gate.set()
        runner.stop()


def test_kill_drops_the_held_poll_and_the_client_reattaches(
        tmp_path, hold_seen):
    import threading

    from trino_tpu.client.client import StatementClient

    runner, conn = _long_poll_cluster(str(tmp_path / "journal.jsonl"))
    try:
        coord = runner.coordinator
        out = {}
        client = StatementClient(coord.url, reattach_max_elapsed_s=60.0)

        def run():
            try:
                out["result"] = client.execute(LONG_POLL_SQL, timeout=120)
            except Exception as e:
                out["error"] = e

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert conn.entered.wait(60)
        assert hold_seen.wait(30), "the client's poll was never held"
        by_hand = _Get(f"{coord.url}/v1/statement/{client.last_query_id}/0")
        assert not by_hand.done.wait(0.3) and t.is_alive()
        port = runner.kill_coordinator()
        # a dead coordinator answers nothing: the connection just drops
        assert by_hand.done.wait(30)
        assert isinstance(by_hand.error, OSError), by_hand.error
        assert _polls(coord) == {"ready": 0, "held": 0, "timeout": 0}
        runner.restart_coordinator(port, session={"resume_policy": "RESTART"})
        conn.gate.set()
        t.join(120)
        assert not t.is_alive() and "error" not in out, out.get("error")
        assert out["result"][1] == LONG_POLL_ROWS
    finally:
        conn.gate.set()
        runner.stop()


def test_the_clients_floor_sleeps_only_against_a_server_that_does_not_hold(
        long_poll, monkeypatch):
    import json
    import threading
    import time
    import types
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from trino_tpu.client import client as client_module

    sleeps = []
    monkeypatch.setattr(client_module, "time", types.SimpleNamespace(
        time=time.time, monotonic=time.monotonic,
        sleep=lambda s: (sleeps.append(s), time.sleep(s))))
    running_answers = 3
    gets, sent = [], []
    # when each poll leaves the client: the floor is the client's promise
    # about its sends (when a handler thread gets to run is the machine's)
    urlopen = client_module.urllib.request.urlopen

    def timed_urlopen(req, *args, **kwargs):
        # a poll of the stub (its nextUri, a str; the POST is a Request)
        if isinstance(req, str) and req.startswith(url):
            sent.append(time.monotonic())
        return urlopen(req, *args, **kwargs)

    monkeypatch.setattr(client_module.urllib.request, "urlopen", timed_urlopen)

    class AnswersAtOnce(BaseHTTPRequestHandler):
        def log_message(self, *args):
            pass

        def _send(self, obj):
            body = json.dumps(obj).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            self._send({"id": "q_stub", "stats": {"state": "QUEUED"},
                        "nextUri": f"{url}/v1/statement/q_stub/0"})

        def do_GET(self):
            gets.append(self.path)
            if len(gets) <= running_answers:
                return self._send({
                    "id": "q_stub", "stats": {"state": "RUNNING"},
                    "nextUri": f"{url}/v1/statement/q_stub/0"})
            self._send({"id": "q_stub", "stats": {"state": "FINISHED"},
                        "columns": ["x"], "data": [[1]]})

    stub = ThreadingHTTPServer(("127.0.0.1", 0), AnswersAtOnce)
    url = f"http://127.0.0.1:{stub.server_port}"
    threading.Thread(target=stub.serve_forever, daemon=True).start()
    try:
        cols, rows = client_module.StatementClient(url).execute("select 1")
    finally:
        stub.shutdown()
        stub.server_close()
    assert (cols, rows) == (["x"], [[1]])
    # the first GET went out at once; each early "running" was paced
    assert len(gets) == running_answers + 1
    assert len(sleeps) == running_answers
    assert all(0.0 < s <= client_module._POLL_FLOOR_S for s in sleeps)
    assert len(sent) == len(gets)
    assert min(b - a for a, b in zip(sent, sent[1:])) >= 0.9 * client_module._POLL_FLOOR_S

    # the coordinator holds the poll itself: one GET, no sleep
    runner, conn = long_poll
    conn.reset(open_gate=True)
    del sleeps[:]
    before = _polls(runner.coordinator)
    client = client_module.StatementClient(runner.coordinator.url)
    assert client.execute(LONG_POLL_SQL)[1] == LONG_POLL_ROWS
    assert sleeps == []
    assert sum(_polls_since(runner.coordinator, before).values()) == 1
    assert not hasattr(client, "poll_interval")
    with pytest.raises(TypeError):
        client_module.StatementClient(runner.coordinator.url, poll_interval=0.05)


def test_many_clients_each_take_their_answer_by_one_poll_that_carried_it(long_poll):
    """More client threads than cores and a short switch interval: every
    request ends with exactly one poll that found or was woken by the
    terminal state, whatever timed out before it, and carries its rows."""
    import sys
    import threading

    from trino_tpu.client.client import StatementClient

    runner, conn = long_poll
    coord = runner.coordinator
    conn.reset(open_gate=True)
    before = _polls(coord)
    clients, each = 16, 5
    rows, errors = [], []

    def run():
        client = StatementClient(coord.url)
        try:
            for _ in range(each):
                rows.append(client.execute(LONG_POLL_SQL, timeout=120)[1])
        except Exception as e:
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, daemon=True) for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not [t for t in threads if t.is_alive()] and not errors, errors
    assert rows == [LONG_POLL_ROWS] * (clients * each)
    after = _polls_since(coord, before)
    assert after["ready"] + after["held"] == clients * each, after
