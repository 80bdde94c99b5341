"""Cluster telemetry observatory: the per-node time-series plane
(utils/timeseries.py ring TSDB + sampler), the roofline registry
(utils/roofline.py calibration cache), the BANDWIDTH_REGRESSION anomaly
sentinel, and the federated /v1/timeseries endpoints on both roles.

Reference behaviors being matched:
- the engine's worker stats heartbeats + Web UI cluster charts: every
  node continuously samples its own resource counters into a bounded
  ring and the coordinator folds all lanes into one cluster picture;
- roofline attribution: achieved GB/s per executed signature against a
  device bandwidth ceiling (TPU HBM table / calibrated STREAM triad);
- the post-mortem bundle carries the query-window utilization slice so
  "what was the node doing at the time" survives the ring's horizon.
"""

import json
import time
import types
import urllib.request

import numpy as np
import pytest

from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.spi import ColumnSchema
from trino_tpu.data.types import BIGINT
from trino_tpu.runtime.history import QueryHistoryStore
from trino_tpu.testing import DistributedQueryRunner
from trino_tpu.utils import roofline as R
from trino_tpu.utils import timeseries as TS

pytestmark = pytest.mark.smoke


def _wait(pred, timeout=30.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            return False
        time.sleep(interval)
    return True


# ------------------------------------------------------- ring TSDB (unit)


def test_ring_bounds_drop_oldest():
    st = TS.TimeSeriesStore(ring_size=16)
    for i in range(20):
        st.record("n1", "s", float(i), ts=1000.0 + i)
    lane = st.snapshot()["n1"]["s"]
    assert len(lane) == 16
    # oldest fell off the back: the lane starts at point 4, ends at 19
    assert lane[0] == [1004.0, 4.0]
    assert lane[-1] == [1019.0, 19.0]
    stats = st.stats()
    assert stats["points"] == 20
    assert stats["dropped"] == 4
    assert stats["lanes"] == 1


def test_snapshot_filters_since_series_nodes_limit():
    st = TS.TimeSeriesStore(ring_size=64)
    for i in range(10):
        st.record("a", "cpu_s", float(i), ts=100.0 + i)
        st.record("a", "rss_bytes", float(i * 2), ts=100.0 + i)
        st.record("b", "cpu_s", float(i * 3), ts=100.0 + i)

    # since= is strictly newer-than
    snap = st.snapshot(since=105.0)
    assert [p[0] for p in snap["a"]["cpu_s"]] == [106.0, 107.0, 108.0, 109.0]

    # series filter drops other lanes entirely
    snap = st.snapshot(series=["rss_bytes"])
    assert set(snap) == {"a"}
    assert set(snap["a"]) == {"rss_bytes"}

    # node filter
    snap = st.snapshot(nodes=["b"])
    assert set(snap) == {"b"}

    # limit keeps the NEWEST points
    snap = st.snapshot(limit=3)
    assert [p[1] for p in snap["b"]["cpu_s"]] == [21.0, 24.0, 27.0]


def test_disabled_store_is_noop_and_configure_resize_drops():
    st = TS.TimeSeriesStore(ring_size=32, enabled=False)
    st.record("n", "s", 1.0)
    assert st.snapshot() == {}
    assert st.stats()["points"] == 0

    st.configure(enabled=True)
    st.record("n", "s", 1.0)
    assert len(st.snapshot()["n"]["s"]) == 1

    # resizing drops history (documented configure() contract)
    st.configure(ring_size=64)
    assert st.snapshot() == {}
    assert st.stats()["ring_size"] == 64
    # same-size configure keeps history
    st.record("n", "s", 2.0)
    st.configure(ring_size=64)
    assert len(st.snapshot()["n"]["s"]) == 1


# --------------------------------------------------------- sampler (unit)


def test_sampler_sources_deltas_and_error_isolation():
    st = TS.TimeSeriesStore(ring_size=32)
    counter = {"v": 100.0}

    def _cum():
        counter["v"] += 7.0
        return counter["v"]

    def _boom():
        raise RuntimeError("subsystem died")

    s = TS.Sampler(
        "node-x",
        {
            "gauge": lambda: 42.0,
            "cum": _cum,
            "skipped": lambda: None,
            "broken": _boom,
        },
        deltas={"cum"},
        store=st,
    )
    s.sample_once(ts=1.0)
    s.sample_once(ts=2.0)
    lanes = st.snapshot()["node-x"]
    assert [p[1] for p in lanes["gauge"]] == [42.0, 42.0]
    # first tick only establishes the delta baseline; second records +7
    assert [p[1] for p in lanes["cum"]] == [7.0]
    assert "skipped" not in lanes
    assert "broken" not in lanes
    assert s.ticks == 2


def test_sampler_cadence_and_clean_shutdown():
    st = TS.TimeSeriesStore(ring_size=256, sample_interval_s=0.05)
    s = TS.Sampler("node-y", {"g": lambda: 1.0}, store=st, interval_s=0.02)
    s.start()
    assert _wait(lambda: s.ticks >= 5, timeout=5.0)
    s.stop()
    assert s._thread is None  # joined, not abandoned
    ticks = s.ticks
    time.sleep(0.1)
    assert s.ticks == ticks  # no zombie sampling after stop
    assert len(st.snapshot()["node-y"]["g"]) == ticks

    # a disabled store refuses to start the thread at all
    st.configure(enabled=False)
    s2 = TS.Sampler("node-z", {"g": lambda: 1.0}, store=st)
    s2.start()
    assert s2._thread is None


# -------------------------------------------------- roofline cache (unit)


def test_cpu_roofline_cache_roundtrip(tmp_path):
    path = str(tmp_path / "roofline.json")

    # a cached figure is returned verbatim — no re-probe
    with open(path, "w") as f:
        json.dump({"cpu_gbps": 123.0, "ts": 0}, f)
    assert R.calibrate_cpu_gbps(cache_path=path) == 123.0

    # force=True re-probes and rewrites the cache
    fresh = R.calibrate_cpu_gbps(cache_path=path, force=True)
    assert fresh > 0
    with open(path) as f:
        saved = json.load(f)
    assert saved["cpu_gbps"] == round(fresh, 3)
    assert saved["cpu_gbps"] != 123.0

    # a corrupt cache falls back to probing instead of dying
    with open(path, "w") as f:
        f.write("{not json")
    assert R.calibrate_cpu_gbps(cache_path=path) > 0


def test_device_roofline_memo_and_pct(tmp_path):
    path = str(tmp_path / "roofline.json")
    R.reset_cache()
    try:
        info = R.device_roofline(cache_path=path)
        assert info["platform"]
        assert info["hbm_gbps"] > 0
        assert info["source"] in ("table", "calibrated", "default")
        # memoized: second call answers identically without the path
        assert R.device_roofline() == info
        # achieving exactly the ceiling is 100% of roofline
        assert R.pct_of_roofline(info["hbm_gbps"]) == pytest.approx(100.0)
        assert R.pct_of_roofline(0.0) == 0.0
    finally:
        R.reset_cache()  # don't leak the tmp-path memo into other tests


# -------------------------------------- bandwidth baseline/sentinel (unit)


def test_history_baseline_gb_per_sec_p50():
    store = QueryHistoryStore(capacity=50)
    for i, gbps in enumerate([4.0, 5.0, 6.0]):
        store.record({
            "query_id": f"bw-{i}", "state": "FINISHED", "planhash": "ph-bw",
            "wall_ms": 100.0, "device_gb_per_sec": gbps,
        })
    # an eager-only run (no roofline figure) must not zero the baseline
    store.record({
        "query_id": "bw-eager", "state": "FINISHED", "planhash": "ph-bw",
        "wall_ms": 100.0,
    })
    base = store.baseline("ph-bw", min_samples=3)
    assert base is not None
    assert base["samples"] == 4
    assert base["gb_per_sec_p50"] == 5.0


# --------------------------------------------------------- cluster fixture


AGG_SQL = "select sum(v) from probe"


@pytest.fixture(scope="module")
def cluster():
    conn = MemoryConnector()
    conn.create_table(
        "probe", [ColumnSchema("k", BIGINT), ColumnSchema("v", BIGINT)]
    )
    conn.insert("probe", {
        "k": np.arange(2000, dtype=np.int64) % 50,
        "v": np.arange(2000, dtype=np.int64),
    })
    # fast ticks so cluster asserts see points within a test's patience;
    # restored after the module so other files keep the 1 s default
    prev = TS.STORE.sample_interval_s
    TS.configure(sample_interval_s=0.1)
    runner = DistributedQueryRunner(
        num_workers=2, default_catalog="memory", heartbeat_interval=0.2,
    )
    runner.register_catalog("memory", conn)
    runner.start()
    try:
        yield runner
    finally:
        runner.stop()
        TS.configure(sample_interval_s=prev)


def _run(runner, sql=AGG_SQL):
    coord = runner.coordinator
    qid = coord.submit_query(sql)
    sm = coord.queries[qid]["sm"]
    assert _wait(lambda: sm.done, 60.0), f"query stuck in {sm.state}"
    assert sm.state == "FINISHED", sm.error
    return qid


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


# -------------------------------------------- federated endpoints (cluster)


def test_timeseries_endpoints_both_roles_federated(cluster):
    coord = cluster.coordinator
    _run(cluster)
    want = {coord.url} | {w.url for w in cluster.workers}

    def federated():
        # every node's lanes, `cpu_s` among them: a delta, there from a
        # node's second tick on, and above zero once a tick saw work
        nodes = _get_json(f"{coord.url}/v1/timeseries")["nodes"]
        return all({"cpu_s", "rss_bytes"} <= set(nodes.get(n, ())) for n in want) \
            and sum(v for _, v in nodes[coord.url]["cpu_s"]) > 0

    assert _wait(federated, timeout=15.0, interval=0.2), (
        "coordinator view never federated all node lanes")

    payload = _get_json(f"{coord.url}/v1/timeseries")
    assert payload["node"] == coord.url
    assert payload["stats"]["points"] > 0
    for node in want:
        lanes = payload["nodes"][node]
        assert "cpu_s" in lanes and "rss_bytes" in lanes
        assert all(v >= 0 for _, v in lanes["cpu_s"])
    # the process did real work; its cpu lane cannot be all-zero
    assert sum(v for _, v in payload["nodes"][coord.url]["cpu_s"]) > 0

    # a worker serves ONLY its own lane
    w0 = cluster.workers[0]
    wp = _get_json(f"{w0.url}/v1/timeseries")
    assert wp["node"] == w0.url
    assert "cpu_s" in wp["series"]

    # series filter over the wire
    only = _get_json(f"{coord.url}/v1/timeseries?series=rss_bytes")
    for lanes in only["nodes"].values():
        assert set(lanes) <= {"rss_bytes"}


def test_timeseries_since_filter_over_wire(cluster):
    coord = cluster.coordinator
    cut = time.time()
    time.sleep(0.4)  # a few 0.1 s ticks past the cut
    payload = _get_json(f"{coord.url}/v1/timeseries?since={cut}")
    lanes = payload["nodes"].get(coord.url) or {}
    assert lanes, "no fresh points after the cut"
    for pts in lanes.values():
        assert all(ts > cut for ts, _ in pts)


# ------------------------------------------------- rss regression (cluster)


def test_rss_current_below_peak_and_heartbeat_carries_both(cluster):
    # unit: the sampled figure is CURRENT residency, the peak is the
    # lifetime high-water mark — sampled <= peak must hold (the /v1/info
    # handler clamps the few-page statm-vs-ru_maxrss lag)
    assert TS.current_rss_bytes() > 0
    assert TS.peak_rss_bytes() > 0

    for w in cluster.workers:
        info = _get_json(f"{w.url}/v1/info")
        assert info["rss_bytes"] > 0
        assert info["peak_rss_bytes"] > 0
        assert info["rss_bytes"] <= info["peak_rss_bytes"]

    # the heartbeat carries both onto the coordinator's membership view
    coord = cluster.coordinator
    assert _wait(
        lambda: all(
            getattr(wi, "rss_bytes", None) and getattr(
                wi, "peak_rss_bytes", None)
            for wi in coord.workers.values()
        ),
        timeout=10.0, interval=0.1,
    ), "heartbeats never delivered rss figures"
    for wi in coord.workers.values():
        assert wi.rss_bytes <= wi.peak_rss_bytes


# ------------------------------------------ bandwidth sentinel (cluster)


def _bw_record(coord, qid, planhash, gbps):
    """A synthetic finished-run record shaped like the live one — only
    the fields _score_anomalies reads."""
    return {
        "sm": types.SimpleNamespace(query_id=qid),
        "sql": "select bw_probe",
        "cache": {"planhash": planhash},
        "query_info": {
            "query_id": qid, "wall_ms": 100.0, "spill_ms": 0.0,
            "task_retries": 0, "compile_signatures": {},
            "device_gb_per_sec": gbps,
        },
    }


def _seed_bw_baseline(coord, planhash, gbps=10.0, n=4):
    for i in range(n):
        coord.history.record({
            "query_id": f"{planhash}-seed-{i}", "state": "FINISHED",
            "planhash": planhash, "wall_ms": 100.0,
            "device_gb_per_sec": gbps,
        })


def test_bandwidth_regression_fires_on_slow_run(cluster):
    coord = cluster.coordinator
    _seed_bw_baseline(coord, "ph-bw-pos", gbps=10.0)
    rec = _bw_record(coord, "q-bw-pos", "ph-bw-pos", gbps=1.0)
    coord._score_anomalies(rec)
    kinds = [a["kind"] for a in rec["query_info"]["anomalies"]]
    assert kinds == ["BANDWIDTH_REGRESSION"]
    a = rec["query_info"]["anomalies"][0]
    assert a["baseline_p50"] == 10.0
    assert a["factor"] == 10.0


def test_bandwidth_regression_stays_quiet(cluster):
    coord = cluster.coordinator
    _seed_bw_baseline(coord, "ph-bw-neg", gbps=10.0)

    # within 2x of baseline: clean
    rec = _bw_record(coord, "q-bw-neg", "ph-bw-neg", gbps=8.0)
    coord._score_anomalies(rec)
    assert rec["query_info"]["anomalies"] == []

    # no roofline figure at all (eager-only plan): silent, not divide-by-0
    rec = _bw_record(coord, "q-bw-none", "ph-bw-neg", gbps=None)
    coord._score_anomalies(rec)
    assert rec["query_info"]["anomalies"] == []

    # noise-band floor: a baseline under the floor never flags
    coord.session.set("anomaly_bandwidth_min_gb_per_sec", "50")
    try:
        rec = _bw_record(coord, "q-bw-floor", "ph-bw-neg", gbps=1.0)
        coord._score_anomalies(rec)
        assert rec["query_info"]["anomalies"] == []
    finally:
        coord.session.set("anomaly_bandwidth_min_gb_per_sec", "0.05")


# ------------------------------------- roofline figures on QueryInfo (live)


def test_query_info_carries_roofline_and_exchange(cluster):
    coord = cluster.coordinator
    qid = _run(cluster)
    qi = coord.queries[qid]["query_info"]
    # exchange accounting exists for any multi-stage plan
    assert isinstance(qi.get("exchange"), list)
    # the compiled path yields roofline figures; the eager fallback
    # (no cost_analysis) legitimately leaves them None — accept both,
    # but whatever is present must be self-consistent
    if qi.get("device_gb_per_sec") is not None:
        assert qi["device_gb_per_sec"] > 0
        roof = qi["roofline"]
        assert roof["device"]["hbm_gbps"] > 0
        for sig in roof["signatures"]:
            assert sig["executes"] >= 1
            assert sig["gb_per_sec"] >= 0
            assert 0 <= sig["pct_of_roofline"]


# ------------------------------------------- post-mortem slice (cluster)


def test_postmortem_bundle_carries_timeseries_slice(cluster):
    coord = cluster.coordinator
    qid = _run(cluster)
    time.sleep(0.25)  # let a couple of ticks land inside the window
    assert coord.write_postmortem(qid, trigger="observatory-test")
    path = coord.postmortem_path(qid)

    slice_rec = None
    with open(path, encoding="utf-8") as f:
        for line in f:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("type") == "timeseries":
                slice_rec = rec
                break
    assert slice_rec is not None, "bundle has no timeseries slice"
    t0, t1 = slice_rec["window"]
    assert t0 is not None and t1 is not None and t1 >= t0
    assert slice_rec["nodes"], "slice carries no node lanes"
    assert coord.url in slice_rec["nodes"]

    # the report renderer understands the bundle end-to-end
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "observatory_report",
        pathlib.Path(__file__).resolve().parent.parent
        / "scripts" / "observatory_report.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    nodes, queries = mod.from_bundle(path)
    assert nodes == slice_rec["nodes"]
    assert any(q.get("query_id") == qid for q in queries)
    text = "\n".join(mod.render_timeline(nodes, None, 40))
    assert coord.url in text


# --------------------------------------------- observe drill (chaos tier)


@pytest.mark.slow
def test_observe_drill_gray_slow_memory_pressure():
    """`chaos_tier.sh observe`: GRAY_SLOW stretches the exchange window
    while tasks hold their memory reservations, MEMORY_PRESSURE shrinks
    one pool mid-run — the observatory must show memory-pool reserved
    RISING then FALLING, and the post-mortem slice must cover it."""
    conn = MemoryConnector()
    conn.create_table(
        "probe", [ColumnSchema("k", BIGINT), ColumnSchema("v", BIGINT)]
    )
    conn.insert("probe", {
        "k": np.arange(2000, dtype=np.int64) % 50,
        "v": np.arange(2000, dtype=np.int64),
    })
    prev = TS.STORE.sample_interval_s
    TS.configure(sample_interval_s=0.05)
    runner = DistributedQueryRunner(
        num_workers=2, default_catalog="memory", heartbeat_interval=0.2,
        node_memory_bytes=200_000,
    )
    runner.register_catalog("memory", conn)
    runner.start()
    coord = runner.coordinator
    try:
        coord.session.set("task_memory_reserve_bytes", "50000")
        coord.session.set("memory_blocked_timeout_s", "30")
        t_start = time.time()
        # one forced baseline tick per worker BEFORE the shrink so the
        # capacity lane shows the drop (MEMORY_PRESSURE is consumed at
        # arm time — it resizes the pool the moment it is injected)
        for w in runner.workers:
            w.sampler.sample_once()
        # latency-only gray failure on worker 0's exchange pages: every
        # consumer fetch waits while ITS reservation is held — the
        # deterministic "reserved stays up for several ticks" lever
        runner.gray_slow(0, delay_ms=300)
        # and shrink worker 0's pool mid-drill (capacity lane must move)
        runner.memory_pressure(0, capacity_bytes=120_000)
        qid = _run(runner)

        # one forced tick per worker AFTER completion pins the fall
        for w in runner.workers:
            w.sampler.sample_once()

        snap = TS.snapshot(
            nodes=[w.url for w in runner.workers],
            series=["mem_reserved_bytes", "mem_capacity_bytes"],
            since=t_start,
        )
        rises = falls = shrunk = False
        for lanes in snap.values():
            pts = [v for _, v in lanes.get("mem_reserved_bytes") or []]
            if pts and max(pts) > 0:
                rises = True
                if pts[-1] < max(pts):
                    falls = True
            caps = [v for _, v in lanes.get("mem_capacity_bytes") or []]
            if caps and min(caps) <= 120_000 < max(caps):
                shrunk = True
        assert rises, "no sampler tick saw a held reservation"
        assert falls, "reserved never fell back after the query finished"
        assert shrunk, "MEMORY_PRESSURE capacity drop not visible"

        # the bundle's slice covers the drill window
        assert coord.write_postmortem(qid, trigger="observe-drill")
        with open(coord.postmortem_path(qid), encoding="utf-8") as f:
            slices = [
                json.loads(ln) for ln in f
                if '"timeseries"' in ln and json.loads(ln).get("type")
                == "timeseries"
            ]
        assert slices
        t0, t1 = slices[0]["window"]
        sm = coord.queries[qid]["sm"]
        assert t0 <= sm.created_at + 0.001
        assert t1 >= sm.finished_at - 0.001
        covered = [
            v for lanes in slices[0]["nodes"].values()
            for _, v in lanes.get("mem_reserved_bytes") or []
        ]
        assert covered and max(covered) > 0, (
            "slice does not cover the pressure window"
        )
    finally:
        runner.stop()
        TS.configure(sample_interval_s=prev)
