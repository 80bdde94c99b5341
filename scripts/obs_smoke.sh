#!/usr/bin/env bash
# Observability smoke: spins a 2-worker cluster and asserts the whole
# observability plane end to end — distributed EXPLAIN ANALYZE with
# per-operator [rows, ms] annotations on every stage, the phase ledger
# and compile-signature attribution, Prometheus /metrics on coordinator
# AND workers (linted against the README via scripts/metrics_lint.py),
# the /v1/query listing + /v1/query/{id} QueryInfo endpoints with the
# history fallback after expiry, traceparent propagation into worker
# task spans, and the storage-governance plane — trino_tpu_disk_pool_*
# gauges on governed workers and a nonzero
# trino_tpu_spool_reproductions_total after SPOOL_LOST injection (the
# self-healing spool actually healing), plus the post-mortem plane —
# nonzero trino_tpu_flightrecorder_events_total, GET /v1/flightrecorder
# on both node roles, a seeded SLOW re-run carrying the `-- anomaly:`
# EXPLAIN ANALYZE footer, and the auto + on-demand post-mortem bundle
# round-trip over GET/POST /v1/query/{id}/postmortem, and the
# transactional write plane — a DML through the staged-commit protocol
# must carry the `-- txn:` footer and a nonzero
# trino_tpu_write_txn_total{outcome="committed"} counter, and the
# partition-tolerance plane — the cluster link matrix served on
# /v1/info (consumer -> producer -> grade) and a nonzero
# trino_tpu_hedged_fetches_total{outcome="won"} under an injected
# GRAY_SLOW producer (the hedged spool fetch actually racing), and the
# telemetry observatory — GET /v1/timeseries on both roles (federated
# cluster view on the coordinator, own-lane-only on workers) with a
# nonzero cpu series, `-- roofline:` / `-- device bandwidth:` /
# `-- exchange:` footers on the distributed EXPLAIN ANALYZE, and moving
# trino_tpu_exchange_bytes_total{direction} counters.
#
# Fast enough to run on every runtime/ or exec/ change; the same checks
# run under the tier-1 gate via tests/test_obs_plane.py.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu python - <<'EOF'
import json
import urllib.request

from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.testing.runner import DistributedQueryRunner

SQL = ("select l_returnflag, count(*) c from lineitem "
       "where l_quantity < 30 group by l_returnflag order by c desc")


def get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.read().decode()


# a disk budget gives every worker a governed NodeDiskPool, so the
# trino_tpu_disk_pool_* gauges below have something to report
runner = DistributedQueryRunner(num_workers=2, disk_budget_bytes=64 << 20)
runner.register_catalog("tpch", TpchConnector(0.01))
runner.start()
try:
    rows = runner.query("explain analyze " + SQL)
    text = "\n".join(r[0] for r in rows)
    print(text)
    print()

    assert text.count("Fragment") >= 2, "expected a multi-stage plan"
    assert "-- cache:" in text, "expected the result-cache footer"
    bare = [
        ln for ln in text.splitlines()
        if ln.strip() and not ln.lstrip().startswith(("Fragment", "--", "wall:", "tasks:"))
        and "[rows:" not in ln
    ]
    assert not bare, f"operator lines missing stats: {bare}"
    assert "slowest operator:" in text and "cluster cpu:" in text

    coord = runner.coordinator
    base = coord.url

    with coord._lock:
        # newest record (insertion-ordered dict): the inner distributed
        # query the EXPLAIN ANALYZE statement ran
        qid = list(coord.queries)[-1]

    # result-cache plane: admit immediately, run the hot query twice, and
    # the hit counter must move (runtime/resultcache.py)
    coord.session.set("result_cache_min_recurrences", "0")
    runner.query(SQL)
    runner.query(SQL)

    # serving fast path (runtime/fastpath.py): PREPARE over the protocol,
    # EXECUTE twice with distinct parameters — miss then hit on the
    # parameterized plan cache — and EXPLAIN ANALYZE EXECUTE must carry
    # the `-- fastpath:` footer with the cache disposition
    from trino_tpu.client import StatementClient

    sc = StatementClient(base)
    sc.execute("PREPARE obs_fp FROM select l_returnflag, count(*) c "
               "from lineitem where l_quantity < ? group by l_returnflag "
               "order by l_returnflag")
    assert "obs_fp" in sc.prepared, "addedPrepare delta not applied"
    sc.execute("EXECUTE obs_fp USING 10.0")
    sc.execute("EXECUTE obs_fp USING 20.0")
    _, fprows = sc.execute("EXPLAIN ANALYZE EXECUTE obs_fp USING 20.0")
    fptext = "\n".join(r[0] for r in fprows)
    fplines = [ln for ln in fptext.splitlines() if ln.startswith("-- fastpath:")]
    assert fplines, f"expected a fastpath footer:\n{fptext}"
    assert "plan_cache=hit" in fplines[0], fplines
    print(f"fastpath: {fplines[0]}")

    mtext = get(base + "/metrics")
    assert "trino_tpu_queries_total" in mtext
    assert "trino_tpu_tasks_dispatched_total" in mtext
    hit_lines = [
        ln for ln in mtext.splitlines()
        if ln.startswith('trino_tpu_result_cache_events_total{event="hit"}')
    ]
    assert hit_lines and float(hit_lines[0].split()[-1]) > 0, (
        f"expected a nonzero result-cache hit counter: {hit_lines}"
    )
    print(f"coordinator /metrics: {len(mtext.splitlines())} lines ok "
          f"(result cache hits: {hit_lines[0].split()[-1]})")

    pc_hits = [
        ln for ln in mtext.splitlines()
        if ln.startswith('trino_tpu_plan_cache_events_total{event="hit"}')
    ]
    assert pc_hits and float(pc_hits[0].split()[-1]) > 0, (
        f"expected a nonzero plan-cache hit counter: {pc_hits}"
    )
    print(f"plan cache hits: {pc_hits[0].split()[-1]}")

    for w in runner.workers:
        wtext = get(f"{w.url}/metrics")
        assert "trino_tpu_worker_tasks_total" in wtext
        print(f"worker {w.url} /metrics: {len(wtext.splitlines())} lines ok")

    # documented-vs-exposed drift gate (scripts/metrics_lint.py): every
    # exposed family must carry HELP text and every README-documented
    # metric must be exposed by coordinator or a worker
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "metrics_lint", os.path.join("scripts", "metrics_lint.py"))
    mlint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mlint)
    targets = [base + "/metrics"] + [w.url + "/metrics" for w in runner.workers]
    failures = mlint.lint(targets, "README.md")
    assert not failures, f"metrics lint: {failures}"
    print(f"metrics_lint: {len(targets)} targets clean")

    # telemetry observatory (utils/timeseries.py + utils/roofline.py):
    # the distributed EXPLAIN ANALYZE must carry the roofline attribution
    # footers, GET /v1/timeseries must answer on BOTH roles (coordinator
    # federated, worker own-lane-only) with a nonzero cpu series, and the
    # per-link exchange accounting must move the direction-labelled
    # exchange byte counters on the workers
    rooflines = [ln for ln in text.splitlines() if ln.startswith("-- roofline:")]
    assert rooflines, f"expected roofline footers:\n{text[-800:]}"
    assert any("% of" in ln for ln in rooflines), rooflines
    devlines = [ln for ln in text.splitlines()
                if ln.startswith("-- device bandwidth:")]
    assert devlines, "expected the query-wide device bandwidth footer"
    exlines = [ln for ln in text.splitlines() if ln.startswith("-- exchange:")]
    assert exlines, "expected per-stage exchange throughput footers"
    print(f"roofline: {rooflines[0]}")
    print(f"exchange: {exlines[0]}")

    import time as _t
    want_nodes = {base} | {w.url for w in runner.workers}
    ts_deadline = _t.monotonic() + 15  # default 1 s ticks: allow a few
    while _t.monotonic() < ts_deadline:
        tsp = json.loads(get(base + "/v1/timeseries"))
        nodes = tsp.get("nodes") or {}
        if want_nodes <= set(nodes) and all(
            "cpu_s" in nodes[n] for n in want_nodes
        ):
            break
        _t.sleep(0.5)
    assert want_nodes <= set(nodes), (
        f"coordinator never federated all lanes: {sorted(nodes)}"
    )
    assert sum(v for _, v in nodes[base]["cpu_s"]) > 0, (
        "coordinator cpu_s series is all-zero"
    )
    wts = json.loads(get(runner.workers[0].url + "/v1/timeseries"))
    assert wts["node"] == runner.workers[0].url
    assert "cpu_s" in (wts.get("series") or {}), "worker lane missing cpu_s"
    print(f"/v1/timeseries: {len(nodes)} node lanes federated, "
          f"worker serves its own lane ok")

    exch_vals = []
    for w in runner.workers:
        for ln in get(f"{w.url}/metrics").splitlines():
            if ln.startswith("trino_tpu_exchange_bytes_total{"):
                exch_vals.append(float(ln.split()[-1]))
    assert exch_vals and max(exch_vals) > 0, (
        f"exchange byte counters did not move: {exch_vals}"
    )
    print(f"exchange_bytes_total: {len(exch_vals)} samples, "
          f"max {max(exch_vals):.0f} B")

    info = json.loads(get(f"{base}/v1/query/{qid}"))
    assert info["stage_count"] >= 2 and info["cpu_ms"] > 0
    ledger = info.get("phase_ledger") or {}
    assert ledger.get("executing_ms", 0) >= 0 and "compiling_ms" in ledger
    assert info.get("compile_signatures"), "expected named jit signatures"
    print(f"/v1/query/{qid}: {info['stage_count']} stages, "
          f"cpu {info['cpu_ms']:.0f} ms, "
          f"compile {ledger.get('compiling_ms', 0):.0f} ms ok")

    listing = json.loads(get(base + "/v1/query"))["queries"]
    assert any(q["query_id"] == qid for q in listing), "listing misses query"
    print(f"/v1/query: {len(listing)} queries listed")

    # history survives expiry: force-expire the live record, then the
    # /v1/query/{id} fallback must serve it from the history store
    coord.expire_query(qid)
    info2 = json.loads(get(f"{base}/v1/query/{qid}"))
    assert info2.get("expired"), "expected history fallback after expiry"
    listing2 = json.loads(get(base + "/v1/query"))["queries"]
    assert any(q["query_id"] == qid for q in listing2), "history not listed"
    print(f"/v1/query/{qid} after expiry: served from history ok")

    # data-plane kernel dispatch: a GROUP BY over a non-dictionary key
    # takes the sorted group-by — visible both as an EXPLAIN ANALYZE
    # `-- kernel:` footer line and as a
    # trino_tpu_kernel_dispatch_total{op="group_by",impl="sort"} count
    from trino_tpu.ops import kernels as _kernels
    from trino_tpu.runtime.engine import Engine

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    eng.session.set("pallas_interpret", "true")
    before = _kernels._DISPATCH.value("group_by", "sort")
    krows = eng.execute(
        "EXPLAIN ANALYZE select l_suppkey, sum(l_extendedprice) "
        "from lineitem group by l_suppkey"
    )
    ktext = "\n".join(str(r[0]) for r in krows)
    klines = [ln for ln in ktext.splitlines() if ln.startswith("-- kernel:")]
    assert any("sort group_by" in ln for ln in klines), (
        f"expected a sort group_by dispatch line: {klines}"
    )
    after = _kernels._DISPATCH.value("group_by", "sort")
    assert after > before, "kernel dispatch counter did not move"
    print(f"kernel dispatch: {klines[0]} (counter {before:.0f} -> {after:.0f})")

    # split-driven scan plane (runtime/splits.py): with split_driven_scans
    # on, a scan must morselize — visible as the `-- splits:` EXPLAIN
    # ANALYZE footer and a nonzero trino_tpu_splits_total on /metrics
    import tempfile as _tf
    coord.session.set("retry_policy", "TASK")
    coord.session.set("exchange_spool_dir",
                      _tf.mkdtemp(prefix="obs_split_spool_"))
    coord.session.set("split_driven_scans", "true")
    coord.session.set("split_target_rows", "8192")
    srows = runner.query("explain analyze " + SQL)
    stext = "\n".join(r[0] for r in srows)
    slines = [ln for ln in stext.splitlines() if ln.startswith("-- splits:")]
    assert slines, f"expected a splits footer:\n{stext[-800:]}"
    print(f"splits: {slines[0]}")
    smtext2 = get(base + "/metrics")
    done = [
        ln for ln in smtext2.splitlines()
        if ln.startswith('trino_tpu_splits_total{state="completed"}')
    ]
    assert done and float(done[0].split()[-1]) > 0, (
        f"expected a nonzero completed-splits counter: {done}"
    )
    print(f"splits completed counter: {done[0].split()[-1]}")
    coord.session.set("split_driven_scans", "false")

    # storage-governance plane (runtime/disk.py + the self-healing spool):
    # every governed worker must expose the disk-pool gauges, and a
    # SPOOL_LOST injection on a committed partition must drive a producer
    # reproduction — visible as a nonzero spool_reproductions_total
    for w in runner.workers:
        wtext = get(f"{w.url}/metrics")
        cap = [
            ln for ln in wtext.splitlines()
            if ln.startswith("trino_tpu_disk_pool_capacity_bytes{")
        ]
        assert cap and float(cap[0].split()[-1]) > 0, (
            f"expected a governed disk pool on {w.url}: {cap}"
        )
    print(f"disk pool gauges: {len(runner.workers)} workers governed ok")

    for i in range(len(runner.workers)):
        runner.inject_task_failure(i, mode="SPOOL_LOST")
    runner.query("select l_linestatus, sum(l_quantity) from lineitem "
                 "group by l_linestatus order by l_linestatus")
    for w in runner.workers:
        w.fault_injector.clear()
    mtext3 = get(base + "/metrics")
    repro = [
        ln for ln in mtext3.splitlines()
        if ln.startswith("trino_tpu_spool_reproductions_total")
        and not ln.startswith("#")
    ]
    assert repro and float(repro[0].split()[-1]) > 0, (
        f"expected a nonzero spool-reproduction counter: {repro}"
    )
    print(f"spool reproductions counter: {repro[0].split()[-1]}")

    # partition-tolerance plane (runtime/health.py): a GRAY_SLOW producer
    # (correct pages, 800 ms late, zero errors) must drive the hedged
    # spool fetch — the won counter moves — and the consumer-side link
    # matrix must surface on the coordinator's /v1/info via heartbeats
    def _hedged_won() -> float:
        vals = []
        for w in runner.workers:
            for ln in get(f"{w.url}/metrics").splitlines():
                if ln.startswith(
                    'trino_tpu_hedged_fetches_total{outcome="won"}'
                ):
                    vals.append(float(ln.split()[-1]))
        return max(vals) if vals else 0.0  # process-global: any node's view

    won_before = _hedged_won()
    # slow EVERY producer: with 2 workers the plan may place the whole
    # partial stage on either one, so a single-producer fault can miss
    # the one link the final stage actually fetches over
    for wi in range(len(runner.workers)):
        runner.gray_slow(producer_index=wi, delay_ms=800)
    runner.query("select l_suppkey, count(*) from lineitem "
                 "group by l_suppkey order by l_suppkey")
    for w in runner.workers:
        w.fault_injector.clear()
    won_after = _hedged_won()
    assert won_after > won_before, (
        f"hedged won counter did not move under GRAY_SLOW: "
        f"{won_before} -> {won_after}"
    )
    print(f"hedged fetches won counter: {won_before:.0f} -> {won_after:.0f}")

    import time as _time
    links = {}
    lm_deadline = _time.monotonic() + 15  # next heartbeat folds the rows
    while _time.monotonic() < lm_deadline:
        links = json.loads(get(base + "/v1/info")).get("links") or {}
        if links:
            break
        _time.sleep(0.5)
    assert links, "expected a cluster link matrix on /v1/info"
    cells = [
        (c, p, cell.get("state"))
        for c, row in links.items() for p, cell in row.items()
    ]
    assert all(s in ("HEALTHY", "DEGRADED", "SUSPECT", "DEAD")
               for _, _, s in cells), cells
    print(f"/v1/info link matrix: {len(links)} consumer rows, "
          f"{len(cells)} links graded ok")

    # flight-recorder plane (utils/flightrecorder.py): the event counter
    # must have moved, and both node roles must serve their ring slice
    mtext4 = get(base + "/metrics")
    frlines = [
        ln for ln in mtext4.splitlines()
        if ln.startswith("trino_tpu_flightrecorder_events_total{")
    ]
    assert frlines and sum(float(ln.split()[-1]) for ln in frlines) > 0, (
        f"expected nonzero flight-recorder event counters: {frlines[:3]}"
    )
    print(f"flightrecorder: {len(frlines)} event kinds counted")
    with coord._lock:
        fr_qid = list(coord.queries)[-1]
    fr = json.loads(get(f"{base}/v1/flightrecorder?query_id={fr_qid}"))
    assert fr["events"], "coordinator flight-recorder slice is empty"
    wfr = json.loads(get(f"{runner.workers[0].url}/v1/flightrecorder"
                         f"?query_id={fr_qid}"))
    assert all(e["node"] in (runner.workers[0].url,
                             f"worker:{runner.workers[0].port}")
               for e in wfr["events"]), "worker served another node's lane"
    print(f"GET /v1/flightrecorder: coord {len(fr['events'])} events, "
          f"worker {len(wfr['events'])} events ok")

    # anomaly sentinel + post-mortem: one clean baseline run, then a
    # seeded SLOW re-run must carry the `-- anomaly:` EXPLAIN ANALYZE
    # footer and auto-write a bundle; the on-demand POST must round-trip
    coord.session.set("result_cache_enabled", "false")
    coord.session.set("anomaly_min_samples", "1")
    ANOM_SQL = ("explain analyze select l_shipmode, count(*) c "
                "from lineitem group by l_shipmode order by l_shipmode")
    # warm the plan's jit signatures first (plain select: different
    # baseline key, so this run is NOT a baseline sample) — otherwise
    # first-compile cost inflates the clean baseline and the seeded SLOW
    # run lands right at the 2x anomaly factor instead of far past it
    runner.query("select l_shipmode, count(*) c from lineitem "
                 "group by l_shipmode order by l_shipmode")
    runner.query(ANOM_SQL)  # clean run -> baseline sample
    for i in range(len(runner.workers)):
        runner.inject_task_failure(i, task_id="*", mode="SLOW",
                                   delay_ms=2500, count=10)
    arows = runner.query(ANOM_SQL)
    for w in runner.workers:
        w.fault_injector.clear()
    atext = "\n".join(r[0] for r in arows)
    alines = [ln for ln in atext.splitlines() if ln.startswith("-- anomaly:")]
    assert any("SLOW_VS_BASELINE" in ln for ln in alines), (
        f"expected a SLOW_VS_BASELINE anomaly footer:\n{atext[-600:]}"
    )
    print(f"anomaly: {alines[0]}")
    with coord._lock:
        anom_qid = list(coord.queries)[-1]
    bundle = get(f"{base}/v1/query/{anom_qid}/postmortem")
    header = json.loads(bundle.splitlines()[0])
    assert header["type"] == "header" and header["query_id"] == anom_qid
    assert header["anomalies"], "auto-bundle missing the anomaly"
    req = urllib.request.Request(
        f"{base}/v1/query/{anom_qid}/postmortem", data=b"{}")
    with urllib.request.urlopen(req, timeout=30) as resp:
        pm = json.loads(resp.read())
    assert pm["trigger"] == "on_demand" and pm["events"] > 0
    amtext = get(base + "/metrics")
    assert 'trino_tpu_query_anomalies_total{kind="SLOW_VS_BASELINE"}' in amtext
    assert 'trino_tpu_postmortem_bundles_total{trigger="anomaly"}' in amtext
    print(f"postmortem: bundle {pm['events']} events from "
          f"{len(pm['nodes'])} nodes ok")
finally:
    runner.stop()

# ---------------------------------------------------------------- fleet plane
# two-coordinator fleet behind the router: kill the query's owner mid-flight
# and assert the failover observability — a nonzero
# trino_tpu_fleet_adoptions_total on the survivor and the `-- fleet:` footer
# on the adopted EXPLAIN ANALYZE (runtime/fleet.py)
import os
import tempfile
import threading
import time

import numpy as np

from trino_tpu.client import StatementClient
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.spi import ColumnSchema
from trino_tpu.data.types import BIGINT


class GatedMemoryConnector(MemoryConnector):
    def __init__(self):
        super().__init__()
        self.gate = threading.Event()
        self.gated_table = None

    def read_split(self, split, columns):
        if split.table == self.gated_table:
            assert self.gate.wait(timeout=120), "gate never opened"
        return super().read_split(split, columns)


conn = GatedMemoryConnector()
conn.create_table("build", [ColumnSchema("k", BIGINT), ColumnSchema("w", BIGINT)])
conn.insert("build", {"k": np.arange(50, dtype=np.int64),
                      "w": np.arange(50, dtype=np.int64) * 10})
conn.create_table("probe", [ColumnSchema("k", BIGINT), ColumnSchema("v", BIGINT)])
conn.insert("probe", {"k": np.arange(2000, dtype=np.int64) % 50,
                      "v": np.arange(2000, dtype=np.int64)})

spool = tempfile.mkdtemp(prefix="obs_fleet_spool_")
fleet = DistributedQueryRunner(
    num_workers=2, default_catalog="memory", heartbeat_interval=0.3,
    num_coordinators=2, fleet_ttl_s=1.5,
)
fleet.register_catalog("memory", conn)
fleet.start()
try:
    for c in fleet.coordinators:
        c.session.set("retry_policy", "TASK")
        c.session.set("exchange_spool_dir", spool)
        c.session.set("resume_policy", "RESUME")

    FLEET_SQL = ("explain analyze select sum(v + w) from probe, build "
                 "where probe.k = build.k")
    conn.gated_table = "probe"

    class _Rider(threading.Thread):
        def __init__(self):
            super().__init__(daemon=True)
            self.client = StatementClient(fleet.client_url,
                                          reattach_max_elapsed_s=90.0)
            self.result = None
            self.error = None

        def run(self):
            try:
                self.result = self.client.execute(FLEET_SQL, timeout=120)
            except Exception as e:
                self.error = e

    rider = _Rider()
    rider.start()
    deadline = time.monotonic() + 60
    committed = lambda: any(
        os.path.exists(os.path.join(spool, n, "COMMITTED"))
        for n in (os.listdir(spool) if os.path.isdir(spool) else [])
    )
    while time.monotonic() < deadline and not committed():
        time.sleep(0.05)
    assert committed(), "build stage never spool-committed"

    owner = None
    for i, c in enumerate(fleet.coordinators):
        with c._lock:
            if any(not rec["done"].is_set() for rec in c.queries.values()):
                owner = i
    assert owner is not None, "no coordinator owns the in-flight query"
    fleet.kill_coordinator(owner)
    conn.gate.set()
    rider.join(timeout=120)
    assert rider.error is None, f"client saw a failure: {rider.error!r}"

    ftext = "\n".join(row[0] for row in rider.result[1])
    flt_lines = [ln for ln in ftext.splitlines() if ln.startswith("-- fleet:")]
    assert flt_lines and "adopted from" in flt_lines[0], (
        f"expected a fleet adoption footer:\n{ftext[-800:]}"
    )
    print(f"fleet: {flt_lines[0]}")

    survivor = fleet.coordinators[1 - owner]
    smtext = get(survivor.url + "/metrics")
    ad = [ln for ln in smtext.splitlines()
          if ln.startswith("trino_tpu_fleet_adoptions_total")
          and not ln.startswith("#")]
    assert ad and float(ad[0].split()[-1]) >= 1, (
        f"expected a nonzero adoption counter: {ad}"
    )
    assert 'trino_tpu_fleet_lease_transitions_total{event="expire"}' in smtext
    print(f"fleet adoptions counter: {ad[0].split()[-1]}")

    sinfo = json.loads(get(survivor.url + "/v1/info"))
    assert sinfo.get("fleet", {}).get("members"), "fleet info missing members"
    ui = get(survivor.url + "/ui")
    assert "origin" in ui, "/ui missing the fleet origin column"
    print(f"fleet /v1/info + /ui: "
          f"{len(sinfo['fleet']['members'])} members listed ok")

    # transactional write plane (runtime/txn.py): a DML through the
    # staged-commit protocol must carry the `-- txn:` EXPLAIN ANALYZE
    # footer and bump trino_tpu_write_txn_total{outcome="committed"}
    wrows = survivor.execute_query(
        "explain analyze insert into build select k + 1000, w from build")
    wtext = "\n".join(row[0] for row in wrows)
    wlines = [ln for ln in wtext.splitlines() if ln.startswith("-- txn:")]
    assert wlines and "outcome=committed" in wlines[0], (
        f"expected a committed txn footer:\n{wtext[-600:]}"
    )
    print(f"write txn: {wlines[0]}")
    wmtext = get(survivor.url + "/metrics")
    wc = [
        ln for ln in wmtext.splitlines()
        if ln.startswith('trino_tpu_write_txn_total{outcome="committed"}')
    ]
    assert wc and float(wc[0].split()[-1]) > 0, (
        f"expected a nonzero committed write-txn counter: {wc}"
    )
    print(f"write txn committed counter: {wc[0].split()[-1]}")
    print("OBS_SMOKE_OK")
finally:
    conn.gate.set()
    fleet.stop()
EOF
