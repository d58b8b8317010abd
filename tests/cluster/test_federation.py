"""Live cluster metrics federation (ISSUE 19 tentpole): workers ship
bounded windowed-metrics frames at the federation cadence, the
coordinator folds them into ONE :class:`ClusterMetricsView`, the
federated SLO watchdog evaluates cluster-level rules against the
merged view, and a breach (or a worker loss) triggers a flight-recorder
postmortem bundle — written atomically BEFORE the run ends.

Covers: the frame build/fold unit surface (counters summed, gauge
envelopes merged, histogram buckets summed so a cluster p99 is a real
merged percentile), clock-skew window alignment (±2-slot worker
offsets rebase onto the coordinator clock with no double-count and no
gap), staleness/mark-dead accounting, the AGGREGATE-breach chaos leg
(no single worker breaches the queue-wait SLO but the cluster merged
p99 does — the watchdog fires live, mid-run), the SIGKILL leg with
federation armed (outputs bit-identical, the dead worker ages out and
its last shipped frame lands in the postmortem bundle), and the
off-path guarantee (federation unarmed -> no frames, no ``federation``
report section, no postmortem dirs, exporter artifacts unchanged).
"""

import glob
import json
import os
import time
import types

import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.cluster import aggregate
from sparkdl_tpu.cluster import router as cluster_router
from sparkdl_tpu.core import decode_pool, health, slo, telemetry
from sparkdl_tpu.core.health import HealthMonitor
from sparkdl_tpu.core.resilience import Fault, FaultInjector
from sparkdl_tpu.core.telemetry import Telemetry
from sparkdl_tpu.engine import DataFrame, EngineConfig

# the synthetic registries below: 60 s window over 12 ring slots
_SPAN_S = 5.0


@pytest.fixture(autouse=True)
def _restore_engine_config():
    saved = EngineConfig.snapshot()
    yield
    EngineConfig.restore(saved)
    cluster_router.shutdown()
    decode_pool.shutdown()


# -- synthetic-frame helpers (no cluster spawned) -----------------------------

def _registry(exemplar_k=0):
    return telemetry.MetricsRegistry(window_s=60.0, window_buckets=12,
                                     exemplar_k=exemplar_k)


def _frame(reg, worker, wid, seq=1, offset_ns=0):
    """Build a federation frame through the REAL worker-side builder."""
    shim = types.SimpleNamespace(metrics=reg)
    frame = aggregate.build_frame(worker, wid, seq, shim,
                                  clock_offset_ns=offset_ns)
    assert frame is not None
    return frame


def _fixed_clock(monkeypatch, t):
    monkeypatch.setattr(telemetry, "_monotonic", lambda: t)


# -- the fold: counters summed, buckets merged, real cluster p99 --------------

def test_fold_sums_counters_and_merges_histogram_buckets(monkeypatch):
    now = 1002.5  # mid-slot on the 5 s ladder
    _fixed_clock(monkeypatch, now)

    reg_a, reg_b = _registry(), _registry()
    for reg, n in ((reg_a, 3), (reg_b, 5)):
        for _ in range(n):
            reg.counter(telemetry.M_ENGINE_ROWS_OUT).inc()
    reg_a.gauge(telemetry.M_EXECUTOR_QUEUE_DEPTH).set(2.0)
    reg_b.gauge(telemetry.M_EXECUTOR_QUEUE_DEPTH).set(7.0)
    for v in (0.2, 0.2, 0.4):
        reg_a.histogram(telemetry.M_QUEUE_WAIT_S).observe(v)
    for v in (0.2, 0.8):
        reg_b.histogram(telemetry.M_QUEUE_WAIT_S).observe(v)

    view = aggregate.ClusterMetricsView(cadence_s=0.25)
    view.ingest(_frame(reg_a, "sparkdl-cluster-0", 0), now=now)
    view.ingest(_frame(reg_b, "sparkdl-cluster-1", 1), now=now)

    snap = view.window_snapshot(60.0, now=now)
    assert snap["workers_reporting"] == 2
    assert snap["counters"][telemetry.M_ENGINE_ROWS_OUT]["count"] == 8
    gauge = snap["gauges"][telemetry.M_EXECUTOR_QUEUE_DEPTH]
    assert gauge["min"] == 2.0 and gauge["max"] == 7.0
    hist = snap["histograms"][telemetry.M_QUEUE_WAIT_S]
    assert hist["count"] == 5
    assert hist["sum"] == pytest.approx(1.8)
    assert hist["min"] == 0.2 and hist["max"] == 0.8

    # per-worker attribution mirrors each side's own fold
    attr = view.attribution(telemetry.M_QUEUE_WAIT_S, "count",
                            60.0, now=now)
    assert attr == {"sparkdl-cluster-0": 3, "sparkdl-cluster-1": 2}

    # frames carry ONLY declared names (the lint's runtime counterpart)
    frame = _frame(reg_a, "sparkdl-cluster-0", 0)
    for section in ("counters", "gauges", "histograms"):
        for name in frame[section]:
            assert (name in telemetry.CANONICAL_METRIC_NAMES
                    or name.startswith(telemetry.HEALTH_METRIC_PREFIX))


def test_merged_p99_breaches_where_no_single_worker_does(monkeypatch):
    """The aggregate-breach construction, statically: each worker's own
    p99 estimate stays under 1.0 s (one's tail is a single outlier its
    p99 never reaches; the other's p99 bucket estimate clamps to its
    modest max), but the MERGED buckets put the cluster p99 in the high
    bucket with a 1.3 s envelope — a real merged percentile >= 1.0 that
    no worst-worker fold could produce."""
    now = 1002.5
    _fixed_clock(monkeypatch, now)

    reg_a, reg_b = _registry(exemplar_k=4), _registry(exemplar_k=4)
    ctx_a = telemetry.SpanContext(trace_id="run-x", span_id=0xA)
    ctx_b = telemetry.SpanContext(trace_id="run-x", span_id=0xB)
    for v in [0.2] * 99 + [1.3]:
        reg_a.histogram(telemetry.M_QUEUE_WAIT_S).observe(v,
                                                          exemplar=ctx_a)
    for v in [0.2] * 98 + [0.9, 0.9]:
        reg_b.histogram(telemetry.M_QUEUE_WAIT_S).observe(v,
                                                          exemplar=ctx_b)

    view = aggregate.ClusterMetricsView(cadence_s=0.25)
    view.ingest(_frame(reg_a, "w-a", 0), now=now)
    view.ingest(_frame(reg_b, "w-b", 1), now=now)

    attr = view.attribution(telemetry.M_QUEUE_WAIT_S, "p99",
                            30.0, now=now)
    assert all(v is not None and v < 1.0 for v in attr.values())
    merged = view.window_snapshot(30.0, now=now)["histograms"][
        telemetry.M_QUEUE_WAIT_S]
    assert merged["p99"] >= 1.0
    assert merged["max"] == 1.3
    # the merged exemplar reservoir keeps the global tail, spans intact
    top = merged["exemplars"][0]
    assert top["value"] == 1.3 and top["span_id"] == 0xA

    # and the federated watchdog sees exactly that verdict on the view
    rules = [r for r in slo.federated_default_rules(window_s=30.0)
             if r.metric == telemetry.M_QUEUE_WAIT_S]
    (rule,) = rules
    assert rule.name.startswith(slo.FEDERATED_RULE_PREFIX)
    with HealthMonitor("fed-unit") as mon:
        wd = slo.SLOWatchdog(rules, attribution=lambda r: view.attribution(
            r.metric, r.stat, r.window_s, now=now))
        verdicts = wd.evaluate(view, now=now)
    assert verdicts[rule.name]["breached"] is True
    (breach,) = mon.events(health.SLO_BREACH)
    assert breach["rule"] == rule.name
    assert breach["workers"] == attr
    assert breach["exemplars"][0]["value"] == 1.3


# -- clock-skew window alignment (ISSUE 19 satellite) -------------------------

def test_skewed_worker_epochs_rebase_with_no_double_count_no_gap(
        monkeypatch):
    """Workers whose clocks run ±2 ring slots off the coordinator's:
    the clock-handshake offset shipped in each frame rebases every slot
    epoch onto the coordinator's clock, so both workers' samples land
    exactly once (no double-count) in the coordinator slot they really
    happened in (no gap) — even for a window of a SINGLE slot."""
    coord_now = 1002.5  # coordinator epoch 200 on the 5 s ladder

    # worker A's clock is 2 slots AHEAD: local 1012.5, offset = -10 s
    _fixed_clock(monkeypatch, coord_now + 2 * _SPAN_S)
    reg_a = _registry()
    for v in (0.2, 0.2, 0.2):
        reg_a.histogram(telemetry.M_QUEUE_WAIT_S).observe(v)
    reg_a.counter(telemetry.M_ENGINE_ROWS_OUT).inc(3)
    frame_a = _frame(reg_a, "w-ahead", 0,
                     offset_ns=int(-2 * _SPAN_S * 1e9))
    assert frame_a["now_epoch"] == 202

    # worker B's clock is 2 slots BEHIND: local 992.5, offset = +10 s
    _fixed_clock(monkeypatch, coord_now - 2 * _SPAN_S)
    reg_b = _registry()
    for v in (0.9, 0.9):
        reg_b.histogram(telemetry.M_QUEUE_WAIT_S).observe(v)
    reg_b.counter(telemetry.M_ENGINE_ROWS_OUT).inc(2)
    frame_b = _frame(reg_b, "w-behind", 1,
                     offset_ns=int(2 * _SPAN_S * 1e9))
    assert frame_b["now_epoch"] == 198

    view = aggregate.ClusterMetricsView(cadence_s=0.25)
    view.ingest(frame_a, now=coord_now)
    view.ingest(frame_b, now=coord_now)

    # a single-slot window on the coordinator clock: epoch 200 only.
    # Unrebased, A's epoch-202 samples would double in any wider window
    # and B's epoch-198 samples would vanish entirely from this one.
    for window_s in (_SPAN_S, 60.0):
        snap = view.window_snapshot(window_s, now=coord_now)
        hist = snap["histograms"][telemetry.M_QUEUE_WAIT_S]
        assert hist["count"] == 5, f"window {window_s}"
        assert hist["sum"] == pytest.approx(3 * 0.2 + 2 * 0.9)
        rows = snap["counters"][telemetry.M_ENGINE_ROWS_OUT]
        assert rows["count"] == 5
    attr = view.attribution(telemetry.M_QUEUE_WAIT_S, "count",
                            _SPAN_S, now=coord_now)
    assert attr == {"w-ahead": 3, "w-behind": 2}


def test_stale_and_dead_workers_age_out_but_frames_are_retained():
    view = aggregate.ClusterMetricsView(cadence_s=0.1)  # stale after .3
    reg_a, reg_b = _registry(), _registry()
    reg_a.histogram(telemetry.M_QUEUE_WAIT_S).observe(0.2)
    reg_b.histogram(telemetry.M_QUEUE_WAIT_S).observe(0.4)
    view.ingest(_frame(reg_a, "w0", 0), now=100.0)
    view.ingest(_frame(reg_b, "w1", 1), now=100.0)
    assert view.workers_reporting(now=100.0) == 2
    assert view.fresh_workers(now=100.0) == ["w0", "w1"]

    # past the staleness horizon the fold empties — explicitly
    assert view.workers_reporting(now=100.31) == 0
    snap = view.window_snapshot(60.0, now=100.31)
    assert snap["workers_reporting"] == 0
    assert snap["histograms"] == {}

    # a dead worker leaves the fold even while its frame is fresh
    view.ingest(_frame(reg_a, "w0", 0, seq=2), now=200.0)
    view.ingest(_frame(reg_b, "w1", 1, seq=2), now=200.0)
    view.mark_dead("w1")
    assert view.fresh_workers(now=200.0) == ["w0"]
    snap = view.window_snapshot(60.0, now=200.0)
    assert snap["workers_reporting"] == 1
    assert snap["histograms"][telemetry.M_QUEUE_WAIT_S]["count"] == 1

    # ...but its last shipped frame stays retained for the recorder
    frames = view.last_frames()
    assert frames["w1"]["alive"] is False
    assert frames["w1"]["frame"]["seq"] == 2
    status = view.status(now=200.0)
    assert status["workers_reporting"] == 1
    assert status["workers_known"] == 2
    assert status["frames_ingested"] == 4
    prom = view.prometheus_text(now=200.0)
    assert "sparkdl_cluster:workers_reporting 1" in prom


# -- the live legs ------------------------------------------------------------

def _queue_wait_rules():
    return [r for r in slo.federated_default_rules(window_s=10.0)
            if r.metric == telemetry.M_QUEUE_WAIT_S]


def _aggregate_breach_op(batch):
    """Each worker observes a queue-wait profile that keeps its OWN p99
    under the 1.0 s threshold; only the cluster-merged buckets breach.
    The tail values come last so a partial frame never breaches early."""
    tel = telemetry.active()
    wid = int(tel.process_scope[1:]) if tel and tel.process_scope else 0
    vals = ([0.2] * 99 + [1.3]) if wid == 0 else ([0.2] * 98 + [0.9, 0.9])
    ctx = telemetry.current_context()
    for v in vals:
        telemetry.observe(telemetry.M_QUEUE_WAIT_S, v, exemplar=ctx)
    x = np.asarray(batch.column("x"), dtype=np.float64)
    return pa.array(x * 2.0)


def _slow_op(batch):
    time.sleep(0.08)  # outlives the frame cadence: every worker ships
    x = np.asarray(batch.column("x"), dtype=np.float64)
    return pa.array(x * 3.0)


def _collect(op, n=24, parts=4):
    df = DataFrame.fromRows([{"x": i} for i in range(n)],
                            numPartitions=parts)
    return df.withColumnBatch("y", op, outputType=pa.float64()).collect()


def _wait_for(mon, event, deadline_s):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline and not mon.count(event):
        time.sleep(0.1)
    return mon.count(event)


def test_aggregate_breach_fires_live_and_dumps_a_postmortem(
        tmp_path, monkeypatch):
    """The ISSUE 19 acceptance leg: NO single worker breaches the local
    queue-wait SLO, but the cluster-wide merged p99 does. The federated
    watchdog fires DURING the run (exactly one breach/recovered pair),
    the breach names both workers' sub-threshold contributions plus a
    resolvable exemplar span, and the flight recorder lands an atomic
    postmortem bundle on disk BEFORE the run ends."""
    monkeypatch.setattr(cluster_router, "_default_federation_rules",
                        _queue_wait_rules)
    EngineConfig.cluster_workers = 2
    EngineConfig.cluster_federation_s = 0.1
    out = str(tmp_path)
    with HealthMonitor("fed-breach") as mon, \
            Telemetry(name="fed-breach", out_dir=out,
                      exemplar_k=4) as tel:
        try:
            got = _collect(_aggregate_breach_op)
            assert _wait_for(mon, health.SLO_BREACH, 30.0) == 1
            # the bundle is on disk MID-RUN, before any shutdown path (the
            # recorder writes it after the breach is recorded: wait for its
            # own event, which follows the rename, not for the breach's)
            assert _wait_for(mon, health.POSTMORTEM_DUMPED, 30.0) == 1
            mid_run = glob.glob(os.path.join(out, "postmortem_*"))
            assert len(mid_run) == 1
            assert not mid_run[0].endswith(".tmp")  # the atomic rename
            assert _wait_for(mon, health.SLO_RECOVERED, 30.0) == 1
        finally:
            cluster_router.shutdown()

    assert [r["y"] for r in got] == [2.0 * i for i in range(24)]
    # exactly ONE breach/recovered pair — partial frames never flapped
    assert mon.count(health.SLO_BREACH) == 1
    assert mon.count(health.SLO_RECOVERED) == 1
    assert mon.count(health.POSTMORTEM_DUMPED) == 1

    (breach,) = mon.events(health.SLO_BREACH)
    assert breach["rule"].startswith(slo.FEDERATED_RULE_PREFIX)
    assert breach["observed"] >= 1.0 > breach["threshold"] - 0.001
    # per-worker attribution: every worker is UNDER the threshold —
    # the breach is a property of the merged view alone
    workers = breach["workers"]
    assert len(workers) == 2
    assert all(v < 1.0 for v in workers.values())
    # the exemplar is a real span in the merged trace
    spans = {s["span_id"] for s in tel.tracer.spans()}
    exemplars = breach["exemplars"]
    assert exemplars[0]["value"] == pytest.approx(1.3)
    assert all(e["trace_id"] == tel.run_id for e in exemplars)
    assert any(e["span_id"] in spans for e in exemplars)

    # the bundle: four artifacts, consistent with the breach
    (bundle,) = glob.glob(os.path.join(out, "postmortem_*"))
    assert os.path.basename(bundle).startswith(
        f"postmortem_{tel.run_id}_")
    assert sorted(os.listdir(bundle)) == [
        "breach.json", "health.json", "snapshots.jsonl", "trace.json"]
    with open(os.path.join(bundle, "breach.json")) as f:
        bj = json.load(f)
    assert bj["trigger"] == "slo_breach"
    assert bj["detail"]["rule"] == breach["rule"]
    assert bj["rings_pulled"] == 2  # both live workers answered
    assert len(bj["federation"]) == 2  # every worker's last frame
    with open(os.path.join(bundle, "trace.json")) as f:
        doc = json.load(f)
    assert {e["pid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    with open(os.path.join(bundle, "snapshots.jsonl")) as f:
        timeline = [json.loads(line) for line in f]
    assert timeline and all("workers_reporting" in t for t in timeline)
    assert any(t["slo"].get(breach["rule"], {}).get("breached")
               for t in timeline)

    # the merged reports carry the federation section + the bundle path
    fed = cluster_router.last_cluster_report()["federation"]
    assert fed["workers_known"] == 2
    assert fed["frames_ingested"] >= 2
    assert fed["postmortems"] == [bundle]
    assert cluster_router.last_run_report()["cluster"]["federation"] \
        == fed


def test_worker_kill_with_federation_armed_keeps_outputs_bit_identical(
        tmp_path):
    """SIGKILL one worker mid-stream with federation armed: outputs are
    bit-identical to the no-cluster run, the dead worker ages out of the
    fold the moment its pipe hits EOF (one cluster_metrics_stale event),
    and the worker-loss postmortem bundle retains its LAST shipped
    frame."""
    want = _collect(_slow_op, 36, 6)

    EngineConfig.cluster_workers = 2
    EngineConfig.cluster_federation_s = 0.04
    out = str(tmp_path)
    inj = FaultInjector.seeded(0, cluster_worker_kill=Fault(times=1,
                                                            after=2))
    with HealthMonitor("fed-chaos") as mon, \
            Telemetry(name="fed-chaos", out_dir=out):
        try:
            with inj:
                got = _collect(_slow_op, 36, 6)
        finally:
            cluster_router.shutdown()

    assert inj.fired == {"cluster_worker_kill": 1}
    assert got == want  # bit-identical THROUGH the loss
    assert mon.count(health.CLUSTER_WORKER_LOST) == 1
    (lost,) = mon.events(health.CLUSTER_WORKER_LOST)
    dead = lost["worker"]

    # the view aged the dead worker out explicitly, exactly once
    (stale,) = mon.events(health.CLUSTER_METRICS_STALE)
    assert stale["worker"] == dead and stale["reason"] == "worker_lost"

    # the worker-loss bundle retains the dead worker's last frame
    assert mon.count(health.POSTMORTEM_DUMPED) == 1
    (bundle,) = glob.glob(os.path.join(out, "postmortem_*"))
    with open(os.path.join(bundle, "breach.json")) as f:
        bj = json.load(f)
    assert bj["trigger"] == "worker_lost"
    assert bj["detail"] == {"worker": dead}
    entry = bj["federation"][dead]
    assert entry["alive"] is False
    assert entry["frame"]["seq"] >= 1
    assert entry["frame"]["worker"] == dead
    # the survivor answered the ring pull; the dead worker cannot
    assert bj["rings_pulled"] == 1

    fed = cluster_router.last_cluster_report()["federation"]
    assert fed["workers_known"] == 2
    assert fed["postmortems"] == [bundle]


# -- the off path -------------------------------------------------------------

def test_federation_off_ships_no_frames_and_reports_stay_shaped(
        tmp_path):
    """cluster_federation_s unset: no frames, no view, no watchdog, no
    postmortems — the cluster report, the merged run report, and the
    exporter artifacts keep their exact pre-federation shape."""
    EngineConfig.cluster_workers = 2
    out = str(tmp_path)
    with Telemetry(name="fed-off", out_dir=out,
                   export_interval_s=30.0) as tel:
        try:
            got = _collect(_slow_op)
            assert cluster_router.exporter_status() is None
            assert cluster_router.exporter_prometheus_text() == ""
        finally:
            cluster_router.shutdown()
    assert len(got) == 24

    assert glob.glob(os.path.join(out, "postmortem_*")) == []
    rep = cluster_router.last_cluster_report()
    assert rep["worker_count"] == 2
    assert "federation" not in rep
    assert "federation" not in cluster_router.last_run_report()["cluster"]
    with open(tel.exporter.snapshot_path) as f:
        for line in f:
            assert "cluster" not in json.loads(line)
    with open(tel.exporter.prom_path) as f:
        # the FEDERATED families (colon-namespaced) never appear; the
        # coordinator's own sparkdl.cluster.* locals of course do
        assert "sparkdl_cluster:" not in f.read()
