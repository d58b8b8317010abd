"""Unified telemetry (ISSUE 4): span tracing with cross-thread parenting,
log-scale histograms, Chrome-trace export, run report, zero-cost no-op."""

import json
import logging
import os
import threading
import time

import numpy as np
import pytest

from sparkdl_tpu.core import health, profiling, resilience, telemetry
from sparkdl_tpu.core.health import HealthMonitor
from sparkdl_tpu.core.pipeline import DevicePrefetcher
from sparkdl_tpu.core.telemetry import (
    Histogram,
    MetricsRegistry,
    Telemetry,
)
from sparkdl_tpu.engine import DataFrame, EngineConfig


@pytest.fixture(autouse=True)
def _restore_engine_config():
    saved = {k: getattr(EngineConfig, k) for k in (
        "speculation", "speculation_quantile", "speculation_min_runtime_s",
        "max_task_retries", "max_workers")}
    yield
    for k, v in saved.items():
        setattr(EngineConfig, k, v)


def _by_id(spans):
    return {s["span_id"]: s for s in spans}


# -- zero-overhead no-op path ------------------------------------------------

def test_inactive_path_is_allocation_free_noop(monkeypatch):
    """No scope: span() returns the SHARED singleton (no allocation), the
    metric helpers are pure no-ops, and nothing is ever recorded —
    including the windowed plane (ISSUE 7): with telemetry inactive the
    record path never even reaches an instrument, and a ring-free
    instrument (the bare default) records without reading the clock."""
    assert telemetry.active() is None
    s1 = telemetry.span("sparkdl.task")
    s2 = telemetry.span("sparkdl.fit", anything=1)
    assert s1 is telemetry.NULL_SPAN and s2 is telemetry.NULL_SPAN
    with s1:
        assert telemetry.current_context() is None
    # metric helpers: no registry exists to record into, no error either
    telemetry.count("sparkdl.health.task_retried")
    telemetry.gauge_set(telemetry.M_PADDING_WASTE, 0.5)
    telemetry.observe(telemetry.M_STEP_TIME_S, 0.1)
    # unwindowed instruments never touch the window clock on the record
    # path — the windowed-metric feature costs the no-ring path nothing
    def clock_read_is_a_bug():
        raise AssertionError("ring-free record path read the window clock")

    monkeypatch.setattr(telemetry, "_monotonic", clock_read_is_a_bug)
    h = Histogram("h")
    h.observe(0.25)
    c = telemetry.Counter("c")
    c.inc()
    g = telemetry.Gauge("g")
    g.set(1.0)
    # their windowed views are inert, not wrong
    assert c.window_count(10.0) == 0
    assert g.window_values(10.0) is None
    w = h.window_snapshot(10.0)
    assert w["count"] == 0 and w["p50"] is None and w["p99"] is None
    monkeypatch.setattr(telemetry, "_monotonic", time.monotonic)
    # a scope opened AFTER the no-ops sees none of them
    with Telemetry("after") as tel:
        pass
    snap = tel.metrics.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}
    assert [s["name"] for s in tel.tracer.spans()] == ["sparkdl.run"]
    # and its windowed snapshot is just as empty
    wsnap = tel.metrics.window_snapshot()
    assert wsnap["counters"] == {} and wsnap["histograms"] == {}


def test_annotate_without_scope_unchanged():
    """profiling.annotate still feeds phase timers with no scope active
    (the pre-telemetry contract)."""
    profiling.reset_phase_stats()
    with profiling.annotate("sparkdl.decode", rows=3):
        pass
    stats = profiling.phase_stats(reset=True)
    assert stats["sparkdl.decode"]["count"] == 1


# -- span model / parenting --------------------------------------------------

def test_nested_spans_parent_under_scope_root():
    with Telemetry("t") as tel:
        with telemetry.span("sparkdl.fit") as outer:
            with telemetry.span("sparkdl.train_step", step=1) as inner:
                assert telemetry.current_context() == inner.context
            assert telemetry.current_context() == outer.context
    spans = _by_id(tel.tracer.spans())
    root = next(s for s in spans.values() if s["name"] == "sparkdl.run")
    fit = next(s for s in spans.values() if s["name"] == "sparkdl.fit")
    step = next(s for s in spans.values()
                if s["name"] == "sparkdl.train_step")
    assert root["parent_id"] is None
    assert fit["parent_id"] == root["span_id"]
    assert step["parent_id"] == fit["span_id"]
    assert step["attributes"]["step"] == 1
    assert len({s["trace_id"] for s in spans.values()}) == 1


def test_span_records_error_attribute_on_exception():
    with Telemetry("t") as tel:
        with pytest.raises(ValueError):
            with telemetry.span("sparkdl.task_attempt", partition=0):
                raise ValueError("boom")
    (span,) = tel.tracer.spans("sparkdl.task_attempt")
    assert span["attributes"]["error"] == "ValueError"


def test_annotate_feeds_active_tracer_with_attributes():
    """Existing phase names become spans for free (the annotate hook)."""
    with Telemetry("t") as tel:
        with profiling.annotate("sparkdl.decode", rows=7):
            pass
    (span,) = tel.tracer.spans("sparkdl.decode")
    assert span["attributes"]["rows"] == 7


def test_cross_thread_handoff_attach_and_explicit_parent():
    with Telemetry("t") as tel:
        with telemetry.span("sparkdl.fit") as fit:
            ctx = telemetry.current_context()

            def staged_worker():
                telemetry.attach(ctx)
                with telemetry.span("sparkdl.stage_batch"):
                    pass

            def explicit_worker():
                with telemetry.span("sparkdl.device_sync", parent=ctx):
                    pass

            threads = [threading.Thread(target=staged_worker),
                       threading.Thread(target=explicit_worker)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    spans = tel.tracer.spans()
    fit_rec = next(s for s in spans if s["name"] == "sparkdl.fit")
    for name in ("sparkdl.stage_batch", "sparkdl.device_sync"):
        child = next(s for s in spans if s["name"] == name)
        assert child["parent_id"] == fit_rec["span_id"]
        assert child["trace_id"] == fit_rec["trace_id"]
        assert child["thread_id"] != fit_rec["thread_id"]


def test_supervisor_pool_spans_parent_under_materialize():
    """Engine partition tasks run on pool threads; their spans must
    parent under the driver's materialize span in the one run trace."""
    with Telemetry("t") as tel:
        df = DataFrame.fromRows([{"x": i} for i in range(12)],
                                numPartitions=3)
        df.withColumn("y", lambda x: x + 1, ["x"]).collect()
    spans = tel.tracer.spans()
    by_id = _by_id(spans)
    mat = next(s for s in spans if s["name"] == "sparkdl.materialize")
    tasks = [s for s in spans if s["name"] == "sparkdl.task"]
    assert len(tasks) == 3
    driver_tid = mat["thread_id"]
    assert any(s["thread_id"] != driver_tid for s in tasks)
    for task in tasks:
        assert task["parent_id"] == mat["span_id"]
        assert task["trace_id"] == tel.run_id
    # each pool task ran (at least) one retry-loop attempt span under it
    for att in (s for s in spans if s["name"] == "sparkdl.task_attempt"):
        assert by_id[att["parent_id"]]["name"] == "sparkdl.task"


def test_retried_task_attempt_spans_share_the_task_trace():
    """A retried task's attempts are siblings under the same sparkdl.task
    span — one trace tells the whole retry story."""
    EngineConfig.max_task_retries = 2
    df = DataFrame.fromRows([{"x": i} for i in range(4)], numPartitions=1)
    failures = {"n": 1}
    lock = threading.Lock()

    def flaky(batch):
        with lock:
            if failures["n"]:
                failures["n"] -= 1
                raise resilience.TransferStall("transient")
        return batch

    with Telemetry("t") as tel:
        df.mapPartitions(flaky).collect()
    attempts = tel.tracer.spans("sparkdl.task_attempt")
    assert [a["attributes"]["attempt"] for a in attempts] == [0, 1]
    assert attempts[0]["attributes"]["error"] == "TransferStall"
    assert "error" not in attempts[1].get("attributes", {})
    parents = {a["parent_id"] for a in attempts}
    assert len(parents) == 1  # both under the SAME pool-thread task span
    assert len({a["trace_id"] for a in attempts}) == 1


def test_hedged_task_spans_share_the_task_trace():
    """A hedged straggler's duplicate attempt parents under the same
    context as the primary (pool_attempt 0 vs 1, one trace)."""
    EngineConfig.speculation = True
    EngineConfig.speculation_quantile = 0.5
    EngineConfig.speculation_min_runtime_s = 0.05
    EngineConfig.max_workers = 9
    df = DataFrame.fromRows([{"x": i} for i in range(12)], numPartitions=6)
    stalled = set()
    lock = threading.Lock()

    def slow_once(batch):
        key = batch.column(0)[0].as_py()
        with lock:
            again = key in stalled
            stalled.add(key)
        if key == 10 and not again:
            time.sleep(1.5)
        return batch

    with HealthMonitor() as mon, Telemetry("t") as tel:
        df.mapPartitions(slow_once).collect()
    assert mon.count(health.HEDGE_WON) == 1
    hedged_partition = mon.events(health.TASK_HEDGED)[0]["partition"]

    def hedged_spans():
        return [s for s in tel.tracer.spans("sparkdl.task")
                if s["attributes"]["partition"] == hedged_partition]

    # a clean run returns without waiting for the hedge LOSER (the
    # stalled primary) — its span lands when its sleep ends; wait it out
    deadline = time.monotonic() + 5.0
    while len(hedged_spans()) < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    task_spans = hedged_spans()
    assert sorted(s["attributes"]["pool_attempt"] for s in task_spans) \
        == [0, 1]
    assert len({s["parent_id"] for s in task_spans}) == 1
    assert len({s["trace_id"] for s in task_spans}) == 1
    # rows_out counts the WINNING attempt only — the hedge loser running
    # to completion must not double-count its partition's rows
    assert tel.metrics.counter(telemetry.M_ENGINE_ROWS_OUT).value == 12


def test_prefetcher_staging_thread_spans_parent_under_consumer():
    """DevicePrefetcher hands the consumer's context to its staging
    thread: spans opened by stage_fn parent under the consumer span."""
    def stage(item):
        with profiling.annotate("sparkdl.stage_batch", item=item):
            return item * 2

    with Telemetry("t") as tel:
        with telemetry.span("sparkdl.fit") as fit:
            with DevicePrefetcher(range(5), stage_fn=stage,
                                  depth=2) as staged:
                assert list(staged) == [0, 2, 4, 6, 8]
    stage_spans = tel.tracer.spans("sparkdl.stage_batch")
    assert len(stage_spans) == 5
    fit_rec = next(s for s in tel.tracer.spans()
                   if s["name"] == "sparkdl.fit")
    for s in stage_spans:
        assert s["parent_id"] == fit_rec["span_id"]
        assert s["thread_id"] != fit_rec["thread_id"]
        assert s["thread_name"].startswith("sparkdl-prefetch")


def test_span_ring_buffer_bounded_with_drop_count():
    with Telemetry("t", max_spans=4) as tel:
        for i in range(10):
            with telemetry.span("sparkdl.task", partition=i):
                pass
    assert len(tel.tracer.spans()) == 4
    # 10 task spans + the run root through a 4-slot ring
    assert tel.tracer.dropped == 7
    assert tel.tracer.summary()["spans_dropped"] == 7
    # the ring keeps the TAIL (most recent) spans
    kept = [s["attributes"].get("partition")
            for s in tel.tracer.spans("sparkdl.task")]
    assert kept == [7, 8, 9]


# -- metrics registry --------------------------------------------------------

def test_histogram_log_buckets_and_percentiles():
    h = Histogram("h", bounds=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.0, 3.0, 5.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(109.5)
    assert snap["min"] == 0.5 and snap["max"] == 100.0
    # bucket assignment uses Prometheus `le` semantics: value <= bound
    assert snap["buckets"] == {"1.0": 2, "4.0": 1, "8.0": 1, "+Inf": 1}


def test_histogram_percentile_within_bucket_error_bound():
    """Factor-2 buckets bound the relative error of the estimate: every
    percentile estimate lands within 2x of the true value."""
    h = Histogram("h")  # default log-scale seconds buckets
    values = [i / 100.0 for i in range(1, 101)]  # 0.01 .. 1.00
    for v in values:
        h.observe(v)
    for q, true in ((0.50, 0.50), (0.95, 0.95), (0.99, 0.99)):
        est = h.percentile(q)
        assert true / 2 <= est <= true * 2, (q, est)
    assert h.percentile(1.0) <= 1.0  # clamped to the observed max


def test_histogram_empty_and_degenerate():
    h = Histogram("h")
    assert h.percentile(0.5) is None
    h.observe(0.0)
    assert h.percentile(0.5) == 0.0  # clamped into [min, max]


def test_registry_get_or_create_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("sparkdl.engine.rows_out").inc(5)
    reg.counter("sparkdl.engine.rows_out").inc(2)  # same instrument
    reg.gauge("sparkdl.batching.padding_waste").set(0.125)
    reg.histogram("sparkdl.task.duration_s").observe(0.25)
    snap = reg.snapshot()
    assert snap["counters"] == {"sparkdl.engine.rows_out": 7}
    assert snap["gauges"] == {"sparkdl.batching.padding_waste": 0.125}
    hist = snap["histograms"]["sparkdl.task.duration_s"]
    assert hist["count"] == 1 and hist["p50"] is not None
    json.dumps(snap)  # JSON-able end to end


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("sparkdl.engine.rows_out").inc(3)
    reg.gauge("sparkdl.train.examples_per_sec").set(120.5)
    h = reg.histogram("sparkdl.task.duration_s", bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.prometheus_text()
    assert "# TYPE sparkdl_engine_rows_out counter" in text
    assert "sparkdl_engine_rows_out 3" in text
    assert "sparkdl_train_examples_per_sec 120.5" in text
    assert 'sparkdl_task_duration_s_bucket{le="0.1"} 1' in text
    assert 'sparkdl_task_duration_s_bucket{le="1.0"} 2' in text  # cumulative
    assert 'sparkdl_task_duration_s_bucket{le="+Inf"} 3' in text
    assert "sparkdl_task_duration_s_count 3" in text


# -- sliding-window metrics (ISSUE 7) ----------------------------------------

class _FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture
def fake_clock(monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(telemetry, "_monotonic", clock)
    return clock


def test_windowed_counter_rotation_and_expiry(fake_clock):
    reg = MetricsRegistry(window_s=10.0, window_buckets=10)  # 1 s slots
    c = reg.counter("sparkdl.health.executor_shed")
    c.inc(3)
    fake_clock.advance(1.0)
    c.inc(2)
    assert c.window_count(10.0) == 5
    assert c.window_count(1.0) == 2   # only the current slot
    fake_clock.advance(8.0)           # first inc is 9 s old: still in
    assert c.window_count(10.0) == 5
    fake_clock.advance(1.0)           # 10 s: the first slot ages out
    assert c.window_count(10.0) == 2
    fake_clock.advance(1.0)           # 11 s: everything aged out
    assert c.window_count(10.0) == 0
    assert c.value == 5               # the cumulative view is untouched
    # a slot index reused after a full ring revolution is reset first —
    # no ghost counts from the previous epoch
    fake_clock.advance(100.0)
    c.inc(1)
    assert c.window_count(10.0) == 1
    assert c.value == 6


def test_windowed_gauge_envelope(fake_clock):
    reg = MetricsRegistry(window_s=10.0, window_buckets=10)
    g = reg.gauge("sparkdl.executor.queue_depth")
    g.set(5)
    g.set(2)                          # same slot: last=2, min=2, max=5
    fake_clock.advance(1.0)
    g.set(9)
    assert g.window_values(10.0) == {"last": 9.0, "min": 2.0, "max": 9.0}
    fake_clock.advance(20.0)          # window empty
    assert g.window_values(10.0) is None
    assert g.value == 9.0             # cumulative last-write survives


def test_windowed_histogram_percentiles_and_aging(fake_clock):
    reg = MetricsRegistry(window_s=10.0, window_buckets=10)
    h = reg.histogram("sparkdl.executor.queue_wait_s")
    for _ in range(50):
        h.observe(0.01)
    for _ in range(50):
        h.observe(0.5)
    w = h.window_snapshot(10.0)
    assert w["count"] == 100
    assert w["rate_per_s"] == pytest.approx(10.0)
    assert w["min"] == 0.01 and w["max"] == 0.5
    assert 0.01 / 2 <= w["p50"] <= 0.01 * 2    # factor-2 bucket bound
    assert 0.5 / 2 <= w["p99"] <= 0.5
    # the spike ages out of the window but stays in the cumulative view:
    # "current p99" stops being polluted by history (the ISSUE 7 motive)
    fake_clock.advance(30.0)
    w2 = h.window_snapshot(10.0)
    assert w2["count"] == 0 and w2["sum"] == 0.0
    assert w2["min"] is None and w2["max"] is None
    assert w2["p50"] is None and w2["p95"] is None and w2["p99"] is None
    cum = h.snapshot()
    assert cum["count"] == 100 and cum["p99"] is not None


def test_registry_window_snapshot_shape_defaults_and_clamp(fake_clock):
    reg = MetricsRegistry(window_s=10.0, window_buckets=10)
    reg.counter("sparkdl.health.executor_shed").inc(4)
    reg.gauge("sparkdl.executor.queue_depth").set(3)
    reg.histogram("sparkdl.executor.queue_wait_s").observe(0.2)
    snap = reg.window_snapshot()          # default: the full ring
    assert snap["window_s"] == 10.0
    assert snap["counters"]["sparkdl.health.executor_shed"] == \
        {"count": 4, "rate_per_s": 0.4}
    assert snap["gauges"]["sparkdl.executor.queue_depth"]["last"] == 3.0
    assert snap["histograms"]["sparkdl.executor.queue_wait_s"]["count"] == 1
    json.dumps(snap)                      # JSON-able end to end
    # a query past the ring capacity clamps to it (can't answer more)
    assert reg.window_snapshot(1e9)["window_s"] == 10.0
    # a non-positive window is a caller bug, not a division crash
    with pytest.raises(ValueError, match="window_s"):
        reg.window_snapshot(0.0)
    # a bare registry (no windows) answers with empty sections
    bare = MetricsRegistry()
    assert bare.window_snapshot() == {
        "window_s": None, "counters": {}, "gauges": {}, "histograms": {}}
    with pytest.raises(ValueError):
        MetricsRegistry(window_s=0.0)


def test_histogram_snapshot_empty_percentiles_are_null():
    """ISSUE 7 satellite: an empty histogram (and an all-zero-count
    window) reports null percentiles, never a bucket-midpoint guess."""
    h = Histogram("h")
    snap = h.snapshot()
    assert snap["count"] == 0
    assert snap["p50"] is None and snap["p95"] is None \
        and snap["p99"] is None
    assert snap["min"] is None and snap["max"] is None
    # percentiles and buckets come from ONE locked copy: an empty
    # histogram's snapshot stays internally consistent
    assert snap["buckets"] == {}
    json.dumps(snap)  # null, not NaN — JSON-able


# -- prometheus exposition conformance (ISSUE 7 satellite) -------------------

def test_prometheus_text_format_conformance():
    """Every family gets exactly one # HELP and one # TYPE line before
    its samples; every sample line parses; histogram buckets are
    cumulative and close with +Inf == count."""
    import re

    reg = MetricsRegistry()
    reg.counter("sparkdl.engine.rows_out").inc(3)
    reg.gauge("sparkdl.train.examples_per_sec").set(120.5)
    h = reg.histogram("sparkdl.task.duration_s", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.prometheus_text()
    assert text.endswith("\n")
    name_re = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
    sample_re = re.compile(
        rf'^({name_re})(\{{le="[^"\n]*"\}})? (-?[0-9.e+-]+|NaN)$')
    help_re = re.compile(rf"^# HELP ({name_re}) .+$")
    type_re = re.compile(
        rf"^# TYPE ({name_re}) (counter|gauge|histogram)$")
    seen_help, seen_type = set(), set()
    for line in text.strip().splitlines():
        if line.startswith("# HELP"):
            m = help_re.match(line)
            assert m, line
            assert m.group(1) not in seen_help, f"duplicate HELP: {line}"
            seen_help.add(m.group(1))
        elif line.startswith("# TYPE"):
            m = type_re.match(line)
            assert m, line
            assert m.group(1) not in seen_type, f"duplicate TYPE: {line}"
            seen_type.add(m.group(1))
        else:
            m = sample_re.match(line)
            assert m, line
            base = m.group(1)
            family = re.sub(r"_(bucket|sum|count)$", "", base)
            # samples only after their family's HELP + TYPE
            assert base in seen_type or family in seen_type, line
            assert base in seen_help or family in seen_help, line
    assert seen_help == seen_type
    # histogram buckets: cumulative, closing +Inf equals the count
    assert 'sparkdl_task_duration_s_bucket{le="0.1"} 1' in text
    assert 'sparkdl_task_duration_s_bucket{le="1.0"} 2' in text
    assert 'sparkdl_task_duration_s_bucket{le="+Inf"} 3' in text
    assert "sparkdl_task_duration_s_count 3" in text


def test_prometheus_label_value_escaping():
    assert telemetry.escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    assert telemetry.escape_label_value("plain") == "plain"
    assert telemetry.escape_label_value(0.1) == "0.1"


# -- chrome trace export -----------------------------------------------------

def test_chrome_trace_roundtrips_with_monotonic_timestamps(tmp_path):
    def worker(ctx):
        with telemetry.span("sparkdl.stage_batch", parent=ctx):
            time.sleep(0.002)

    with Telemetry("t") as tel:
        with telemetry.span("sparkdl.fit") as fit:
            time.sleep(0.001)
            with telemetry.span("sparkdl.train_step"):
                time.sleep(0.002)
            t = threading.Thread(target=worker, args=(fit.context,))
            t.start()
            t.join()
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(tel.tracer.chrome_trace()))
    doc = json.load(open(path))  # round-trips through json.load
    events = doc["traceEvents"]
    complete = {e["name"]: e for e in events if e["ph"] == "X"}
    assert {"sparkdl.run", "sparkdl.fit", "sparkdl.train_step",
            "sparkdl.stage_batch"} <= set(complete)
    for e in complete.values():
        assert e["ts"] >= 0 and e["dur"] >= 0
    # monotonic consistency: children start within their parent's window
    fit_e = complete["sparkdl.fit"]
    for child in ("sparkdl.train_step", "sparkdl.stage_batch"):
        c = complete[child]
        assert fit_e["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= fit_e["ts"] + fit_e["dur"] + 1e-3
    # one track per thread: distinct tids + thread_name metadata
    tids = {e["tid"] for e in events if e["ph"] == "X"}
    assert len(tids) == 2
    meta = [e for e in events if e["ph"] == "M"]
    assert {e["tid"] for e in meta} == tids


# -- run report + health integration ----------------------------------------

def test_run_report_written_at_scope_exit(tmp_path):
    with HealthMonitor("hm") as mon:
        with Telemetry("job", out_dir=str(tmp_path)) as tel:
            health.record(health.TASK_RETRIED, partition=1)
            health.record(health.TASK_QUARANTINED, partition=2, error="x")
            with profiling.annotate("sparkdl.decode"):
                pass
            telemetry.observe(telemetry.M_STEP_TIME_S, 0.02)
    report = json.load(open(tel.report_path))
    assert report["run_id"] == tel.run_id
    # trace summary
    assert report["trace"]["spans_recorded"] >= 2
    assert "sparkdl.decode" in report["trace"]["by_name"]
    # metric snapshot mirrors the health counters exactly
    counters = report["metrics"]["counters"]
    assert counters["sparkdl.health.task_retried"] \
        == mon.count(health.TASK_RETRIED) == 1
    assert counters["sparkdl.health.task_quarantined"] \
        == mon.count(health.TASK_QUARANTINED) == 1
    # phase/overlap stats and the health report ride along
    assert "sparkdl.decode" in report["phases"]
    assert "overlap_ratio" in report["overlap"]
    assert report["health"]["counters"]["task_retried"] == 1
    # chrome trace artifact exists and loads
    trace = json.load(open(report["chrome_trace"]))
    assert any(e["name"] == "sparkdl.run" for e in trace["traceEvents"])


def test_no_files_written_without_out_dir(tmp_path, monkeypatch):
    monkeypatch.delenv(telemetry.TELEMETRY_DIR_ENV, raising=False)
    with Telemetry("quiet") as tel:
        pass
    assert tel.report_path is None


def test_env_var_opt_in(tmp_path, monkeypatch):
    monkeypatch.setenv(telemetry.TELEMETRY_DIR_ENV, str(tmp_path))
    with Telemetry("envjob") as tel:
        pass
    assert tel.report_path is not None
    assert json.load(open(tel.report_path))["run"] == "envjob"


def test_scope_root_span_carries_error_of_failed_run():
    with pytest.raises(ValueError):
        with Telemetry("failing") as tel:
            raise ValueError("boom")
    (root,) = tel.tracer.spans("sparkdl.run")
    assert root["attributes"]["error"] == "ValueError"


def test_scopes_nest_and_restore():
    with Telemetry("outer") as outer:
        assert telemetry.active() is outer
        with Telemetry("inner") as inner:
            assert telemetry.active() is inner
            telemetry.count("sparkdl.health.gang_restart")
        assert telemetry.active() is outer
    assert telemetry.active() is None
    assert inner.metrics.counter("sparkdl.health.gang_restart").value == 1
    assert outer.metrics.snapshot()["counters"] == {}


def test_log_records_stamped_with_run_and_trace_ids(caplog):
    logger = logging.getLogger("sparkdl_tpu.core.health")
    with caplog.at_level(logging.INFO, logger="sparkdl_tpu.core.health"):
        with Telemetry("stamp") as tel:
            logger.info("inside scope")
        logger.info("outside scope")
    inside = next(r for r in caplog.records if r.message == "inside scope")
    outside = next(r for r in caplog.records
                   if r.message == "outside scope")
    assert inside.run_id == tel.run_id
    assert inside.trace_id == tel.run_id
    assert not hasattr(outside, "run_id")
    # non-framework records stay untouched even inside a scope
    with Telemetry("stamp2"):
        other = logging.LogRecord("someapp", logging.INFO, __file__, 1,
                                  "x", (), None)
        assert not hasattr(other, "run_id")


# -- instrumentation: batching / trainer metrics -----------------------------

def test_run_batched_feeds_padding_and_bucket_metrics():
    import jax.numpy as jnp

    from sparkdl_tpu.core.batching import run_batched

    x = np.arange(40, dtype=np.float32).reshape(10, 4)
    with Telemetry("t") as tel:
        out = run_batched(lambda c: jnp.asarray(c) * 2, x, batch_size=8)
    np.testing.assert_allclose(np.asarray(out), x * 2)
    snap = tel.metrics.snapshot()
    # 10 rows in chunks of 8: [8 valid @ bucket 8, 2 valid @ bucket 8
    # (min_bucket)] -> 10 valid + 6 pad rows
    assert snap["counters"][telemetry.M_BATCH_ROWS] == 10
    assert snap["counters"][telemetry.M_BATCH_PAD_ROWS] == 6
    assert snap["gauges"][telemetry.M_PADDING_WASTE] \
        == pytest.approx(6 / 16)
    assert snap["histograms"][telemetry.M_BATCH_BUCKET_ROWS]["count"] == 2


def test_trainer_fit_emits_spans_and_step_metrics():
    import jax
    import flax.linen as nn

    from sparkdl_tpu.train.trainer import Trainer

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(2)(x)

    m = M()
    v = m.init(jax.random.PRNGKey(0), np.zeros((1, 3), np.float32))
    xs = np.random.default_rng(0).normal(size=(8, 3)).astype(np.float32)
    ys = np.zeros((8, 2), np.float32)
    batches = [(xs[i:i + 4], ys[i:i + 4]) for i in range(0, 8, 4)]
    trainer, state = Trainer.from_flax(m, v, loss="mse", optimizer="sgd",
                                       learning_rate=0.1)
    with Telemetry("fit") as tel:
        trainer.fit(state, batches, epochs=2, prefetch=2, sync_every=2)
    spans = tel.tracer.spans()
    by_id = _by_id(spans)
    fit = next(s for s in spans if s["name"] == "sparkdl.fit")
    assert fit["attributes"]["steps"] == 4
    epochs = [s for s in spans if s["name"] == "sparkdl.epoch"]
    assert [e["attributes"]["epoch"] for e in epochs] == [0, 1]
    for e in epochs:
        assert e["parent_id"] == fit["span_id"]
    # staging-thread spans parent under their epoch in the same trace
    driver_tid = fit["thread_id"]
    stage = [s for s in spans if s["name"] == "sparkdl.stage_batch"]
    assert len(stage) == 4
    for s in stage:
        assert by_id[s["parent_id"]]["name"] == "sparkdl.epoch"
        assert s["thread_id"] != driver_tid
        assert s["trace_id"] == tel.run_id
    steps = [s for s in spans if s["name"] == "sparkdl.train_step"]
    assert [s["attributes"]["step"] for s in steps] == [1, 2, 3, 4]
    # host step-interval histogram observed (never a device sync)
    snap = tel.metrics.snapshot()
    assert snap["histograms"][telemetry.M_STEP_TIME_S]["count"] == 3


# -- cross-process tracing + tail exemplars (ISSUE 15) ------------------------

def test_histogram_window_snapshot_at_ring_rotation_boundary(fake_clock):
    """The exact slot-rotation edge: a slot at window-age stays included
    (resolution = one slot span), one tick past it ages out, and a fresh
    observation REUSES its ring index after clearing the old exemplars —
    no ghosts from the previous revolution."""
    reg = MetricsRegistry(window_s=10.0, window_buckets=10,
                          exemplar_k=2)  # 1 s slots
    h = reg.histogram("sparkdl.executor.queue_wait_s")
    ctx_a = telemetry.SpanContext("t", 0xA)
    h.observe(0.4, exemplar=ctx_a)        # lands in slot epoch 1000
    fake_clock.advance(9.0)               # exact boundary: still inside
    w = h.window_snapshot(10.0)
    assert w["count"] == 1
    assert w["exemplars"] == [
        {"value": 0.4, "trace_id": "t", "span_id": 0xA}]
    fake_clock.advance(1.0)               # one slot past: aged out
    w = h.window_snapshot(10.0)
    assert w["count"] == 0
    assert w["exemplars"] == []           # armed: key present but empty
    # same ring index, new epoch: rotation resets counts AND exemplars
    ctx_b = telemetry.SpanContext("t", 0xB)
    h.observe(0.2, exemplar=ctx_b)
    w = h.window_snapshot(10.0)
    assert w["count"] == 1
    assert w["exemplars"] == [
        {"value": 0.2, "trace_id": "t", "span_id": 0xB}]


def test_exemplar_reservoir_keeps_topk_by_value(fake_clock):
    """k=2 reservoir: the smallest kept exemplar is evicted by a larger
    newcomer, a sub-minimum value is rejected, and the snapshot lists
    survivors descending."""
    reg = MetricsRegistry(window_s=10.0, window_buckets=10, exemplar_k=2)
    h = reg.histogram("sparkdl.executor.queue_wait_s")
    for value, span_id in ((1.0, 0xA), (3.0, 0xB), (2.0, 0xC)):
        h.observe(value, exemplar=telemetry.SpanContext("t", span_id))
    w = h.window_snapshot(10.0)
    assert w["exemplars"] == [
        {"value": 3.0, "trace_id": "t", "span_id": 0xB},
        {"value": 2.0, "trace_id": "t", "span_id": 0xC}]  # 0xA evicted
    h.observe(0.5, exemplar=telemetry.SpanContext("t", 0xD))
    assert h.window_snapshot(10.0)["exemplars"] == [
        {"value": 3.0, "trace_id": "t", "span_id": 0xB},
        {"value": 2.0, "trace_id": "t", "span_id": 0xC}]  # 0xD rejected
    # an exemplar-less observation still counts, just isn't kept
    h.observe(9.0)
    w = h.window_snapshot(10.0)
    assert w["count"] == 5 and w["max"] == 9.0
    assert w["exemplars"][0]["span_id"] == 0xB


def test_exemplars_off_keeps_window_snapshot_shape(fake_clock):
    """Unarmed (the default): passing an exemplar is inert and the
    snapshot has NO ``exemplars`` key — the pre-ISSUE-15 shape exactly."""
    reg = MetricsRegistry(window_s=10.0, window_buckets=10)
    h = reg.histogram("sparkdl.executor.queue_wait_s")
    h.observe(0.3, exemplar=telemetry.SpanContext("t", 1))
    w = h.window_snapshot(10.0)
    assert w["count"] == 1
    assert "exemplars" not in w


def test_export_ring_rebases_remaps_and_accounts_truncation():
    tr = telemetry.Tracer(trace_id="run-x")
    root = tr.span(telemetry.SPAN_RUN, parent=telemetry.ROOT)
    root.__enter__()                      # stays open, like a live scope
    t_lo = time.perf_counter_ns()
    for i in range(6):
        with tr.span(telemetry.SPAN_TASK, parent=root.context,
                     partition=i):
            pass
    t_hi = time.perf_counter_ns()
    ring = tr.export_ring(clock_offset_ns=1_000_000, process="w0",
                          parent_remap={root.context.span_id: 0xC0DE},
                          limit=4)
    assert ring["clock_offset_ns"] == 1_000_000
    assert ring["dropped"] == 2           # truncation is never silent
    assert len(ring["spans"]) == 4
    # the most recent spans are the ones kept (traces want the tail)
    assert [s["attributes"]["partition"] for s in ring["spans"]] == \
        [2, 3, 4, 5]
    for s in ring["spans"]:
        assert s["pid"] == os.getpid()
        assert s["process"] == "w0"
        assert s["parent_id"] == 0xC0DE   # re-parented off the open root
        # rebased to ABSOLUTE parent-clock time: local clock + offset
        assert t_lo + 1_000_000 <= s["start_ns"] <= s["end_ns"] \
            <= t_hi + 1_000_000
    # the exporter's own ring is untouched by building the shipped view
    assert len(tr.spans(telemetry.SPAN_TASK)) == 6


def test_adopt_remote_spans_rebases_and_rejects_noncanonical():
    worker = telemetry.Tracer(trace_id="run-x")
    for _ in range(3):
        with worker.span(telemetry.SPAN_CLUSTER_TASK, parent=None):
            pass
    ring = worker.export_ring(process="w1")
    bad = dict(ring["spans"][0], name="sparkdl.decode_chunkk")
    coord = telemetry.Tracer(trace_id="run-x")
    adopted, rejected = coord.adopt_remote_spans(ring["spans"] + [bad])
    assert (adopted, rejected) == (3, 1)
    got = coord.spans(telemetry.SPAN_CLUSTER_TASK)
    assert len(got) == 3
    for s in got:
        assert s["process"] == "w1"       # keeps its origin labeling
        assert s["end_ns"] >= s["start_ns"]
    summ = coord.summary()
    assert summ["remote_adopted"] == 3
    assert summ["remote_rejected"] == 1
    assert summ["spans_recorded"] == 3    # the bad record never landed


def test_record_remote_allocates_ids_and_rejects_noncanonical():
    tr = telemetry.Tracer(trace_id="run-x")
    parent = telemetry.SpanContext("run-x", 0x77)
    t0 = time.perf_counter_ns()
    assert tr.record_remote(telemetry.SPAN_DECODE_CHUNK, parent,
                            t0, t0 + 5_000_000, pid=12345,
                            process="decode-12345", blobs=3) is True
    (s,) = tr.spans(telemetry.SPAN_DECODE_CHUNK)
    assert s["parent_id"] == 0x77 and s["trace_id"] == "run-x"
    assert s["pid"] == 12345 and s["process"] == "decode-12345"
    assert s["thread_id"] == 0 and s["thread_name"] == "decode-12345"
    assert s["attributes"] == {"blobs": 3}
    assert s["span_id"] != 0x77           # allocated HERE, pid-salted
    assert s["span_id"] >> 40 == os.getpid()
    # non-canonical: rejected + counted, never raised (runtime path)
    assert tr.record_remote("sparkdl.decode_chunkk", parent,
                            t0, t0, pid=1) is False
    assert tr.summary()["remote_rejected"] == 1


def test_remote_span_wire_record_requires_canonical_name():
    rec = telemetry.remote_span(telemetry.SPAN_DECODE_CHUNK,
                                100, 200, pid=7, blobs=2)
    assert rec == {"name": telemetry.SPAN_DECODE_CHUNK,
                   "start_ns": 100, "end_ns": 200, "pid": 7,
                   "attributes": {"blobs": 2}}
    assert telemetry.remote_span(telemetry.SPAN_DECODE_CHUNK, 1, 2
                                 )["pid"] == os.getpid()
    with pytest.raises(ValueError, match="canonical"):
        telemetry.remote_span("sparkdl.decode_chunkk", 0, 1)


def test_clock_handshake_over_a_pipe():
    import multiprocessing as mp

    parent, child = mp.get_context("spawn").Pipe()
    try:
        def _answer():
            tag, t0 = parent.recv()
            assert tag == "clock"
            assert isinstance(t0, int)
            parent.send(time.perf_counter_ns())

        t = threading.Thread(target=_answer)
        t.start()
        offset = telemetry.clock_handshake(child)
        t.join()
        # same process, same CLOCK_MONOTONIC: the estimated offset is
        # bounded by the pipe round-trip (generous CI slack)
        assert abs(offset) < 100_000_000
    finally:
        parent.close()
        child.close()
    # a dead peer (or one that never answers) degrades to 0, not a hang
    a, b = mp.get_context("spawn").Pipe()
    a.close()
    assert telemetry.clock_handshake(b, timeout_s=0.1) == 0
    b.close()


def test_chrome_trace_process_groups_only_after_remote_merge():
    tr = telemetry.Tracer(trace_id="run-x")
    with tr.span(telemetry.SPAN_TASK):
        pass
    # purely local: NO process_name metadata — the pre-merge shape
    events = tr.chrome_trace()["traceEvents"]
    assert not any(e["name"] == "process_name" for e in events)
    tr.record_remote(telemetry.SPAN_DECODE_CHUNK,
                     telemetry.SpanContext("run-x", 1), 0, 10,
                     pid=424242, process="decode-424242")
    events = tr.chrome_trace()["traceEvents"]
    groups = {e["pid"]: e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert groups == {os.getpid(): "coordinator",
                      424242: "decode-424242"}


# ---------------------------------------------------------------------------
# PR 30: annotate without the profiler's host annotation, a public tracer
# epoch, and a device-only maybe_trace with its clock file
# ---------------------------------------------------------------------------


def test_annotate_opens_no_trace_annotation(monkeypatch):
    """The profiler's host tracer cannot be used on this path, so nothing
    would ever read a TraceAnnotation: annotate must not open one."""
    import jax.profiler

    def boom(*a, **k):
        raise AssertionError("annotate opened a jax.profiler.TraceAnnotation")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    profiling.reset_phase_stats()
    with Telemetry() as tel:
        with profiling.annotate("sparkdl.decode", rows=1):
            pass
    with profiling.annotate("sparkdl.decode"):
        pass
    assert profiling.phase_stats()["sparkdl.decode"]["count"] == 2
    assert len(tel.tracer.spans("sparkdl.decode")) == 1


def test_tracer_epoch_puts_spans_on_the_host_clock():
    before = time.perf_counter_ns()
    with Telemetry() as tel:
        opened = time.perf_counter_ns()
        with telemetry.span("sparkdl.decode"):
            inside = time.perf_counter_ns()
        closed = time.perf_counter_ns()
    assert before <= tel.tracer.epoch_ns <= opened
    (s,) = tel.tracer.spans("sparkdl.decode")
    assert opened <= s["start_ns"] + tel.tracer.epoch_ns <= inside
    assert inside <= s["end_ns"] + tel.tracer.epoch_ns <= closed


def test_maybe_trace_writes_the_clock_file(tmp_path, monkeypatch):
    """Device-only profiler options, two marker programs inside the trace,
    and sparkdl_clock.json with what on_device_clock takes."""
    import jax.profiler

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: calls.append(
            ("start", d, profiler_options.host_tracer_level,
             profiler_options.python_tracer_level, time.perf_counter_ns())))
    monkeypatch.setattr(
        jax.profiler, "stop_trace",
        lambda: calls.append(("stop", time.perf_counter_ns())))
    monkeypatch.delenv(profiling.PROFILE_DIR_ENV, raising=False)
    with profiling.maybe_trace() as on:
        assert on is False
    assert calls == []
    target = str(tmp_path / "trace")
    os.makedirs(target)
    monkeypatch.setenv(profiling.PROFILE_DIR_ENV, target)
    with Telemetry() as tel:
        with profiling.maybe_trace() as on:
            assert on is True
            body_ns = time.perf_counter_ns()
    (start, stop) = calls
    assert start[:4] == ("start", target, 0, 0)
    with open(os.path.join(target, profiling.PROFILE_CLOCK_FILE)) as f:
        clock = json.load(f)
    assert clock["epoch_ns"] == tel.tracer.epoch_ns
    assert set(clock["ready_ns"]) == {profiling.PROFILE_START,
                                      profiling.PROFILE_STOP}
    # each marker ran inside the trace, on its side of the body
    assert (start[4] <= clock["ready_ns"][profiling.PROFILE_START]
            <= body_ns <= clock["ready_ns"][profiling.PROFILE_STOP]
            <= stop[1])
    assert os.listdir(target) == [profiling.PROFILE_CLOCK_FILE]
