"""The reduction from trace events to busy seconds, idle share, the top
device operations and the idle gaps by host annotation — on a synthetic
trace whose answer is known, and on a small trace recorded on the chip."""

import gzip
import json
import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def synthetic():
    ops = [["fusion.1", 0, 10], ["fusion.2", 5, 10],      # overlap: 0..15
           ["fusion.1", 30, 10],                           # 30..40
           ["copy", 60, 5]]                                # 60..65
    modules = [["jit_step(123)", 0, 15], ["jit_step(123)", 30, 10],
               ["jit_other(9)", 60, 5]]
    host = [["sparkdl.decode", 14, 10],        # covers 15..24 of gap 15..30
            ["sparkdl.host_stage", 20, 30],    # covers 20..30, and 40..50
            ["sparkdl.device_apply", 42, 18]]  # inner, covers 42..60
    return {"device": {"/device:TPU:0": {"XLA Ops": ops,
                                         "XLA Modules": modules}},
            "host": host}


def test_busy_is_the_union_not_the_sum():
    out = trace_reduce.reduce(synthetic(), window=(0, 100))
    assert out["busy_s"] == pytest.approx(30e-9)         # 15 + 10 + 5
    assert out["window_s"] == pytest.approx(100e-9)
    assert dict(out["device_ops"])["fusion.1"] == pytest.approx(20e-9)


def test_gaps_are_shared_among_the_annotations_open_during_them():
    gaps = dict(trace_reduce.reduce(synthetic(), window=(0, 100))["idle_gaps"])
    # 15..30: decode 15..20, then host_stage (started later) 20..30;
    # 40..60: host_stage 40..42, device_apply (inner) 42..60; 65..100: none
    assert gaps["sparkdl.decode"] == pytest.approx(5e-9)
    assert gaps["sparkdl.host_stage"] == pytest.approx(12e-9)
    assert gaps["sparkdl.device_apply"] == pytest.approx(18e-9)
    assert gaps["(none)"] == pytest.approx(35e-9)
    assert sum(gaps.values()) == pytest.approx(70e-9)


def test_a_run_cut_by_the_edge_counts_in_part():
    out = trace_reduce.reduce(synthetic(), window=(35, 100))
    runs, seconds = out["programs"]["jit_step"]
    assert runs == pytest.approx(0.5) and seconds == pytest.approx(5e-9)


def marked():
    events = synthetic()
    events["device"]["/device:TPU:0"]["XLA Modules"] += [
        ["jit_bench_trace_start(77)", 20, 2], ["jit_bench_trace_stop(78)", 58, 2]]
    return events


def test_the_window_is_between_the_two_marker_programs():
    events = marked()
    assert trace_reduce.marked_window(
        events, "bench_trace_start", "bench_trace_stop") == (22, 58)
    assert trace_reduce.marked_window(
        synthetic(), "bench_trace_start", "bench_trace_stop") is None
    out = trace_reduce.reduce(events, window=(22, 58))
    assert out["busy_s"] == pytest.approx(10e-9)          # 30..40
    assert set(out["programs"]) == {"jit_step"}           # no marker in it


def test_host_spans_come_onto_the_device_clock_by_the_markers():
    runs = trace_reduce.marker_runs(marked(), ("bench_trace_start",
                                               "bench_trace_stop"))
    assert runs == {"bench_trace_start": (20, 22),
                    "bench_trace_stop": (58, 60)}
    # the host read its clock 3 and 5 after the markers' runs ended, on a
    # clock 1000 ahead: offset = -mean(1003, 1005) = -1004
    ready = {"bench_trace_start": 1025, "bench_trace_stop": 1065}
    assert trace_reduce.on_device_clock(
        [["sparkdl.decode", 1034, 10]], ready, runs) == [
            ["sparkdl.decode", 30, 10]]
    assert trace_reduce.on_device_clock([["x", 1, 1]], ready, {}) == []


def test_the_marker_programs_carry_their_names():
    import harness

    tracer = harness.Tracer(True)
    tracer.prepare()
    for name, marker in tracer._markers.items():
        assert f"@jit_{name}" in marker.lower(tracer._token).as_text()


def test_no_device_operation_no_reading():
    assert trace_reduce.reduce({"device": {}, "host": []}) is None
    assert trace_reduce.reduce({"device": {"/device:TPU:0": {"XLA Ops": []}},
                                "host": []}) is None


def test_recorded_chip_trace():
    with gzip.open(os.path.join(HERE, "recorded_trace.json.gz"), "rt") as f:
        events = json.load(f)
    out = trace_reduce.reduce(events)
    assert 0 < out["busy_s"] < out["window_s"]
    # the union can never exceed the sum of the operations' own durations
    total = sum(d for lines in events["device"].values()
                for _, _, d in lines.get("XLA Ops", [])) / 1e9
    assert out["busy_s"] <= total / len(events["device"]) + 1e-9
    gaps = sum(s for _, s in out["idle_gaps"])
    assert gaps <= out["window_s"] - out["busy_s"] + 1e-9
    assert out["programs"] and all(r > 0 for r, _ in out["programs"].values())
