"""The ``span`` reader on hand-made spans, and one traced rehearsal run that
reports the metrics it feeds (PR 30)."""

import json
import os

import pytest

import rehearse
from readers import span

MS = 1_000_000
# two threads inside ``a``: [0, 40) and [30, 50) overlap, [70, 80) stands
# alone; ``b`` lies elsewhere
SPANS = [["a", 0, 40 * MS], ["a", 30 * MS, 20 * MS], ["b", 55 * MS, 5 * MS],
         ["a", 70 * MS, 10 * MS]]


def run_with(spans, **window):
    return {"window": dict({"spans": spans, "images": 10, "seconds": 0.1},
                           **window)}


def spec(name, stat, per="seconds", scale=1):
    return {"kind": "span", "span": name, "stat": stat, "per": per,
            "scale": scale}


def test_total_sums_over_threads_and_union_counts_wall_time_once():
    run = run_with(SPANS)
    assert span.read(spec("a", "total_s", "images", 1000), run) \
        == pytest.approx(7.0)                   # 70 ms over 10 images
    assert span.read(spec("a", "union_s", "images", 1000), run) \
        == pytest.approx(6.0)                   # [0, 50) + [70, 80)
    assert span.read(spec("a", "union_s", scale=100), run) \
        == pytest.approx(60.0)                  # 60 ms of a 100 ms window
    assert span.read(spec("b", "union_s", scale=100), run) \
        == pytest.approx(5.0)


@pytest.mark.parametrize("intervals,want", [
    ([(0, 5)], 5), ([(0, 5), (5, 9)], 9), ([(0, 9), (2, 3)], 9),
    ([(4, 6), (0, 1)], 3)])
def test_union_of_any_order_and_nesting(intervals, want):
    spans = [["a", start, stop - start] for start, stop in intervals]
    assert span.read(spec("a", "union_s", scale=1e9),
                     run_with(spans, seconds=1)) == pytest.approx(want)


def test_absent_name_reads_nought_and_no_spans_reads_none():
    for stat in ("total_s", "union_s"):
        assert span.read(spec("sparkdl.queue_wait", stat), run_with(SPANS)) \
            == 0.0
        assert span.read(spec("a", stat), run_with([])) is None
        assert span.read(spec("a", stat), {"window": {"seconds": 1.0}}) \
            is None
    # nothing to divide by: no reading either
    assert span.read(spec("a", "total_s", "images"),
                     run_with(SPANS, images=0)) is None
    with pytest.raises(SystemExit):
        span.read(spec("a", "mean_s"), run_with(SPANS))


NEW = {"collect.row_assembly_share", "executor.apply_wall_share",
       "executor.queue_wait_ms_per_image", "executor.launch_ms_per_image",
       "executor.device_wait_ms_per_image", "executor.fetch_ms_per_image",
       "executor.d2h_bytes_per_image"}


def test_a_traced_rehearsal_reports_the_seven_metrics(root, recorded_trace):
    line, err = rehearse.run(root, "testnet-featurize.arrays", seed=31,
                             trace=1)
    assert line["correct"] is True, err
    got = {name: m["value"] for name, m in line["metrics"].items()}
    assert NEW <= set(got)
    assert 0 < got["collect.row_assembly_share"] < 100
    assert 0 < got["executor.apply_wall_share"] < 100
    for name in ("launch", "fetch"):
        assert got[f"executor.{name}_ms_per_image"] > 0
    assert got["executor.queue_wait_ms_per_image"] >= 0
    assert got["executor.device_wait_ms_per_image"] >= 0
    parts = sum(got[f"executor.{name}_ms_per_image"] for name in
                ("queue_wait", "launch", "device_wait", "fetch"))
    assert parts <= got["executor.apply_ms_per_image"]
    # TestNet's rows come back as float32 vectors
    with open(os.path.join(root, "configs", "testnet-featurize.json")) as f:
        config = json.load(f)
    dim = config.get("feature_dim")
    if dim:
        assert got["executor.d2h_bytes_per_image"] == 4 * dim
    assert got["executor.d2h_bytes_per_image"] > 0
    # every phase the new spans feed is in the untraced facts too
    for name in ("sparkdl.row_assembly", "sparkdl.launch",
                 "sparkdl.device_sync", "sparkdl.fetch"):
        assert line["facts"]["phase_s"][name] > 0
