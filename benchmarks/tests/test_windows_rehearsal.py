"""The ``windows`` driver end to end at TestLatentMoE size, dropped into a
copy of the benchmark as new files: set-up, window, check, result line; a
traced run's per-layer metrics; the control through the cell's limits; the
FLOP count at the published widths (the hand count at the small size and the
token traffic are in tests/models/test_latent_moe.py)."""

import json
import os

import rehearse

CELL = "testmoe-windows.windows"


def test_cell_runs_and_is_correct(root, capsys):
    line, err = rehearse.run(root, CELL, seed=2**31 + 19)
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] % 8 == 0
    assert set(line["metrics"]) == {"featurize_images_per_s", "setup_s"}
    assert line["facts"]["compiles_in_window"] == 0
    assert set(line["compared"]) == {"pooled_angle_gap", "logprob_gap_p50",
                                     "logprob_gap_p90"}
    assert "routing agreement by expert layer: [" in capsys.readouterr().err
    assert err.strip().splitlines()[-1] == "correct: True"


def test_traced_run_reports_the_cells_per_layer_metrics(root,
                                                        recorded_trace):
    line, err = rehearse.run(root, CELL, seed=31, trace=1)
    assert line["correct"] is True, err
    want = {m["name"] for m in json.load(open(os.path.join(
        os.path.dirname(root), "BENCHMARK.json")))["per_layer"]
        if CELL in m["workloads"]}
    assert set(line["metrics"]) == want
    value = {k: m["value"] for k, m in line["metrics"].items()}
    # 4 of 16 experts held, 4 chosen of 16: one pair a token on average
    assert 0.5 < value["moe.local_pairs_per_token"] < 1.5
    assert value["moe.expert_load_max_over_mean"] >= 1.0
    assert value["moe.overflow_pairs_per_row"] >= 0.0
    assert value["sequence.tokens_per_launch"] == 2 * 16
    assert value["staging.h2d_bytes_per_image"] == 16 * 4
    assert value["executor.d2h_bytes_per_image"] > (64 + 16) * 4
    assert value["collect.vectorized_values_per_image"] == 64 + 16 + 16


def test_a_control_is_not_correct(root):
    """The reference with float8 operands put in the program's place fails
    the cell's comparison."""
    import harness

    cell = harness.Cell(CELL, root)
    driver = harness.by_name("drivers", "windows", root).Driver(
        cell, 7, {"peaks": rehearse.PEAKS, "device": rehearse.DEVICE,
                  "root": root})
    import check
    import control_windows

    out = control_windows.readings(driver, 0.01, lambda numbers: dict(zip(
        ("correct", "compared"),
        check.decide(numbers, cell.workload["limits"]))))
    assert out["sound"]["correct"] is True, out
    assert out["control_fp8_reference"]["correct"] is False, out
    assert out["fault_rows_swapped"]["correct"] is False, out


def test_flops_lm_at_the_published_widths():
    """ISSUE 32's own arithmetic: 2,266 M multiply-accumulates a token."""
    import flops_lm

    config = json.load(open(os.path.join(
        rehearse.BENCH, "configs", "openpangu-ultra-moe-718b-ep16.json")))
    macs = flops_lm.macs_per_window(config, 4096)
    per_token = sum(macs.values()) / 4096
    assert abs(per_token / 2266e6 - 1) < 0.005
    share = {k: v / sum(macs.values()) for k, v in macs.items()}
    assert abs(share["attention_projections"] - 0.43) < 0.01
    assert abs(share["attention_scores_values"] - 0.19) < 0.01
    assert abs(share["routed_experts"] - 0.04) < 0.005
