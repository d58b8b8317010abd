"""The ``windows_shortconv`` driver end to end at TestShortConvMoE size,
dropped into a copy of the benchmark as new files: set-up (a layer drawn with
its kind, the head tied), window, check, result line; a traced run's per-layer
metrics, the two this cell brought among them; the control through the cell's
limits. ``rehearse_cells.STAND_IN`` knows the cells of PR 32's day, so the
cell is added here to the root that ``make_root`` made (the model's own tests
and the FLOP count by hand are in tests/models/test_shortconv_moe.py)."""

import json
import os

import pytest
import rehearse

CELL = "testshortconv-windows.windows"
REAL = "lfm2-8b-a1b.windows"


@pytest.fixture(scope="module")
def root_with_cell(root):
    """The rehearsal's ``BENCHMARK.json`` with this cell beside the others,
    under every metric the real benchmark lists the real cell for."""
    path = os.path.join(os.path.dirname(root), "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    with open(os.path.join(rehearse.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    if all(w["name"] != CELL for w in bench["workloads"]):
        bench["configs"].append({
            "name": "testshortconv-windows", "source": "rehearsal",
            "reduced": [], "why": "rehearsal",
            "file": "benchmarks/configs/testshortconv-windows.json"})
        bench["workloads"].append({
            "name": CELL, "config": "testshortconv-windows",
            "traffic": "windows", "chips": 1, "why": "rehearsal"})
        for group in ("end_to_end", "per_layer"):
            for metric, ours in zip(real[group], bench[group]):
                assert metric["name"] == ours["name"]
                if REAL in metric.get("workloads", ()):
                    ours["workloads"].append(CELL)
        with open(path, "w") as f:
            json.dump(bench, f)
    return root


def test_cell_runs_and_is_correct(root_with_cell, capsys):
    line, err = rehearse.run(root_with_cell, CELL, seed=2**31 + 23)
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] % 8 == 0
    assert set(line["metrics"]) == {"featurize_images_per_s", "setup_s"}
    assert line["facts"]["compiles_in_window"] == 0
    assert set(line["compared"]) == {"pooled_angle_gap", "logprob_gap_p50",
                                     "logprob_gap_p90"}
    assert "routing agreement by expert layer: [" in capsys.readouterr().err
    assert err.strip().splitlines()[-1] == "correct: True"


def test_traced_run_reports_the_cells_per_layer_metrics(root_with_cell,
                                                        recorded_trace):
    line, err = rehearse.run(root_with_cell, CELL, seed=37, trace=1)
    assert line["correct"] is True, err
    with open(os.path.join(rehearse.REPO, "BENCHMARK.json")) as f:
        want = {m["name"] for m in json.load(f)["per_layer"]
                if REAL in m["workloads"]}
    assert {"moe.buffer_fill_share", "sequence.conv_layers_per_image",
            "program.mfu.featurize", "device.busy_mfu.featurize"} <= want
    assert set(line["metrics"]) == want
    value = {k: m["value"] for k, m in line["metrics"].items()}
    # every expert is held: a token's four pairs stay, and the buffer is them
    assert value["moe.local_pairs_per_token"] == 4
    assert value["moe.buffer_fill_share"] == 100
    assert value["moe.overflow_pairs_per_row"] == 0
    assert value["moe.expert_load_max_over_mean"] >= 1.0
    assert value["sequence.conv_layers_per_image"] == 3
    assert value["attention.fused_layers_per_image"] == 0
    assert value["sequence.tokens_per_launch"] == 2 * 16
    assert value["staging.h2d_bytes_per_image"] == 16 * 4
    assert value["collect.vectorized_values_per_image"] == 64 + 16 + 16


def test_a_control_is_not_correct(root_with_cell):
    """The reference with float8 operands put in the program's place fails
    the cell's comparison, as do the sampled rows swapped."""
    import harness

    cell = harness.Cell(CELL, root_with_cell)
    driver = harness.by_name("drivers", cell.config["entry"],
                             root_with_cell).Driver(
        cell, 7, {"peaks": rehearse.PEAKS, "device": rehearse.DEVICE,
                  "root": root_with_cell})
    import check
    import control_windows

    out = control_windows.readings(driver, 0.01, lambda numbers: dict(zip(
        ("correct", "compared"),
        check.decide(numbers, cell.workload["limits"]))))
    assert out["sound"]["correct"] is True, out
    assert out["control_fp8_reference"]["correct"] is False, out
    assert out["fault_rows_swapped"]["correct"] is False, out
    assert len(out["routing_agreement"]) == 3


def test_the_drivers_weights_tie_the_head_and_follow_the_layer_types(
        root_with_cell):
    import harness

    cell = harness.Cell(CELL, root_with_cell)
    driver = harness.by_name("drivers", cell.config["entry"],
                             root_with_cell).Driver(
        cell, 3, {"peaks": rehearse.PEAKS, "device": rehearse.DEVICE,
                  "root": root_with_cell})
    from drivers import common

    driver.key = common.prng_key(3)
    variables = driver.make_variables()
    assert variables["head"] is variables["embed"]
    assert variables["embed"].dtype.name == "bfloat16"
    kinds = ["conv" if "conv" in layer else "full_attention"
             for layer in variables["layers"]]
    assert kinds == cell.config["layer_types"]
    assert ["mlp" in layer for layer in variables["layers"]] == [
        True, False, False, False]
