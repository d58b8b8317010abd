"""The ``windows_laguna`` driver end to end at TestLaguna size, dropped into a
copy of the benchmark as new files: set-up (a layer drawn with its kind and
whether it is dense given, the head untied), window, check, result line; a
traced run's per-layer metrics, the three this cell brought among them; the
controls through the cell's limits — the float8 reference, the sampled rows
swapped, and the four that belong to the model: the shared expert left out,
the gate taken as 1, the full layers turning all of a head, the span left
out. ``rehearse_cells.STAND_IN`` knows the cells it was written with, so the
cell is added here to the root that ``make_root`` made (the model's own tests
and the FLOP count by hand are in tests/models/test_laguna_moe.py)."""

import json
import os

import pytest
import rehearse

CELL = "testlaguna-windows.windows"
REAL = "laguna-xs2.windows"
NEW = {"moe.shared_expert_layers_per_image",
       "attention.gated_layers_per_image", "moe.product_visits_per_tile"}


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
            "name": "testlaguna-windows", "source": "rehearsal",
            "reduced": [], "why": "rehearsal",
            "file": "benchmarks/configs/testlaguna-windows.json"})
        bench["workloads"].append({
            "name": CELL, "config": "testlaguna-windows",
            "traffic": "windows", "chips": 1, "why": "rehearsal"})
        for group in ("end_to_end", "per_layer"):
            for metric, ours in zip(real[group], bench[group]):
                assert metric["name"] == ours["name"]
                if REAL in metric.get("workloads", ()):
                    ours["workloads"].append(CELL)
        with open(path, "w") as f:
            json.dump(bench, f)
    return root


def make_driver(root, seed):
    import harness

    cell = harness.Cell(CELL, root)
    return cell, harness.by_name("drivers", cell.config["entry"],
                                 root).Driver(
        cell, seed, {"peaks": rehearse.PEAKS, "device": rehearse.DEVICE,
                     "root": root})


def test_cell_runs_and_is_correct(root_with_cell, capsys):
    line, err = rehearse.run(root_with_cell, CELL, seed=2**31 + 37)
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] % 4 == 0
    assert set(line["metrics"]) == {"featurize_images_per_s", "setup_s"}
    assert line["facts"]["compiles_in_window"] == 0
    assert set(line["compared"]) == {"pooled_angle_gap", "logprob_gap_p50",
                                     "logprob_gap_p90"}
    assert "routing agreement by expert layer: [" in capsys.readouterr().err
    assert err.strip().splitlines()[-1] == "correct: True"


def test_traced_run_reports_the_cells_per_layer_metrics(root_with_cell,
                                                        recorded_trace):
    line, err = rehearse.run(root_with_cell, CELL, seed=47, trace=1)
    assert line["correct"] is True, err
    with open(os.path.join(rehearse.REPO, "BENCHMARK.json")) as f:
        want = {m["name"] for m in json.load(f)["per_layer"]
                if REAL in m["workloads"]}
    assert NEW | {"attention.window_layers_per_image",
                  "moe.buffer_fill_share", "head.fused_windows_per_image",
                  "program.mfu.featurize", "device.busy_mfu.featurize"} <= want
    assert "sequence.conv_layers_per_image" not in want
    assert not [name for name in want if name.startswith("ssm.")]
    assert set(line["metrics"]) == want
    value = {k: m["value"] for k, m in line["metrics"].items()}
    # five attention layers, each gated; layers 1-3 sliding; four expert
    # layers, each with its shared expert; a CPU lowers no kernel
    assert value["attention.gated_layers_per_image"] == 5
    assert value["moe.shared_expert_layers_per_image"] == 4
    assert value["attention.window_layers_per_image"] == 3
    assert value["attention.fused_layers_per_image"] == 0
    assert value["moe.fused_product_layers_per_image"] == 0
    assert value["head.fused_windows_per_image"] == 0
    # a launch of 2 windows of 32 has 256 pairs a layer, one row tile of the
    # grouped products, which each of its experts visits once
    assert 1 < value["moe.product_visits_per_tile"] <= 16
    assert value["moe.local_pairs_per_token"] == 4
    assert value["moe.buffer_fill_share"] == 100
    assert value["moe.overflow_pairs_per_row"] == 0
    assert value["sequence.tokens_per_launch"] == 64
    assert value["staging.h2d_bytes_per_image"] == 32 * 4


def test_the_controls_are_not_correct(root_with_cell):
    """The reference with float8 operands, the sampled rows swapped, and the
    reference without each of the model's four mechanisms, each put in the
    program's place, fail the cell's comparison."""
    cell, driver = make_driver(root_with_cell, 7)
    import check
    import control_laguna

    out = control_laguna.readings(driver, 0.01, lambda numbers: dict(zip(
        ("correct", "compared"),
        check.decide(numbers, cell.workload["limits"]))))
    assert out["sound"]["correct"] is True, out
    for control in ("control_fp8_reference", "fault_rows_swapped") + tuple(
            f"fault_no_{m}" for m in control_laguna.FAULTS):
        assert out[control]["correct"] is False, (control, out)
    # the four expert layers route; without the shared expert the first
    # still routes as the sound reference does (the shared expert acts
    # after its router)
    assert len(out["routing_agreement"]) == 4
    assert out["fault_no_shared_routing_agreement"][0] == 1.0
    assert out["reference_s"] >= 0


def test_the_drivers_weights_follow_the_reference_and_the_head_is_untied(
        root_with_cell):
    cell, driver = make_driver(root_with_cell, 3)
    from drivers import common

    driver.key = common.prng_key(3)
    variables = driver.make_variables()
    assert variables["head"] is not variables["embed"]
    assert variables["embed"].dtype.name == "bfloat16"
    layers = variables["layers"]
    assert [layer["attn"]["q"].shape[1] // 16 for layer in layers] == \
        cell.config["num_attention_heads_per_layer"] == [6, 8, 8, 8, 6]
    assert ["mlp" in layer for layer in layers] == [True] + [False] * 4
    assert all("shared" in layer["moe"] for layer in layers[1:])
    assert all(layer["attn"]["gate"].shape == (64, heads) for layer, heads
               in zip(layers, (6, 8, 8, 8, 6)))


def test_a_program_without_the_model_ends_before_any_weight(root_with_cell,
                                                            monkeypatch):
    """What the parent commit does with this cell's files: the driver's
    message, before a weight is drawn."""
    from sparkdl_tpu.models import registry

    monkeypatch.delitem(registry.SEQUENCE_MODELS, "TestLaguna")
    cell, driver = make_driver(root_with_cell, 5)
    monkeypatch.setattr(driver, "make_variables", lambda: pytest.fail(
        "weights drawn"))
    with pytest.raises(SystemExit, match="no sequence model 'TestLaguna'"):
        driver.setup()
