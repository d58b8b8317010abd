"""The ``windows_span`` driver end to end at TestSpanMoE size, dropped into a
copy of the benchmark as new files: set-up (a layer drawn whatever its kind,
the head untied), window, check, result line; a traced run's per-layer
metrics, the two this cell brought among them; the controls through the
cell's limits — the float8 reference, the sampled rows swapped, and the two
that belong to the model: the span left out, the plain rotary in the full
layers. ``rehearse_cells.STAND_IN`` knows the cells of PR 32's day, so the
cell is added here to the root that ``make_root`` made (the model's own tests
and the FLOP count by hand are in tests/models/test_span_moe.py)."""

import json
import os

import pytest
import rehearse

CELL = "testspan-windows.windows"
REAL = "mellum2-12b-instruct.windows16k"


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
            "name": "testspan-windows", "source": "rehearsal",
            "reduced": [], "why": "rehearsal",
            "file": "benchmarks/configs/testspan-windows.json"})
        bench["workloads"].append({
            "name": CELL, "config": "testspan-windows",
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
    line, err = rehearse.run(root_with_cell, CELL, seed=2**31 + 29)
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
    line, err = rehearse.run(root_with_cell, CELL, seed=41, trace=1)
    assert line["correct"] is True, err
    with open(os.path.join(rehearse.REPO, "BENCHMARK.json")) as f:
        want = {m["name"] for m in json.load(f)["per_layer"]
                if REAL in m["workloads"]}
    assert {"attention.window_layers_per_image",
            "attention.scored_keys_per_token", "moe.buffer_fill_share",
            "program.mfu.featurize", "device.busy_mfu.featurize"} <= want
    assert "sequence.conv_layers_per_image" not in want
    assert set(line["metrics"]) == want
    value = {k: m["value"] for k, m in line["metrics"].items()}
    # four of the five held layers are sliding ones; the blocked path (a CPU)
    # scores, a block of 8 queries at a time, the block and the 7 keys before
    # it there, and each block's whole prefix in the full layer
    assert value["attention.window_layers_per_image"] == 4
    assert value["attention.scored_keys_per_token"] == (
        4 * 8 * (8 + 3 * 15) + 8 * (8 + 16 + 24 + 32)) / 32
    assert value["attention.fused_layers_per_image"] == 0
    # every expert is held: a token's four pairs stay, and the buffer is them
    assert value["moe.local_pairs_per_token"] == 4
    assert value["moe.buffer_fill_share"] == 100
    assert value["moe.overflow_pairs_per_row"] == 0
    assert value["moe.expert_load_max_over_mean"] >= 1.0
    assert value["moe.fused_product_layers_per_image"] == 0
    assert value["sequence.tokens_per_launch"] == 32
    assert value["staging.h2d_bytes_per_image"] == 32 * 4
    assert value["collect.vectorized_values_per_image"] == 64 + 32 + 32


def test_the_controls_are_not_correct(root_with_cell):
    """The reference with float8 operands, the sampled rows swapped, the
    reference without the span and the reference without YaRN, each put in
    the program's place, fail the cell's comparison."""
    cell, driver = make_driver(root_with_cell, 7)
    import check
    import control_span

    out = control_span.readings(driver, 0.01, lambda numbers: dict(zip(
        ("correct", "compared"),
        check.decide(numbers, cell.workload["limits"]))))
    assert out["sound"]["correct"] is True, out
    for control in ("control_fp8_reference", "fault_rows_swapped",
                    "fault_no_span", "fault_no_yarn"):
        assert out[control]["correct"] is False, (control, out)
    assert len(out["routing_agreement"]) == 5
    assert len(out["fault_no_span_routing_agreement"]) == 5
    # the first layer routes before any attention differs... after one: the
    # span's absence is seen from the first layer on, YaRN's from the fourth
    assert out["fault_no_yarn_routing_agreement"][:3] == [1.0, 1.0, 1.0]
    assert out["fault_no_yarn_routing_agreement"][3] < 1.0


def test_the_drivers_weights_follow_the_reference_and_the_head_is_untied(
        root_with_cell):
    cell, driver = make_driver(root_with_cell, 3)
    from drivers import common

    driver.key = common.prng_key(3)
    variables = driver.make_variables()
    assert variables["head"] is not variables["embed"]
    assert variables["embed"].dtype.name == "bfloat16"
    assert variables["head"].shape == variables["embed"].shape == (32, 64)
    assert len(variables["layers"]) == len(cell.config["layer_types"]) == 5
    assert all("attn" in layer and "moe" in layer and "q_norm" not in
               layer["attn"] for layer in variables["layers"])


def test_a_program_without_the_model_ends_before_any_weight(root_with_cell,
                                                            monkeypatch):
    """What the parent commit does with this cell's files: the driver's
    message, before a weight is drawn."""
    from sparkdl_tpu.models import registry

    monkeypatch.delitem(registry.SEQUENCE_MODELS, "TestSpanMoE")
    cell, driver = make_driver(root_with_cell, 5)
    monkeypatch.setattr(driver, "make_variables", lambda: pytest.fail(
        "weights drawn"))
    with pytest.raises(SystemExit, match="no sequence model 'TestSpanMoE'"):
        driver.setup()
