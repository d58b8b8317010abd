"""The ``windows_ssm`` driver end to end at TestStateSpace size, dropped into
a copy of the benchmark as new files: set-up (a layer drawn with its mixer
given, the head tied, a warm-up that asks for no expert counts), window,
check, result line; a traced run's per-layer metrics, the three this cell
brought among them; the new ``kernel_roofline`` reader on a made-up
reduction; the controls through the cell's limits — the float8 reference, the
sampled rows swapped, and the two that belong to the model: the state not
handed over, the inner norms left out; the hand counts of ``flops_ssm.py``.
``rehearse_cells.STAND_IN`` knows the cells of PR 32's day, so the cell is
added here to the root that ``make_root`` made (the model's own tests are in
tests/models/test_state_space.py)."""

import json
import os
import types

import pytest
import rehearse

CELL = "teststatespace-windows.windows16k"
REAL = "jamba2-3b.windows16k"


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
            "name": "teststatespace-windows", "source": "rehearsal",
            "reduced": [], "why": "rehearsal",
            "file": "benchmarks/configs/teststatespace-windows.json"})
        bench["workloads"].append({
            "name": CELL, "config": "teststatespace-windows",
            "traffic": "windows16k", "chips": 1, "why": "rehearsal"})
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
    line, err = rehearse.run(root_with_cell, CELL, seed=2**31 + 31)
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] % 4 == 0
    assert set(line["metrics"]) == {"featurize_images_per_s", "setup_s"}
    assert line["facts"]["compiles_in_window"] == 0
    assert set(line["compared"]) == {"pooled_angle_gap", "logprob_gap_p50",
                                     "logprob_gap_p90"}
    assert "routing agreement" not in capsys.readouterr().err
    assert err.strip().splitlines()[-1] == "correct: True"


def test_traced_run_reports_the_cells_per_layer_metrics(root_with_cell,
                                                        recorded_trace):
    line, err = rehearse.run(root_with_cell, CELL, seed=43, trace=1)
    assert line["correct"] is True, err
    with open(os.path.join(rehearse.REPO, "BENCHMARK.json")) as f:
        want = {m["name"] for m in json.load(f)["per_layer"]
                if REAL in m["workloads"]}
    assert {"ssm.layers_per_image", "ssm.fused_scan_layers_per_image",
            "ssm.scan_roofline_share", "attention.fused_layers_per_image",
            "head.fused_windows_per_image", "program.mfu.featurize",
            "device.busy_mfu.featurize"} <= want
    assert not [name for name in want if name.startswith("moe.")]
    assert "sequence.conv_layers_per_image" not in want
    # the recorded trace is an image model's: it holds no scan, so that one
    # metric has nothing to read here (the next test gives it something)
    assert set(line["metrics"]) == want - {"ssm.scan_roofline_share"}
    value = {k: m["value"] for k, m in line["metrics"].items()}
    # layers 0, 2 and 3 of the four are state-space ones; a CPU lowers no
    # kernel: neither scan, attention nor head
    assert value["ssm.layers_per_image"] == 3
    assert value["ssm.fused_scan_layers_per_image"] == 0
    assert value["attention.fused_layers_per_image"] == 0
    assert value["head.fused_windows_per_image"] == 0
    assert value["sequence.tokens_per_launch"] == 128
    assert value["staging.h2d_bytes_per_image"] == 128 * 4
    assert value["collect.vectorized_values_per_image"] == 128 + 64 + 128


def test_kernel_roofline_reader_on_a_made_up_reduction(root_with_cell):
    """Two call sites among the ten, one traced pass of 4 windows: the least
    seconds of 8 calls over the seconds they took; the bound is the larger of
    the two; nothing to read gives None."""
    import harness

    cell = harness.Cell(CELL, root_with_cell)
    reader = harness.by_name("readers", "kernel_roofline", root_with_cell)
    spec = cell.reader_spec("ssm.scan_roofline_share")["reader"]
    assert spec["op_prefix"] == "%selective_scan"
    ops = 7 * 128 * 128 * 16                    # flops_ssm.scan_kernel_ops
    moved = 128 * (128 * 10 + 2 * 16 * 4)       # flops_ssm.scan_kernel_bytes
    peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
    least = max(ops, moved) / 1e9
    assert least == ops / 1e9
    trace = {"device_ops": [
        ["%fusion.1 = f32[128,64]", 0.5],
        ["%selective_scan.3 = (bf16[128,128], f32[16,128]) custom-call", 0.02],
        ["%selective_scan.5 = (bf16[128,128], f32[16,128]) custom-call", 0.03],
    ]}
    run = {"trace": trace, "window": {"traced_images": 4}, "cell": cell,
           "peaks": peaks}
    assert reader.read(spec, run) == pytest.approx(
        100 * least * 2 * 4 / 0.05)
    slow_memory = dict(peaks, hbm_bytes_per_s=1e3)
    assert reader.read(spec, dict(run, peaks=slow_memory)) == pytest.approx(
        100 * (moved / 1e3) * 2 * 4 / 0.05)
    assert reader.read(spec, dict(run, trace={"device_ops": [
        ["%fusion.1", 0.5]]})) is None
    assert reader.read(spec, dict(run, trace=None)) is None
    assert reader.read(spec, dict(run, window={"traced_images": 0})) is None


def test_the_controls_are_not_correct(root_with_cell):
    """The reference with float8 operands, the sampled rows swapped, the
    reference whose state is not handed over and the reference without its
    inner norms, each put in the program's place, fail the cell's
    comparison."""
    cell, driver = make_driver(root_with_cell, 7)
    import check
    import control_ssm

    out = control_ssm.readings(driver, 0.01, lambda numbers: dict(zip(
        ("correct", "compared"),
        check.decide(numbers, cell.workload["limits"]))))
    assert out["sound"]["correct"] is True, out
    for control in ("control_fp8_reference", "fault_rows_swapped",
                    "fault_inner_norms"):
        assert out[control]["correct"] is False, (control, out)
    # a window of 128 has no position 256 to reset the state at: at this size
    # the fault is the sound reference (tests/models/test_state_space.py
    # resets every 16 and fails it)
    assert out["fault_carry"]["correct"] is True
    assert all(value == 0.0
               for value, _ in out["fault_carry"]["compared"].values())
    # the embedding and one reading after each of the four layers
    assert len(out["stream_rms"]) == 5 and out["reference_s"] >= 0


def test_the_drivers_weights_follow_the_reference_and_the_head_is_tied(
        root_with_cell):
    cell, driver = make_driver(root_with_cell, 3)
    from drivers import common

    driver.key = common.prng_key(3)
    variables = driver.make_variables()
    assert variables["head"] is variables["embed"]
    assert variables["embed"].dtype.name == "bfloat16"
    assert variables["embed"].shape == (64, 64)
    assert ["ssm" in layer for layer in variables["layers"]] == [
        True, False, True, True]
    assert all("mlp" in layer and "moe" not in layer
               for layer in variables["layers"])
    assert "attn" in variables["layers"][1]
    assert "q_norm" not in variables["layers"][1]["attn"]


def test_a_program_without_the_model_ends_before_any_weight(root_with_cell,
                                                            monkeypatch):
    """What the parent commit does with this cell's files: the driver's
    message, before a weight is drawn."""
    from sparkdl_tpu.models import registry

    monkeypatch.delitem(registry.SEQUENCE_MODELS, "TestStateSpace")
    cell, driver = make_driver(root_with_cell, 5)
    monkeypatch.setattr(driver, "make_variables", lambda: pytest.fail(
        "weights drawn"))
    with pytest.raises(SystemExit,
                       match="no sequence model 'TestStateSpace'"):
        driver.setup()


@pytest.mark.parametrize("window", [16, 40])
def test_flops_ssm_against_a_hand_count(root_with_cell, window):
    import flops_ssm
    import harness

    config = harness.Cell(CELL, root_with_cell).config
    mixer = 64 * 256 + 128 * (8 + 32) + 8 * 128 + 128 * 64 + 128 * 4
    attention = 2 * 64 * 64 + 2 * 64 * 16
    mlp = 3 * 64 * 128
    pairs = window * (window + 1) // 2
    scan = 7 * window * 128 * 16
    assert flops_ssm.scan_kernel_ops(config, window) == scan
    assert flops_ssm.scan_kernel_bytes(config, window) == window * (
        128 * 10 + 2 * 16 * 4)
    assert flops_ssm.window_flops(config, window) == 2 * (
        window * (3 * mixer + attention + 4 * mlp) + 4 * pairs * 2 * 16
        + (window - 1) * 64 * 64) + 3 * scan
