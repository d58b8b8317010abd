"""The ``windows_ling`` driver end to end at TestLing size, dropped into a
copy of the benchmark as new files: set-up (a layer drawn with its kind —
linear or latent attention — and whether it is dense given, the head
untied), window, check, result line; a traced run's per-layer metrics, the
three this cell brought among them; the recurrence kernel's share of its
roofline as the ``kernel_roofline`` reader would read it; the controls through the cell's limits —
the float8 reference, the sampled rows swapped, and the five that belong to
the model: the state reset at each chunk, the delta rule's removal left out,
the decay a head's mean, routing over all experts, the rotary by halves.
``rehearse_cells.STAND_IN`` knows the cells it was written with, so the cell
is added here to the root that ``make_root`` made (the model's own tests and
the FLOP count by hand are in tests/models/test_ling.py)."""

import json
import os

import pytest
import rehearse

CELL = "testling-windows.windows"
REAL = "ling-3.0-flash-ep4.windows16k"
NEW = {"linear_attention.layers_per_image",
       "linear_attention.fused_layers_per_image",
       "moe.group_limited_layers_per_image"}


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
            "name": "testling-windows", "source": "rehearsal",
            "reduced": [], "why": "rehearsal",
            "file": "benchmarks/configs/testling-windows.json"})
        bench["workloads"].append({
            "name": CELL, "config": "testling-windows",
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
    assert NEW | {"moe.shared_expert_layers_per_image",
                  "moe.buffer_fill_share", "head.fused_windows_per_image",
                  "attention.fused_layers_per_image",
                  "program.mfu.featurize", "device.busy_mfu.featurize"} <= want
    assert not {"attention.window_layers_per_image",
                "sequence.conv_layers_per_image"} & want
    assert set(line["metrics"]) == want
    value = {k: m["value"] for k, m in line["metrics"].items()}
    # six linear-attention layers and one latent; six expert layers, each
    # routing within groups beside its shared expert; a CPU lowers no kernel
    assert value["linear_attention.layers_per_image"] == 6
    assert value["linear_attention.fused_layers_per_image"] == 0
    assert value["moe.group_limited_layers_per_image"] == 6
    assert value["moe.shared_expert_layers_per_image"] == 6
    assert value["attention.fused_layers_per_image"] == 0
    assert value["moe.fused_product_layers_per_image"] == 0
    assert value["head.fused_windows_per_image"] == 0
    assert value["moe.local_pairs_per_token"] == 4
    assert value["moe.buffer_fill_share"] == 100
    assert value["moe.overflow_pairs_per_row"] == 0
    assert value["sequence.tokens_per_launch"] == 256
    assert value["staging.h2d_bytes_per_image"] == 128 * 4


def test_the_roofline_share_reads_the_kernels_calls(root_with_cell):
    """The recurrence kernel's share of its roofline by the ``kernel_roofline``
    reader with ``flops_ling``'s counts, over a hand-made trace: one call
    site taking 4 × its least time reads 25 %. (No metric reads it yet: on
    the chip no call is among the ten ops the trace reduction keeps,
    PERF.md §7 row 15.)"""
    import flops_ling
    import harness

    cell = harness.Cell(CELL, root_with_cell)
    spec = {"reader": {"kind": "kernel_roofline",
                       "op_prefix": "%fused_delta_rule",
                       "module": "flops_ling", "ops": "delta_kernel_ops",
                       "bytes": "delta_kernel_bytes"}}
    window = cell.traffic["window"]
    least = max(flops_ling.delta_kernel_ops(cell.config, window)
                / rehearse.PEAKS["bf16_flops_per_s"],
                flops_ling.delta_kernel_bytes(cell.config, window)
                / rehearse.PEAKS["hbm_bytes_per_s"])
    run = {"cell": cell, "window": {"traced_images": 2}, "peaks":
           rehearse.PEAKS, "trace": {"device_ops": [
               ["%fused_delta_rule.3", 2 * 4 * least], ["%fusion.1", 1.0]]}}
    reader = harness.by_name("readers", spec["reader"]["kind"],
                             root_with_cell)
    assert reader.read(spec["reader"], run) == pytest.approx(25.0)
    run["trace"]["device_ops"] = [["%fusion.1", 1.0]]
    assert reader.read(spec["reader"], run) is None


def test_the_controls_are_not_correct(root_with_cell):
    """The reference with float8 operands, the sampled rows swapped, and the
    reference without each of the model's five mechanisms, each put in the
    program's place, fail the cell's comparison."""
    cell, driver = make_driver(root_with_cell, 7)
    import check
    import control_ling

    out = control_ling.readings(driver, 0.01, lambda numbers: dict(zip(
        ("correct", "compared"),
        check.decide(numbers, cell.workload["limits"]))))
    assert out["sound"]["correct"] is True, out
    for control in ("control_fp8_reference", "fault_rows_swapped") + tuple(
            f"fault_no_{m}" for m in control_ling.FAULTS):
        assert out[control]["correct"] is False, (control, out)
    # six expert layers route; the faults of the mixers leave the first
    # expert layer's routing alone only where they change nothing before it
    # — the rotary by halves is in the latent layer, the fifth held one
    assert len(out["routing_agreement"]) == 6
    assert out["fault_no_interleave_routing_agreement"][:4] == [1.0] * 4
    assert min(out["fault_no_groups_routing_agreement"]) < 0.95
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
    assert ["kda" if "kda" in layer else "mla" for layer in layers] == \
        ["kda"] * 5 + ["mla", "kda"]
    assert ["mlp" in layer for layer in layers] == [True] + [False] * 6
    assert all({"shared", "expert_bias"} <= set(layer["moe"])
               for layer in layers[1:])
    assert layers[5]["mla"]["q"].shape == (64, 4 * (16 + 8))
    assert layers[0]["kda"]["beta"].shape == (64, 4)


def test_a_program_without_the_model_ends_before_any_weight(root_with_cell,
                                                            monkeypatch):
    """What the parent commit does with this cell's files: the driver's
    message, before a weight is drawn."""
    from sparkdl_tpu.models import registry

    monkeypatch.delitem(registry.SEQUENCE_MODELS, "TestLing")
    cell, driver = make_driver(root_with_cell, 5)
    monkeypatch.setattr(driver, "make_variables", lambda: pytest.fail(
        "weights drawn"))
    with pytest.raises(SystemExit, match="no sequence model 'TestLing'"):
        driver.setup()
