"""Every cell end to end at TestNet size: set-up, window, check, result
line — with the look for a chip skipped and nothing else."""

import json
import os

import pytest

import rehearse

E2E = {"testnet-featurize.arrays": "featurize_images_per_s",
       "testnet-train.fit": "fit_images_per_s"}


@pytest.mark.parametrize("cell", sorted(E2E))
def test_cell_runs_and_is_correct(root, cell):
    line, err = rehearse.run(root, cell, seed=2**31 + 17)
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {E2E[cell], "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["facts"]["compiles_in_window"] == 0
    assert list(line)[-1] == "compared"
    # each number compared is printed beside its limit, and last
    assert err.strip().splitlines()[-1] == "correct: True"
    for name, (value, limit) in line["compared"].items():
        assert f"compared {name}: {value!r} limit {limit!r}" in err


def test_same_seed_same_inputs(root):
    import traffic

    a = traffic.image_arrays({"n": 8, "size": [32, 32]}, 2**31 + 5)
    b = traffic.image_arrays({"n": 8, "size": [32, 32]}, 2**31 + 5)
    c = traffic.image_arrays({"n": 8, "size": [32, 32]}, 2**31 + 6)
    assert (a == b).all() and not (a == c).all()


@pytest.mark.parametrize("cell", sorted(E2E))
def test_traced_run_reports_the_cells_per_layer_metrics(root, cell,
                                                        recorded_trace):
    line, err = rehearse.run(root, cell, seed=23, trace=1)
    assert line["correct"] is True, err
    want = {m["name"] for m in json.load(open(os.path.join(
        os.path.dirname(root), "BENCHMARK.json")))["per_layer"]
        if cell in m["workloads"]}
    assert set(line["metrics"]) == want
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    for name, m in line["metrics"].items():
        assert m["value"] > 0 or "wait" in name, (name, m)
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10


def test_a_traced_featurize_run_traces_the_cells_whole_passes(
        root, recorded_trace):
    """The traced part is the cell's ``trace_passes`` whole passes after one
    settled pass, however short the window."""
    line, err = rehearse.run(root, "testnet-featurize.arrays", seed=29,
                             seconds=0.001, trace=1)
    assert line["facts"]["traced_images"] == 2 * 48, err
    assert line["attempted"] == 3 * 48
    line, _ = rehearse.run(root, "testnet-featurize.arrays", seed=29,
                           seconds=0.001)
    assert line["attempted"] == 48 and line["facts"]["traced_images"] == 0


def test_no_tpu_no_result(capsys):
    """The entry point refuses the CPU before anything is built and prints
    no result line."""
    import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "inceptionv3-featurize.arrays", "--seed",
                  "1", "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
