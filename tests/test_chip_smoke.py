"""CPU rehearsal of chip_smoke.py (the on-chip bring-up proof).

The script itself refuses the CPU, so its phases are functions of a model
name and sizes: here they run with TestNet and a handful of rows, which
finds wrong paths, arguments and control flow before a chip call does.
Nothing here is a device measurement.
"""

import json

import jax
import numpy as np
import pytest

import chip_smoke
from sparkdl_tpu.core import batching, executor
from sparkdl_tpu.engine.dataframe import EngineConfig


@pytest.fixture(autouse=True)
def _library_defaults():
    """The smoke runs at library defaults; the suite's conftest pins fp32 +
    pow2 for bit-identity, so put bf16 + the tuned ladder back here."""
    saved = EngineConfig.snapshot()
    EngineConfig.inference_precision = "bfloat16"
    EngineConfig.bucket_ladder = "tuned"
    executor.reset()
    batching.reset_planners()
    yield
    executor.reset()
    batching.reset_planners()
    EngineConfig.restore(saved)


@pytest.fixture(scope="module")
def meter():
    return chip_smoke.CompileMeter()


def test_refuses_the_cpu_before_building_anything(capsys):
    with pytest.raises(SystemExit) as err:
        chip_smoke.main([])
    assert "needs a TPU" in str(err.value)
    assert err.value.code != 0
    assert capsys.readouterr().out == ""  # no result line, no phase line


def test_refuses_a_chip_count_it_was_not_asked_for(monkeypatch):
    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    assert chip_smoke.require_tpu(1) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(SystemExit, match="for 4 chip"):
        chip_smoke.require_tpu(4)


def test_one_chip_phases_on_testnet(meter, capsys):
    facts, batch, features = chip_smoke.phase_featurize(
        "TestNet", 12, 8, 0, "cpu", around=(40, 56))
    assert facts["feature_shape"] == [12, 16]
    assert facts["decode_path"] in ("native library", "PIL")
    assert facts["compile_spans"] >= 1
    assert batch.shape == (12, 32, 32, 3) and batch.dtype == np.uint8

    served = chip_smoke.run_phase(
        meter, "serving", chip_smoke.phase_serving, "TestNet",
        batch, features, 8, "cpu")
    assert served["evictions"] == 1 and served["model_load_spans"] >= 2

    trained = chip_smoke.run_phase(
        meter, "train", chip_smoke.phase_train, "TestNet", 8, 3, 0, "cpu")
    assert trained["steps"] == 3 and trained["param_leaves_changed"] >= 1

    lines = [json.loads(line)
             for line in capsys.readouterr().out.splitlines()]
    assert [line["phase"] for line in lines] == ["serving", "train"]
    assert all(line["ok"] and "compile_seconds" in line for line in lines)


def test_failed_phase_prints_not_ok_and_reraises(meter, capsys):
    def boom():
        raise ValueError("nope")

    with pytest.raises(ValueError, match="nope"):
        chip_smoke.run_phase(meter, "boom", boom)
    line = json.loads(capsys.readouterr().out)
    assert line["phase"] == "boom" and line["ok"] is False
    assert line["error"] == "ValueError: nope"


def test_mesh_phases_on_virtual_devices():
    """The --chips 4 path, on the suite's eight virtual CPU devices: batch
    shards on distinct devices, variables on all of them, mesh == one
    device."""
    n = len(jax.devices())
    feat = chip_smoke.phase_mesh_featurize("TestNet", 2 * n, n, 0, n,
                                           around=(40, 56))
    assert len(feat["batch_shard_devices"]) == n
    assert feat["rows_per_shard"] == 1
    trained = chip_smoke.phase_mesh_train("TestNet", 2 * n, 0, n)
    assert len(trained["batch_shard_devices"]) == n
    # TestNet is well conditioned: float32 data parallelism is exact to the
    # MLP tolerance here (ResNet50 on the chip is not — PERF.md)
    assert trained["float32"]["leaves_outside_mlp_dp_tolerance"] == 0


def test_mesh_train_refuses_a_step_that_ignores_most_of_the_batch(
        monkeypatch):
    """What the noise-floor bound is for: a "mesh" step that trains on one
    shard only is off by the size of the update, far above the floor."""
    from sparkdl_tpu.train import Trainer

    real_fit = Trainer.fit

    def one_shard_fit(self, state, batches, **kwargs):
        if self.mesh is not None:
            batches = [(x[:len(x) // 4].repeat(4, axis=0),
                        y[:len(y) // 4].repeat(4, axis=0))
                       for x, y in batches]
        return real_fit(self, state, batches, **kwargs)

    monkeypatch.setattr(Trainer, "fit", one_shard_fit)
    n = len(jax.devices())
    with pytest.raises(AssertionError, match="the noise floor is"):
        chip_smoke.phase_mesh_train("TestNet", 2 * n, 0, n)
