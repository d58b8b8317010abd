"""``correct`` has been shown to fail: the control (the reference in the
nearest lower precision put in the program's place) and each fault a cell can
have, planted under the timed path, at a size a test run can hold. On the
chip, at the cells' own sizes, ``control.py`` reads the same numbers (PERF.md
lists them)."""

import numpy as np
import pytest

import rehearse


def _driver(root, cell_name, seed):
    import sys

    for path in (root, rehearse.REPO):
        if path not in sys.path:
            sys.path.insert(0, path)
    import harness

    cell = harness.Cell(cell_name, root)
    module = harness.by_name("drivers", cell.config["entry"], root)
    return cell, module.Driver(cell, seed, {"peaks": rehearse.PEAKS,
                                            "device": rehearse.DEVICE,
                                            "root": root})


# -- the control ---------------------------------------------------------------


@pytest.mark.parametrize("seed", [3, 2**31 + 4, 5])
def test_featurize_control_is_not_correct(root, seed):
    import check
    import harness
    from drivers import common
    from references import plain

    cell, driver = _driver(root, "testnet-featurize.arrays", seed)
    driver.setup()
    driver.measure(0.2, harness.Tracer(False))
    pixels = driver.arrays[driver.sample]
    want = common.reference_features(driver.reference, driver.variables,
                                     pixels, 8)
    control = common.reference_features(driver.reference, driver.variables,
                                        pixels, 8, quant=plain.fp8_operands)
    limits = cell.workload["limits"]
    assert check.decide(driver.numbers(driver.samples, want), limits)[0]
    assert not check.decide(driver.numbers([control], want), limits)[0]


@pytest.mark.parametrize("seed", [3, 2**31 + 4, 5])
def test_fit_control_is_not_correct(root, seed):
    import check
    from references import plain

    cell, driver = _driver(root, "testnet-train.fit", seed)
    driver.setup()
    want = driver.reference_readings()
    limits = cell.workload["limits"]
    assert check.decide(driver.numbers(driver.program_readings(), want)[0],
                        limits)[0]
    control = driver.reference_readings(quant=plain.fp8_operands)
    assert not check.decide(driver.numbers(control, want)[0], limits)[0]


# -- faults planted under the timed path -----------------------------------------


def test_fault_an_answer_altered_where_it_is_produced(root, monkeypatch):
    from sparkdl_tpu.core import executor

    real = executor.execute

    def altered(*args, **kwargs):
        out = np.array(real(*args, **kwargs))
        out[0] = out[0] * 1.5 + 0.25        # one row of every launch
        return out

    monkeypatch.setattr(executor, "execute", altered)
    line, err = rehearse.run(root, "testnet-featurize.arrays", seed=9)
    assert line["correct"] is False, err


def _plant_in_step(monkeypatch, wrap):
    from sparkdl_tpu.train import Trainer

    real = Trainer.make_train_step

    def make(self, *args, **kwargs):
        return wrap(real(self, *args, **kwargs))

    monkeypatch.setattr(Trainer, "make_train_step", make)


def test_fault_a_step_that_returns_its_state_unchanged(root, monkeypatch):
    def wrap(step):
        def unchanged(state, x, y):
            new, metrics = step(state, x, y)
            return state.replace(step=new.step), metrics
        return unchanged

    # donation would delete the state we hand back: build it without
    from sparkdl_tpu.train import Trainer
    real = Trainer.make_train_step
    monkeypatch.setattr(
        Trainer, "make_train_step",
        lambda self, donate=True: wrap(real(self, donate=False)))
    line, err = rehearse.run(root, "testnet-train.fit", seed=10)
    assert line["correct"] is False, err
    assert line["compared"]["update_norm_gap"][0] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(root, monkeypatch):
    import jax.numpy as jnp

    def wrap(step):
        def half(state, x, y):
            n = x.shape[0] // 2
            return step(state, jnp.concatenate([x[:n], x[:n]]),
                        jnp.concatenate([y[:n], y[:n]]))
        return half

    _plant_in_step(monkeypatch, wrap)
    line, err = rehearse.run(root, "testnet-train.fit", seed=11)
    assert line["correct"] is False, err
