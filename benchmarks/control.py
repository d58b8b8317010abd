#!/usr/bin/env python3
"""Readings for the limits of ``correct``, taken on the chip at a cell's own
size (run by hand through the chip tool; the benchmark's runs never run it):

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 [--seconds 5]

For every seed, in one process, each of these goes through the harness's own
comparison, ``check.decide`` with the cell's limits, and is printed as
``{"correct": …, "compared": {number: [value, limit]}}``: the sound program
against the plain reference (``sound``: the lower readings); the control —
the reference with float8 operands put in the program's place and, for a
featurize cell, the program's own ``inference_precision="int8"`` path; and
the faults the cell can have — rows altered where they are produced
(featurize), half of the batch left out with the mean taken over the rest,
planted in the reference put in the program's place, and a state returned
unchanged (training). One JSON line per seed.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def featurize_readings(driver, seconds, decide):
    import numpy as np

    import harness
    from drivers import common
    from references import plain
    from sparkdl_tpu.core import executor
    from sparkdl_tpu.engine.dataframe import EngineConfig

    driver.setup()
    driver.measure(seconds, harness.Tracer(False))
    pixels = driver.arrays[driver.sample]
    block = driver.traffic.get("check_block", 64)
    want = common.reference_features(driver.reference, driver.variables,
                                     pixels, block)
    out = {"sound": decide(driver.numbers(driver.samples, want))}
    control = common.reference_features(driver.reference, driver.variables,
                                        pixels, block,
                                        quant=plain.fp8_operands)
    out["control_fp8_reference"] = decide(driver.numbers([control], want))

    def one_pass():
        driver.samples = []
        driver._pass()
        return decide(driver.numbers(driver.samples, want))

    saved = EngineConfig.inference_precision
    EngineConfig.inference_precision = "int8"
    try:
        out["control_int8_program"] = one_pass()
    finally:
        EngineConfig.inference_precision = saved

    real = executor.execute

    def altered(*args, **kwargs):
        rows = np.array(real(*args, **kwargs))
        rows[::16] = rows[::16] * 1.5 + 0.25    # every 16th row of a launch
        return rows

    executor.execute = altered
    try:
        out["fault_altered_rows"] = one_pass()
    finally:
        executor.execute = real
    driver.release()
    return out


def fit_readings(driver, seconds, decide):
    import numpy as np

    from references import plain

    driver.setup()
    got = driver.program_readings()
    want = driver.reference_readings()
    numbers, where = driver.numbers(got, want)
    out = {"sound": decide(numbers), "where": where}
    driver.release()
    control = driver.reference_readings(quant=plain.fp8_operands)
    out["control_fp8_reference"] = decide(driver.numbers(control, want)[0])
    full = driver.batches
    driver.batches = [(np.concatenate([x[:len(x) // 2]] * 2),
                       np.concatenate([y[:len(y) // 2]] * 2))
                      for x, y in full]
    try:
        half = driver.reference_readings()
    finally:
        driver.batches = full
    out["fault_half_batch"] = decide(driver.numbers(half, want)[0])
    still = dict(got, grad=dict.fromkeys(got["grad"], 0.0),
                 update=dict.fromkeys(got["update"], 0.0))
    out["fault_state_unchanged"] = decide(driver.numbers(still, want)[0])
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args(argv)

    sys.path[:0] = [p for p in (HERE, CHECKOUT) if p not in sys.path]
    import run as run_module

    run_module.place_compile_cache()
    import check
    import harness
    import peaks

    cell = harness.Cell(args.workload)
    device, chip_peaks = peaks.require_tpu(cell.chips)
    module = harness.by_name("drivers", cell.config["entry"])
    read = {"featurize": featurize_readings, "fit": fit_readings}[
        cell.config["entry"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        driver = module.Driver(cell, seed, {"peaks": chip_peaks,
                                            "device": device,
                                            "root": harness.ROOT})
        out = read(driver, args.seconds, lambda numbers: dict(zip(
            ("correct", "compared"),
            check.decide(numbers, cell.workload["limits"]))))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": round(time.perf_counter() - t0, 1),
                          **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
