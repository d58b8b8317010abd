#!/usr/bin/env python3
"""Readings for the limits of ``correct`` of a cell of the ``windows_ssm``
driver, taken on the chip at the cell's own size (run by hand through the
chip tool; beside ``control_windows.py`` and ``control_span.py``, whose cells
have expert layers and a routing to agree on):

    python3 benchmarks/control_ssm.py --workload <cell> --seeds 1,2,3

For every seed, in one process, each of these goes through the harness's own
comparison, ``check.decide`` with the cell's limits: the sound program
against the plain reference (``sound``, with the seconds the reference's pass
took and the residual stream's root mean square layer by layer beside it);
the control — the reference with float8 operands put in the program's place;
the sampled rows handed back in each other's place; and the two faults that
belong to this model, each the reference with one thing left out put in the
program's place: **the state reset to zero every 256 positions**
(``fault_carry``: what a blocked scan computes when it drops the hand-over
between its blocks) and **δ, B and C without their norms**
(``fault_inner_norms``). One JSON line per seed.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def readings(driver, seconds, decide):
    import harness
    from references import plain

    driver.setup()
    driver.measure(seconds, harness.Tracer(False))
    samples = driver.samples
    driver.release()
    stream = []
    t0 = time.perf_counter()
    want = driver.reference_outputs(stream=stream)
    out = {"sound": decide(driver.numbers(samples, want)),
           "reference_s": round(time.perf_counter() - t0, 1),
           "stream_rms": [round(v, 3) for v in stream]}
    out["control_fp8_reference"] = decide(driver.numbers(
        [driver.reference_outputs(quant=plain.fp8_operands)], want))
    out["fault_rows_swapped"] = decide(driver.numbers(
        [tuple(a[::-1] for a in samples[-1])], want))
    for thing in ("carry", "inner_norms"):
        out[f"fault_{thing}"] = decide(driver.numbers(
            [driver.reference_outputs(without=thing)], want))
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
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        driver = module.Driver(cell, seed, {"peaks": chip_peaks,
                                            "device": device,
                                            "root": harness.ROOT})
        out = readings(driver, args.seconds, lambda numbers: dict(zip(
            ("correct", "compared"),
            check.decide(numbers, cell.workload["limits"]))))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": round(time.perf_counter() - t0, 1),
                          **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
