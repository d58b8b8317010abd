#!/usr/bin/env python3
"""``control_windows.py``'s readings for a cell of the ``windows_ling``
driver, and five more that belong to its model, taken on the chip at the
cell's own size (run by hand, on one chip):

    python3 benchmarks/control_ling.py --workload <cell> --seeds 1,2,3

After ``control_windows.readings`` (sound, the float8 control, the sampled
rows swapped), each through ``check.decide`` with the cell's limits, and the
seconds the sound reference's pass took: the reference with one of the
model's mechanisms left out, put in the program's place against the sound
reference — **the hand-over of the state** between chunks, the state reset
every 64 positions (``fault_no_carry``), **the delta rule's removal**
``−β k kᵀ`` (``fault_no_delta``), **the decay by channel**, a head's mean in
its place (``fault_no_decay``), **the groups** of the routing, the top 8 over
all 512 experts (``fault_no_groups``), and **the interleaved rotary**, a
head's halves paired (``fault_no_interleave``). A program that lacked any of
them would read the same. One JSON line per seed.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

FAULTS = ("carry", "delta", "decay", "groups", "interleave")


def readings(driver, seconds, decide):
    import control_windows

    out = control_windows.readings(driver, seconds, decide)
    t0 = time.perf_counter()
    want = driver.reference_outputs()
    out["reference_s"] = round(time.perf_counter() - t0, 1)
    for mechanism in FAULTS:
        fault = driver.reference_outputs(without=mechanism)
        out[f"fault_no_{mechanism}"] = decide(driver.numbers(
            [fault[:2]], want[:2]))
        out[f"fault_no_{mechanism}_routing_agreement"] = \
            driver.routing_agreement(fault[2], want[2])
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
