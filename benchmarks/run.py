#!/usr/bin/env python3
"""One run of one cell of the benchmark:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses anything but as many TPUs as the cell asks for, of a kind in the
peaks table; builds the inputs and weights from the seed; warms the cell's
shapes (set-up); measures for ``--seconds``; compares what the timed path
produced with the plain reference; prints one JSON object as the last line
of standard output. The only process that touches JAX is this one.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def place_compile_cache():
    """JAX's persistent compilation cache: where the environment says, else
    the fixed ``<checkout>/.jax_cache`` (the path is part of the cache's
    key). Set before JAX or the program is imported; the program takes the
    directory it is given."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(CHECKOUT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    place_compile_cache()
    for path in (HERE, CHECKOUT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import harness
    import peaks

    cell = harness.Cell(args.workload)          # unknown cell: exit, no JAX
    device, chip_peaks = peaks.require_tpu(cell.chips)
    harness.run_cell(args.workload, args.seed, args.seconds, args.trace,
                     device, chip_peaks, T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
