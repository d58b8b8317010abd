"""Rehearsal plumbing for the tests: a copy of the benchmark's directory with
the TestNet configuration, cells and reference dropped in as NEW files (no
file of the benchmark is edited), and one run of a cell there on the CPU with
the harness's look for a chip skipped."""

import io
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

CELLS = {"testnet-featurize.arrays": ("testnet-featurize", "arrays"),
         "testnet-train.fit": ("testnet-train", "fit")}

DEVICE = {"platform": "cpu", "kind": "rehearsal", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
         "source": "made up for the rehearsal"}


def make_root(tmp):
    """``tmp/benchmarks`` = the benchmark + the rehearsal's files, and
    ``tmp/BENCHMARK.json`` naming the rehearsal cells under the real
    benchmark's metrics. Returns the root directory."""
    root = os.path.join(tmp, "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    for sub in ("configs", "workloads", "references"):
        for name in os.listdir(os.path.join(HERE, "rehearsal", sub)):
            shutil.copy(os.path.join(HERE, "rehearsal", sub, name),
                        os.path.join(root, sub, name))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    stand_in = {"inceptionv3-featurize.arrays": "testnet-featurize.arrays",
                "resnet50-train.fit": "testnet-train.fit"}
    bench = dict(real)
    bench["configs"] = [
        {"name": c, "source": "rehearsal", "reduced": [], "why": "rehearsal",
         "file": f"benchmarks/configs/{c}.json"}
        for c in sorted({c for c, _ in CELLS.values()})]
    bench["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "rehearsal"}
        for n, (c, t) in CELLS.items()]
    for group in ("end_to_end", "per_layer"):
        bench[group] = [
            dict(m, workloads=[stand_in[w] for w in m["workloads"]])
            if "workloads" in m else m for m in real[group]]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def run(root, cell, seed=1, seconds=0.5, trace=0):
    """One rehearsal run; returns ``(line, stderr text)``."""
    for path in (root, REPO):
        if path not in sys.path:
            sys.path.insert(0, path)
    import harness

    out, err = io.StringIO(), io.StringIO()
    line = harness.run_cell(cell, seed, seconds, trace, DEVICE, PEAKS,
                            time.perf_counter(), root=root, out=out, err=err)
    assert json.loads(out.getvalue().splitlines()[-1]) == json.loads(
        json.dumps(line))
    return line, err.getvalue()
