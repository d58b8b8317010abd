"""``rehearse.make_root`` for a benchmark that has grown: it stands each of
the benchmark's cells in by the rehearsal cell of the same driver, and leaves
a cell it has no stand-in for out of the metrics' lists (``rehearse.py``
knows PR 29's two cells by name and fails on a third). ``benchmarks/
conftest.py`` puts it in ``rehearse.make_root``'s place; a ``benchmark`` PR
may fold it into ``rehearse.py`` (PERF.md §7)."""

import json
import os
import shutil

import rehearse

STAND_IN = {"inceptionv3-featurize.arrays": "testnet-featurize.arrays",
            "resnet50-train.fit": "testnet-train.fit",
            "openpangu-ultra-moe-718b-ep16.windows":
                "testmoe-windows.windows"}


def make_root(tmp):
    """``tmp/benchmarks`` = the benchmark + the rehearsal's files, and
    ``tmp/BENCHMARK.json`` naming the rehearsal cells under the real
    benchmark's metrics. Returns the root directory."""
    root = os.path.join(tmp, "benchmarks")
    shutil.copytree(rehearse.BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "conftest.py"))
    for sub in ("configs", "workloads", "references"):
        source = os.path.join(rehearse.HERE, "rehearsal", sub)
        for name in os.listdir(source):
            shutil.copy(os.path.join(source, name),
                        os.path.join(root, sub, name))
    with open(os.path.join(rehearse.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {}
    for cell in sorted(set(STAND_IN.values())):
        with open(os.path.join(root, "workloads", f"{cell}.json")) as f:
            cells[cell] = json.load(f)["config"]
    bench["configs"] = [
        {"name": c, "source": "rehearsal", "reduced": [], "why": "rehearsal",
         "file": f"benchmarks/configs/{c}.json"}
        for c in sorted(set(cells.values()))]
    bench["workloads"] = [
        {"name": n, "config": c, "traffic": n.rsplit(".", 1)[1], "chips": 1,
         "why": "rehearsal"} for n, c in cells.items()]
    for group in ("end_to_end", "per_layer"):
        bench[group] = [
            dict(m, workloads=[STAND_IN[w] for w in m["workloads"]
                               if w in STAND_IN])
            if "workloads" in m else m for m in bench[group]]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
