"""A later PR adds files and entries and edits nothing that is there: a new
configuration, a new cell, a new per-layer metric and a reader of a new kind
are dropped into a copy of the benchmark as NEW files, named in
``BENCHMARK.json``, and the harness runs them."""

import hashlib
import json
import os

import rehearse


def _digest(root):
    out = {}
    for base, _, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_new_files_are_enough(tmp_path, recorded_trace):
    root = rehearse.make_root(str(tmp_path))
    before = _digest(root)

    def write(relative, data):
        path = os.path.join(root, relative)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(data if isinstance(data, str) else json.dumps(data))

    base = json.load(open(os.path.join(root, "configs",
                                       "testnet-featurize.json")))
    write("configs/testnet-b.json", dict(base, name="testnet-b"))
    cell = json.load(open(os.path.join(root, "workloads",
                                       "testnet-featurize.arrays.json")))
    cell["traffic"].update(n=24, batch_size=8)
    write("workloads/testnet-b.small.json",
          dict(cell, name="testnet-b.small", config="testnet-b"))
    write("readers/count.py",
          "def read(spec, run):\n"
          "    return run['window'].get(spec['key'])\n")
    write("metrics/window.passes_rows.json",
          {"name": "window.passes_rows", "unit": "rows", "layer": "entry",
           "moves": "featurize_images_per_s",
           "workloads": ["testnet-b.small"],
           "reader": {"kind": "count", "key": "images"}})

    bench_path = os.path.join(str(tmp_path), "BENCHMARK.json")
    bench = json.load(open(bench_path))
    bench["configs"].append({"name": "testnet-b", "source": "rehearsal",
                             "file": "benchmarks/configs/testnet-b.json",
                             "reduced": [], "why": "drop-in"})
    bench["workloads"].append({"name": "testnet-b.small",
                               "config": "testnet-b", "traffic": "small",
                               "chips": 1, "why": "drop-in"})
    bench["per_layer"].append({"name": "window.passes_rows", "unit": "rows",
                               "better": "higher", "source": "program_counter",
                               "layer": "entry",
                               "moves": "featurize_images_per_s",
                               "workloads": ["testnet-b.small"]})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "testnet-featurize.arrays" in metric.get("workloads", []):
            metric["workloads"].append("testnet-b.small")   # appended to
    json.dump(bench, open(bench_path, "w"))

    line, err = rehearse.run(root, "testnet-b.small", seed=5)
    assert line["correct"] is True, err
    assert line["attempted"] % 24 == 0

    line, err = rehearse.run(root, "testnet-b.small", seed=6, trace=1)
    assert line["metrics"]["window.passes_rows"]["value"] == line[
        "attempted"]
    assert "program.mfu.featurize" in line["metrics"]

    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
