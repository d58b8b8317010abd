"""The ``startup`` reader on a hand-made snapshot, the seven ``setup.*``
metric files through their readers on the rehearsal root, and what a program
older than the start-up record gives them: nothing, and no error (PR 41)."""

import json
import os

import pytest

import harness
import rehearse
from readers import startup

SETUP = ("setup.import_s", "setup.model_build_s", "setup.compile_s",
         "setup.cache_retrieval_s", "setup.cache_misses",
         "setup.first_launch_s", "setup.program_share")
RECORD = {"import_s": 4.0, "model_build_s": 1.5, "trace_lower_s": 0.5,
          "backend_compile_s": 0.25, "cache_retrieval_s": 2.0,
          "cache_hits": 3.0, "cache_misses": 0.0, "first_launch_s": 0.75,
          "compile_spans": 3.0}


def run_with(record, setup_s=30.0):
    return {"setup_s": setup_s, "window": {"telemetry": {
        "counters": {}, "histograms": {},
        "gauges": {startup.PREFIX + k: v for k, v in record.items()}}}}


def spec(key, *keys):
    return {"kind": "startup", "key": key, "keys": list(keys)}


COMPILE = ("trace_lower_s", "backend_compile_s", "cache_retrieval_s")
PROGRAM = ("import_s", "model_build_s") + COMPILE + ("first_launch_s",)


def test_sum_and_share_of_a_hand_made_record():
    run = run_with(RECORD)
    assert startup.read(spec("sum", *COMPILE), run) == pytest.approx(2.75)
    assert startup.read(spec("share", *PROGRAM), run) \
        == pytest.approx(100 * 9.0 / 30.0)
    # nothing happened is a reading, not an absence
    assert startup.read(spec("sum", "cache_misses"), run) == 0.0


def test_an_older_program_reads_nothing_and_does_not_raise():
    older = run_with({k: v for k, v in RECORD.items()
                      if k != "cache_retrieval_s"})
    assert startup.read(spec("sum", *COMPILE), older) is None
    assert startup.read(spec("share", *PROGRAM), older) is None
    assert startup.read(spec("sum", *COMPILE), run_with({})) is None
    assert startup.read(spec("sum", *COMPILE), {"window": {}}) is None
    assert startup.read(spec("sum", *COMPILE),
                        {"window": {"telemetry": {"counters": {}}}}) is None
    assert startup.read(spec("share", *PROGRAM),
                        run_with(RECORD, setup_s=0)) is None
    with pytest.raises(SystemExit):
        startup.read(spec("mean", *COMPILE), run_with(RECORD))


def test_the_seven_metric_files_resolve_on_a_hand_made_run(root):
    """Each file's reader is found by name under the rehearsal root and
    reads the hand-made record; the files agree with ``BENCHMARK.json``."""
    with open(os.path.join(rehearse.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    run = run_with(RECORD)
    got = {}
    for name in SETUP:
        with open(os.path.join(root, "metrics", f"{name}.json")) as f:
            metric = json.load(f)
        entry = entries[name]
        assert entry["moves"] == metric["moves"] == "setup_s"
        assert entry["better"] == "lower"
        assert entry["workloads"] == metric["workloads"] == cells
        assert (entry["unit"], entry["layer"]) == (metric["unit"],
                                                   metric["layer"])
        reader = harness.by_name("readers", metric["reader"]["kind"], root)
        got[name] = reader.read(metric["reader"], run)
        assert reader.read(metric["reader"], run_with({})) is None
    assert got == {"setup.import_s": 4.0, "setup.model_build_s": 1.5,
                   "setup.compile_s": pytest.approx(2.75),
                   "setup.cache_retrieval_s": 2.0, "setup.cache_misses": 0.0,
                   "setup.first_launch_s": 0.75,
                   "setup.program_share": pytest.approx(30.0)}


@pytest.mark.parametrize("cell", ["testnet-featurize.arrays",
                                  "testnet-train.fit",
                                  "testmoe-windows.windows"])
def test_a_traced_rehearsal_reports_all_seven_as_numbers(root, recorded_trace,
                                                         cell):
    """The real path: the driver's scope opens inside ``measure()``, after
    the model was built and first launched, and after the drivers'
    ``reset_phase_stats()`` — and still holds the record. (The record is the
    process's and the rehearsal shares its process with other tests, so its
    sum is not held to this run's ``setup_s`` here; the chip runs are.)"""
    line, err = rehearse.run(root, cell, seed=2_345_678_901, trace=1)
    assert line["correct"] is True, err
    got = {name: line["metrics"][name]["value"] for name in SETUP}
    assert all(isinstance(value, float) for value in got.values())
    assert got["setup.import_s"] > 0 and got["setup.model_build_s"] > 0
    assert got["setup.compile_s"] > 0 and got["setup.first_launch_s"] > 0
    # the CPU rehearsal runs with the persistent cache off
    assert got["setup.cache_misses"] == 0.0
    assert got["setup.cache_retrieval_s"] == 0.0
    assert got["setup.program_share"] > 0
    assert line["facts"]["compiles_in_window"] == 0
