"""Rehearsal tests of the benchmark: run by hand, on the CPU —

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

They are not part of the repo's tier-1 tests (``tests/``)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the CPU has no use for the persistent compile cache (and warns about it)
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.dirname(HERE),
             os.path.dirname(os.path.dirname(HERE))):
    if path not in sys.path:
        sys.path.insert(0, path)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def root(tmp_path_factory):
    """A copy of the benchmark with the TestNet rehearsal dropped in."""
    import rehearse

    return rehearse.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture
def recorded_trace(monkeypatch):
    """``--trace 1`` on the CPU, which has no device plane: the trace
    recorded on the chip stands in for the profiler's file; everything else
    is the real path (telemetry scope, phase timers, readers found by
    name)."""
    import gzip
    import json

    import trace_reduce

    with gzip.open(os.path.join(HERE, "recorded_trace.json.gz"), "rt") as f:
        recorded = json.load(f)
    monkeypatch.setattr(trace_reduce, "extract", lambda path: recorded)
