"""Tracing / profiling subsystem (SURVEY.md §5.1).

The reference had no in-tree profiling (users hand-instrumented Spark UI /
TF timelines). TPU-native equivalent, three layers:

1. **Phase timers** — always-on, ~100ns wall-clock accumulators around the
   host pipeline phases (decode, stage, device execution). Read with
   ``phase_stats()``; they answer "is the MXU starved by the host?" without
   a trace.
2. **Trace annotations** — ``annotate("phase")`` adds a named span to any
   captured ``jax.profiler`` trace (and feeds the phase timers).
3. **Trace capture** — ``maybe_trace()`` wraps a block in
   ``jax.profiler.trace(dir)`` when ``SPARKDL_PROFILE_DIR`` is set, so any
   workload (bench.py, a transform, a fit) can be traced without code
   changes. The captured ``.trace.json.gz`` attributes per-fusion
   device time.

Timing methodology note: bench.py measures device throughput with
in-program loops (``lax.fori_loop`` with a loop-carried dependence) and
a scalar ``device_get`` as the completion barrier; whether a
cross-dispatch ``block_until_ready`` would serve equally is not measured
on the current machine.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Dict, Iterator, Optional

from sparkdl_tpu.core import telemetry

_lock = threading.Lock()
_phase_totals: Dict[str, float] = {}
_phase_counts: Dict[str, int] = {}

PROFILE_DIR_ENV = "SPARKDL_PROFILE_DIR"

# Canonical phase names for the async input pipeline (core/pipeline.py).
# HOST_WAIT is the starvation timer: seconds the device-driving thread
# spent waiting for the staging thread to deliver a batch. With the
# pipeline overlapped, host ETL phases (sparkdl.decode / sparkdl.stage /
# sparkdl.stage_batch) accumulate on the STAGING thread concurrently with
# sparkdl.train_step on the main thread — phase totals can legitimately
# sum past wall-clock; HOST_WAIT is the serial remainder the host still
# costs the device. DEVICE_SYNC times the deferred step-counter barriers
# (Trainer.fit sync points), i.e. real device execution the host waited
# out, where the pre-pipeline sparkdl.train_step span folded dispatch and
# execution together.
HOST_WAIT = "sparkdl.host_wait"
STAGE_BATCH = "sparkdl.stage_batch"
DEVICE_SYNC = "sparkdl.device_sync"

# Host ETL phases whose time the pipeline can hide behind device compute
# (used by overlap accounting: bench.py's overlap_ratio).
HOST_ETL_PHASES = ("sparkdl.decode", "sparkdl.stage", STAGE_BATCH,
                   "sparkdl.host_stage", "sparkdl.host_resize")


@contextlib.contextmanager
def annotate(name: str, **attributes: Any) -> Iterator[None]:
    """Named span: feeds phase timers, any active profiler trace, and —
    when a ``core.telemetry`` scope is active — the telemetry tracer
    (ambient-parented, so existing phase names become correlated spans
    for free). ``attributes`` ride on the telemetry span only; the
    phase timers stay name-keyed aggregates."""
    import jax.profiler

    t0 = time.perf_counter()
    with telemetry.span(name, **attributes):
        with jax.profiler.TraceAnnotation(name):
            yield
    dt = time.perf_counter() - t0
    with _lock:
        _phase_totals[name] = _phase_totals.get(name, 0.0) + dt
        _phase_counts[name] = _phase_counts.get(name, 0) + 1


def add_phase_time(name: str, seconds: float, count: int = 1) -> None:
    """Feed a phase timer directly (no span) — for waits measured by the
    async pipeline where a TraceAnnotation per queue-get would be noise."""
    with _lock:
        _phase_totals[name] = _phase_totals.get(name, 0.0) + seconds
        _phase_counts[name] = _phase_counts.get(name, 0) + count


def overlap_stats() -> Dict[str, float]:
    """Overlap accounting for the async input pipeline.

    ``host_etl_s``: host decode/stage seconds (the work the pipeline can
    hide). ``host_wait_s``: seconds the device-driving thread actually
    waited on the host (starvation). ``overlap_ratio``: fraction of host
    ETL hidden behind device compute — 1.0 means the host was never the
    bottleneck, 0.0 means fully serial (every ETL second stalled the
    device, the pre-pipeline behavior).
    """
    stats = phase_stats()
    etl = sum(stats[p]["total_s"] for p in HOST_ETL_PHASES if p in stats)
    wait = stats.get(HOST_WAIT, {}).get("total_s", 0.0)
    ratio = 1.0 if etl <= 0 else max(0.0, min(1.0, 1.0 - wait / etl))
    return {"host_etl_s": etl, "host_wait_s": wait, "overlap_ratio": ratio}


def phase_stats(reset: bool = False) -> Dict[str, Dict[str, float]]:
    """{phase: {total_s, count, mean_s}} accumulated since last reset."""
    with _lock:
        out = {
            name: {
                "total_s": total,
                "count": _phase_counts[name],
                "mean_s": total / _phase_counts[name],
            }
            for name, total in _phase_totals.items()
        }
        if reset:
            _phase_totals.clear()
            _phase_counts.clear()
    return out


def reset_phase_stats() -> None:
    phase_stats(reset=True)


@contextlib.contextmanager
def maybe_trace(trace_dir: Optional[str] = None) -> Iterator[bool]:
    """Capture a jax.profiler trace when enabled, else no-op.

    Enabled when ``trace_dir`` is passed or ``SPARKDL_PROFILE_DIR`` is set.
    Yields whether tracing is active.
    """
    target = trace_dir or os.environ.get(PROFILE_DIR_ENV)
    if not target:
        yield False
        return
    import jax.profiler

    with jax.profiler.trace(target):
        yield True
