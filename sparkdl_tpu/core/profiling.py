"""Tracing / profiling subsystem (SURVEY.md §5.1).

The reference had no in-tree profiling (users hand-instrumented Spark UI /
TF timelines). TPU-native equivalent, three layers:

1. **Phase timers** — always-on, ~100ns wall-clock accumulators around the
   host pipeline phases (decode, stage, device execution). Read with
   ``phase_stats()``; they answer "is the MXU starved by the host?" without
   a trace.
2. **Spans** — ``annotate("phase")`` feeds the phase timer of that name
   and, under a ``core.telemetry`` scope, records a span parented on the
   thread's open span. Host spans live in the telemetry trace only: the
   profiler's host tracer cannot be used on this path (PERF.md §6), so
   nothing is written into a ``jax.profiler`` trace.
3. **Device trace capture** — ``maybe_trace()`` wraps a block in a
   device-only ``jax.profiler`` trace when ``SPARKDL_PROFILE_DIR`` is set,
   so any workload (bench.py, a transform, a fit) can be traced without
   code changes, and writes ``sparkdl_clock.json`` beside it: what puts
   the telemetry trace's host spans on the device trace's clock.

Timing methodology note: bench.py measures device throughput with
in-program loops (``lax.fori_loop`` with a loop-carried dependence) and
a scalar ``device_get`` as the completion barrier; whether a
cross-dispatch ``block_until_ready`` would serve equally is not measured
on the current machine.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, Optional

from sparkdl_tpu.core import telemetry

_lock = threading.Lock()
_phase_totals: Dict[str, float] = {}
_phase_counts: Dict[str, int] = {}

PROFILE_DIR_ENV = "SPARKDL_PROFILE_DIR"
# maybe_trace's marker programs (``jit_<name>`` on the device's line) and
# the file that holds the host's clock at each
PROFILE_START = "sparkdl_profile_start"
PROFILE_STOP = "sparkdl_profile_stop"
PROFILE_CLOCK_FILE = "sparkdl_clock.json"

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
def annotate(name: str, **attributes: Any) -> Iterator[Any]:
    """Named span: feeds the phase timer ``name`` and — when a
    ``core.telemetry`` scope is active — the telemetry tracer
    (ambient-parented, so existing phase names become correlated spans
    for free). ``attributes`` ride on the telemetry span only; the
    phase timers stay name-keyed aggregates. Yields the span (the inert
    ``NULL_SPAN`` without a scope) for attributes known only inside."""
    t0 = time.perf_counter()
    with telemetry.span(name, **attributes) as span:
        yield span
    dt = time.perf_counter() - t0
    with _lock:
        _phase_totals[name] = _phase_totals.get(name, 0.0) + dt
        _phase_counts[name] = _phase_counts.get(name, 0) + 1


def add_phase_time(name: str, seconds: float, count: int = 1) -> None:
    """Feed a phase timer directly (no span) — for waits measured by the
    async pipeline where a span per queue-get would be noise."""
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


def _marker(name: str, scale: int, shift: int) -> Any:
    """A tiny jitted program called ``jit_<name>``, compiled and run once.
    The compile cache's key leaves the name out, so each marker computes
    what nothing else does, or a cached twin would lend its own name."""
    import jax

    def body(x):
        return x * scale + shift

    body.__name__ = name
    fn = jax.jit(body)
    fn(0).block_until_ready()
    return fn


@contextlib.contextmanager
def maybe_trace(trace_dir: Optional[str] = None) -> Iterator[bool]:
    """Capture a device-only ``jax.profiler`` trace when enabled, else
    no-op. Enabled when ``trace_dir`` is passed or ``SPARKDL_PROFILE_DIR``
    is set. Yields whether tracing is active.

    The profiler's host and Python tracers stay off (on the featurize
    path the host tracer records 3e7 events a pass and slows launches 25
    times: PERF.md §6). Host spans come from the telemetry trace instead,
    and ``<trace dir>/sparkdl_clock.json`` lines the two up: a marker
    program runs on the device right after the start and right before the
    stop (``jit_sparkdl_profile_start`` / ``jit_sparkdl_profile_stop`` on
    the device's line), and the file holds ``ready_ns`` — the host's
    ``time.perf_counter_ns`` when each marker's result came back — and
    ``epoch_ns`` of the telemetry scope open around the block (``null`` if
    none: a scope opened inside it carries its own in its run report).
    Span times + ``epoch_ns`` are host clock; host clock + (a marker's end
    on the device − its ``ready_ns``) is the device trace's clock.
    """
    target = trace_dir or os.environ.get(PROFILE_DIR_ENV)
    if not target:
        yield False
        return
    import jax.profiler

    markers = {PROFILE_START: _marker(PROFILE_START, 7901, 104717),
               PROFILE_STOP: _marker(PROFILE_STOP, 7883, 104711)}
    ready_ns: Dict[str, int] = {}

    def mark(name: str) -> None:
        markers[name](0).block_until_ready()
        ready_ns[name] = time.perf_counter_ns()

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    tel = telemetry.active()
    epoch_ns = None if tel is None else tel.tracer.epoch_ns
    jax.profiler.start_trace(target, profiler_options=options)
    try:
        mark(PROFILE_START)
        yield True
        mark(PROFILE_STOP)
    finally:
        jax.profiler.stop_trace()
        path = os.path.join(target, PROFILE_CLOCK_FILE)
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump({"clock": "time.perf_counter_ns",
                       "ready_ns": ready_ns, "epoch_ns": epoch_ns}, f)
        os.replace(tmp, path)
