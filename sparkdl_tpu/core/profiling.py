"""Tracing / profiling subsystem (SURVEY.md §5.1).

The reference had no in-tree profiling (users hand-instrumented Spark UI /
TF timelines). TPU-native equivalent, four parts:

1. **Phase timers** — always-on, ~100ns wall-clock accumulators around the
   host pipeline phases (decode, stage, device execution). Read with
   ``phase_stats()``; they answer "is the MXU starved by the host?" without
   a trace, and a caller that measures a window resets them at its start
   (``reset_phase_stats()``).
2. **Spans** — ``annotate("phase")`` feeds the phase timer of that name
   and, under a ``core.telemetry`` scope, records a span parented on the
   thread's open span. Host spans live in the telemetry trace only: the
   profiler's host tracer cannot be used on this path (PERF.md §6), so
   nothing is written into a ``jax.profiler`` trace.
3. **The start-up record** — name-keyed seconds and counts for the
   process's life (``startup_stats()``), fed where set-up work happens
   and by no scope: importing the program's packages (``import_begin`` /
   ``import_end``), making a model ready to launch (``model_build``), and
   the first launch of each of the program's compiled programs
   (``compile_span``: trace and lowering, backend compile or the compile
   cache's retrieval — from JAX's own monitoring events, heard only while
   such a span is open on the compiling thread — and what is left of the
   launch up to its first sync point, ``first_launch_wait``). It is NOT
   cleared by ``reset_phase_stats()``: a window's reset must not lose what
   came before it. A telemetry scope mirrors it into its
   ``sparkdl.startup.*`` gauges when it opens and whenever the record
   grows, and the run report carries it as ``startup`` — so a scope opened
   after the model was built and compiled still answers "where did the
   time to the first row go?". Nested blocks count once (each block adds
   its own time less its children's), so on one thread the parts add up to
   no more than the wall time around them.
4. **Device trace capture** — ``maybe_trace()`` wraps a block in a
   device-only ``jax.profiler`` trace when ``SPARKDL_PROFILE_DIR`` is set,
   so any workload (a transform, a fit) can be traced without code
   changes, and writes ``sparkdl_clock.json`` beside it: what puts the
   telemetry trace's host spans on the device trace's clock.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, Optional, Tuple

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
# (used by overlap accounting: overlap_stats' overlap_ratio).
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


# ---------------------------------------------------------------------------
# The start-up record (module docstring, part 3)
# ---------------------------------------------------------------------------

# every key is always there: a reader finds 0.0 where nothing happened
_startup: Dict[str, float] = dict.fromkeys(telemetry.STARTUP_KEYS, 0.0)
_tls = threading.local()

# JAX's monitoring events the record reads (JAX 0.9: jax/_src/dispatch.py,
# compiler.py, compilation_cache.py). The backend-compile duration spans
# the compile cache's lookup too, so a hit's retrieval lies inside it; the
# trace and lowering durations are not read (nested ``jit``s report theirs
# inside the outer one's): ``_compile_parts`` takes the time before the
# backend compile began instead.
_JAX_BACKEND = "/jax/core/compile/backend_compile_duration"
_JAX_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_misses"}
_listening = False
_listener_calls = 0     # callbacks JAX made, heard or not (tests count it)


def startup_stats() -> Dict[str, float]:
    """The start-up record: ``{key: seconds or count}`` for every key of
    ``telemetry.STARTUP_KEYS``, over the process's life."""
    with _lock:
        return dict(_startup)


def _add_startup(**parts: float) -> None:
    with _lock:
        for key, value in parts.items():
            _startup[key] += value
    mirror_startup(telemetry.active())


def mirror_startup(tel: Optional[telemetry.Telemetry]) -> None:
    """Set ``tel``'s ``sparkdl.startup.*`` gauges to the record, every key
    (``Telemetry.__enter__``, and each time the record grows under it)."""
    if tel is not None:
        for key, value in startup_stats().items():
            tel.metrics.gauge(telemetry.STARTUP_METRIC_PREFIX + key).set(
                value)


def _begin_block() -> float:
    """Open a timed block of the record on this thread. Blocks nest: each
    keeps the seconds of the blocks inside it, which ``_end_block`` takes
    out of its own, so nested time is counted once whatever the keys."""
    blocks = _tls.__dict__.setdefault("blocks", [])
    blocks.append(0.0)
    return time.perf_counter()


def _end_block(started: float) -> float:
    """Close this thread's innermost block; its own seconds."""
    wall = time.perf_counter() - started
    blocks = _tls.blocks
    inside = blocks.pop()
    if blocks:
        blocks[-1] += wall
    return max(0.0, wall - inside)


def import_begin() -> float:
    """First line of a package's ``__init__`` (and of a lazy resolver):
    the start of ``import_s``. Hand the result to ``import_end``."""
    return _begin_block()


def import_end(started: float) -> None:
    _add_startup(import_s=_end_block(started))


@contextlib.contextmanager
def model_build(model: str, **attributes: Any) -> Iterator[Any]:
    """``sparkdl.model_build``: what makes a model ready to launch —
    resolving and folding weights, casting them, building the apply
    function or the trainer's state. Feeds ``model_build_s``."""
    started = _begin_block()
    try:
        with annotate(telemetry.SPAN_MODEL_BUILD, model=model,
                      **attributes) as span:
            yield span
    finally:
        own = _end_block(started)
    _add_startup(model_build_s=own)


def _hear_jax() -> None:
    """Register the record's two listeners with JAX, once a process (JAX
    keeps listeners for good; this module stays importable without it)."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _on_event(event: str, **_: Any) -> None:
    global _listener_calls
    _listener_calls += 1
    heard = getattr(_tls, "compile", None)
    key = _JAX_COUNTS.get(event)
    if heard is not None and key is not None:
        heard[key] = heard.get(key, 0) + 1


def _on_duration(event: str, seconds: float, **_: Any) -> None:
    global _listener_calls
    _listener_calls += 1
    heard = getattr(_tls, "compile", None)
    if heard is None:
        return
    if event == _JAX_BACKEND:
        # an event arrives at its end: the compile began ``seconds`` ago
        heard["backend"].append((time.perf_counter() - seconds, seconds))
    elif event == _JAX_RETRIEVAL:
        heard["retrieval"] += seconds


def _compile_parts(heard: Dict[str, Any], started: float, wall: float,
                   inside: float) -> Tuple[Dict[str, float], float]:
    """One ``sparkdl.compile`` span's seconds, split so that the parts add
    up to the span's own time (``wall`` less the blocks ``inside`` it):
    ``trace_lower_s`` is the time before the LAST backend compile began
    less the earlier ones (small programs run while tracing), so nested
    ``jit``s, whose trace durations JAX reports inside the outer one's,
    are not counted twice; the backend's seconds are the retrieval where
    the cache hit and compile otherwise. Returns the record's parts and
    the rest: what the launch took beside them (argument hand-over,
    dispatch)."""
    own = max(0.0, wall - inside)
    backend = heard["backend"]
    total = sum(seconds for _, seconds in backend)
    before = 0.0
    if backend:
        before = backend[-1][0] - started - (total - backend[-1][1])
    trace_lower = min(max(before - inside, 0.0), own)
    total = min(total, own - trace_lower)
    retrieval = min(heard["retrieval"], total)
    return {"trace_lower_s": trace_lower,
            "backend_compile_s": total - retrieval,
            "cache_retrieval_s": retrieval,
            "cache_hits": heard.get("cache_hits", 0),
            "cache_misses": heard.get("cache_misses", 0),
            }, own - trace_lower - total


@contextlib.contextmanager
def compile_span(**attributes: Any) -> Iterator[Any]:
    """``sparkdl.compile`` around the first launch of one of the program's
    compiled programs (a new shape of ``ModelFunction.jitted``, the
    trainer's step and eval programs). While it is open on this thread
    JAX's compile events feed the record — compiles outside any such span
    (a caller's own programs) are left out. The span carries its share as
    attributes; what the launch took beside tracing and compiling goes to
    ``first_launch_s``, and the thread is marked so that the launch's
    first sync point adds its wait (``first_launch_wait``)."""
    _hear_jax()
    outer = getattr(_tls, "compile", None)
    heard: Dict[str, Any] = {"backend": [], "retrieval": 0.0}
    _tls.compile = heard
    started = _begin_block()
    try:
        with annotate(telemetry.SPAN_COMPILE, **attributes) as span:
            yield span
            parts, rest = _compile_parts(
                heard, started, time.perf_counter() - started,
                _tls.blocks[-1])
            for key, value in parts.items():
                span.set_attribute(key, value)
    finally:
        _tls.compile = outer
        _end_block(started)
    _tls.first_launch = True
    _add_startup(first_launch_s=rest, compile_spans=1, **parts)


def first_launch_wait() -> Optional[float]:
    """At a launch's first sync point (``batching.fetch``, the trainer's
    ``sync`` and ``evaluate``), before it blocks: the clock where this
    thread's last launch opened ``sparkdl.compile`` — once — and ``None``
    otherwise, at the cost of one thread-local read. Hand the result to
    ``first_launch_done`` once the outputs are there."""
    if getattr(_tls, "first_launch", False):
        _tls.first_launch = False
        return time.perf_counter()
    return None


def first_launch_done(started: Optional[float]) -> None:
    if started is not None:
        _add_startup(first_launch_s=time.perf_counter() - started)


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
