"""The harness: finds a cell's files by the names in ``BENCHMARK.json``,
runs its driver once, and prints the result line.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found by name — a later PR adds files and
entries and edits nothing that is here:

  configs/<config>.json      sizes, source, ``entry`` (the driver), reference
  workloads/<cell>.json      traffic parameters, ``why``, ``who``
  metrics/<metric>.json      ``reader`` = {kind, …}: how the number is read
  drivers/<entry>.py         ``Driver(cell, seed, env)`` with
                             ``setup()``, ``measure(seconds, tracer)``,
                             ``release()``, ``check()``
  readers/<kind>.py          ``read(reader_spec, run)`` → number or None
  references/<name>.py       the plain reference of a model
"""

import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


_LOADED = {}


def by_name(package, name, root=ROOT):
    """The module ``<root>/<package>/<name>.py``, loaded from that file (a
    second root in one process, as in the tests, gets its own)."""
    path = os.path.join(root, package, f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: no {package}/{name}.py under {root}")
    if root not in sys.path:
        sys.path.insert(0, root)
    if root == ROOT:
        return importlib.import_module(f"{package}.{name}")
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"{package}.{name}", path)
        _LOADED[path] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_LOADED[path])
    return _LOADED[path]


class Cell:
    """One entry of ``workloads`` with its files."""

    def __init__(self, name, root=ROOT, benchmark=None):
        self.root = root
        self.benchmark = benchmark or load_json(os.path.dirname(root),
                                                "BENCHMARK.json")
        entries = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in entries:
            raise SystemExit(f"benchmark: no workload {name!r} in "
                             f"BENCHMARK.json; there are {sorted(entries)}")
        self.entry = entries[name]
        self.name = name
        self.chips = self.entry["chips"]
        config = next(c for c in self.benchmark["configs"]
                      if c["name"] == self.entry["config"])
        self.config = load_json(os.path.dirname(root), config["file"])
        self.workload = load_json(root, "workloads", f"{name}.json")
        self.traffic = self.workload["traffic"]

    def metrics(self, group):
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.benchmark[group]
                if self.name in m.get("workloads", [self.name])]

    def reader_spec(self, metric_name):
        return load_json(self.root, "metrics", f"{metric_name}.json")


class Tracer:
    """The profiler around a part of the window.

    The profiler's host tracer stays off: with it on, the TPU runtime's own
    threads record an event for every small piece of a host-side transpose
    (3e7 events, 890 MB and two minutes of stop for one featurize pass, the
    launches 25 times slower; PERF.md, PR 29). So the traced part is marked
    on the device's own line instead: ``start``/``stop`` run two tiny marker
    programs (``jit_bench_trace_start``, ``jit_bench_trace_stop``), and the
    window is from the end of the one to the start of the other, idle time
    before the first and after the last operation included. The host's clock
    when each marker's result came back (``ready_ns``) puts the program's own
    spans, which the drivers read from its telemetry, on the device's clock.

    ``after(delay, seconds)`` does start and stop from one side thread.
    ``overhead_s`` is the time spent inside the profiler's own start and
    stop calls, which a driver whose passes are synchronous takes out of its
    window. With ``enabled=False`` every call is a no-op."""

    START, STOP = "bench_trace_start", "bench_trace_stop"
    # the device stalls for a second or so when the profiler starts; the
    # traced part begins once that has passed
    SETTLE_S = 2.0

    def __init__(self, enabled):
        self.enabled = enabled
        self.directory = None
        self.started = self.stopped = None      # host perf_counter seconds
        self.overhead_s = self.start_s = 0.0
        self.ready_ns = {}      # marker → host perf_counter_ns, result back
        self._markers = {}
        self._lock = threading.Lock()
        self._thread = None
        self._cancel = threading.Event()

    def prepare(self):
        """Compile and run the two marker programs once (set-up)."""
        if not self.enabled:
            return
        import jax
        import jax.numpy as jnp

        self._token = jnp.zeros((), jnp.int32)
        # two computations unlike each other and anything else: the compile
        # cache's key leaves the name out, so a program that computes the
        # same would lend its own name
        for name, body in ((self.START, lambda x: x * 7919 + 104729),
                           (self.STOP, lambda x: x * 7907 + 104723)):
            body.__name__ = name
            self._markers[name] = jax.jit(body)
            self._markers[name](self._token).block_until_ready()

    def _mark(self, name):
        self._markers[name](self._token).block_until_ready()
        self.ready_ns[name] = time.perf_counter_ns()

    def start(self):
        with self._lock:
            if not self.enabled or self.started is not None:
                return
            import jax.profiler

            t0 = time.perf_counter()
            self.directory = tempfile.mkdtemp(prefix="bench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            time.sleep(self.SETTLE_S)
            self._mark(self.START)
            self.started = time.perf_counter()
            self.start_s = self.started - t0
            self.overhead_s += self.start_s

    def stop(self):
        with self._lock:
            if self.started is None or self.stopped is not None:
                return
            import jax.profiler

            self.stopped = time.perf_counter()
            self._mark(self.STOP)
            jax.profiler.stop_trace()
            self.overhead_s += time.perf_counter() - self.stopped

    def after(self, delay, seconds):
        """Trace ``seconds`` from ``delay`` on, from a side thread."""
        if not self.enabled:
            return

        def body():
            if not self._cancel.wait(delay):
                self.start()
                self._cancel.wait(seconds)
                self.stop()

        self._thread = threading.Thread(target=body, daemon=True,
                                        name="bench-tracer")
        self._thread.start()

    def _end(self):
        self._cancel.set()
        if self._thread is not None:
            self._thread.join()
        self.stop()

    def abandon(self):
        """Stop what still runs and remove the trace's files."""
        self._end()
        if self.directory is not None:
            shutil.rmtree(self.directory, ignore_errors=True)

    def finish(self, spans=()):
        """Stop what still runs, reduce the trace, remove its files.
        ``spans``: the program's host spans, ``[name, start_ns, dur_ns]``
        on ``time.perf_counter_ns``."""
        self._end()
        if self.directory is None:
            return None
        import trace_reduce

        try:
            events = trace_reduce.extract(
                trace_reduce.find_xplane(self.directory))
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)
        events["host"] += trace_reduce.on_device_clock(
            spans, self.ready_ns, trace_reduce.marker_runs(
                events, (self.START, self.STOP)))
        window = trace_reduce.marked_window(events, self.START, self.STOP)
        if window is None and events["device"]:
            print("benchmark: the marker programs are not in the trace; "
                  "the window is from the first to the last device "
                  "operation", file=sys.stderr)
        return trace_reduce.reduce(events, window)


def memory_peak_bytes():
    """The peak on the fullest chip. The TPU runtime keeps a loaded
    program's temporaries in a region of their own (``bytes_reserved``)
    that ``peak_bytes_in_use`` (arrays: weights, staged batches, outputs)
    does not count; both come out of the same memory — the largest free
    block shrinks by both — so the peak is their sum."""
    import jax

    peaks = []
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def run_cell(name, seed, seconds, trace, device, peaks, t_start,
             root=ROOT, benchmark=None, out=sys.stdout, err=sys.stderr):
    """One run of one cell; prints the result line on ``out`` and returns
    it. ``device``/``peaks`` come from ``peaks.require_tpu`` (a rehearsal
    passes its own); ``t_start`` is the process's start on
    ``time.perf_counter``."""
    cell = Cell(name, root, benchmark)
    driver = by_name("drivers", cell.config["entry"], root).Driver(
        cell, seed, {"peaks": peaks, "device": device, "root": root})
    tracer = Tracer(bool(trace))
    try:
        return _run(cell, driver, tracer, seconds, trace, device, peaks,
                    t_start, root, out, err)
    finally:
        tracer.abandon()


def _run(cell, driver, tracer, seconds, trace, device, peaks, t_start, root,
         out, err):
    tracer.prepare()
    driver.setup()
    setup_s = time.perf_counter() - t_start
    window = driver.measure(seconds, tracer)
    traced = tracer.finish(window.get("spans", ()))
    device = dict(device, memory_peak_bytes=memory_peak_bytes())
    driver.release()
    t_check = time.perf_counter()
    correct, compared = driver.check()
    window["check_s"] = time.perf_counter() - t_check

    run = {"cell": cell, "window": window, "trace": traced, "peaks": peaks,
           "setup_s": setup_s}
    metrics = {}
    if trace:
        if traced is None:
            raise SystemExit("benchmark: the traced window holds no device "
                             "operation")
        device.update(busy_s=traced["busy_s"], window_s=traced["window_s"])
        for metric in cell.metrics("per_layer"):
            spec = cell.reader_spec(metric["name"])
            value = by_name("readers", spec["reader"]["kind"], root).read(
                spec["reader"], run)
            if value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        for metric in cell.metrics("end_to_end"):
            metrics[metric["name"]] = {"value": values[metric["name"]],
                                       "unit": metric["unit"]}

    line = {"correct": bool(correct), "attempted": window["attempted"],
            "failed": window["failed"], "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {"device_ops": traced["device_ops"],
                             "idle_gaps": traced["idle_gaps"]}
    window["trace_overhead_s"] = [tracer.start_s,
                                  tracer.overhead_s - tracer.start_s]
    line["facts"] = {k: window[k] for k in ("seconds", "images",
                                            "traced_images", "check_s",
                                            "compiles_in_window",
                                            "trace_overhead_s", "phase_s")
                     if k in window}
    line["compared"] = compared
    for key, (value, limit) in compared.items():
        print(f"compared {key}: {value!r} limit {limit!r}", file=err)
    print(f"correct: {bool(correct)}", file=err, flush=True)
    print(json.dumps(line), file=out, flush=True)
    return line
