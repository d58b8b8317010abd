"""Device execution service tests (ISSUE 5 tentpole, core/executor.py):
cross-partition dynamic batch coalescing — bit-identical order-preserving
results, the solo inline fast path, hedge dedup, per-request failure
isolation, and shutdown that never leaks a future."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.core import (executor, health, profiling, resilience,
                              telemetry)
from sparkdl_tpu.core.executor import ExecutorShutdown, task_scope
from sparkdl_tpu.core.health import HealthMonitor
from sparkdl_tpu.core.model_function import ModelFunction, TensorSpec
from sparkdl_tpu.core.resilience import Fault, FaultInjector
from sparkdl_tpu.core.telemetry import Telemetry
from sparkdl_tpu.engine.dataframe import EngineConfig

_ELEMENT = (6,)
_FEATURES = 3


@pytest.fixture(autouse=True)
def _fresh_executor():
    """Each test gets its own service instance and pristine knobs
    (EngineConfig is process-wide class state; the snapshot covers every
    public knob, so the ISSUE 6 overload knobs — and future ones — are
    restored without listing them)."""
    saved = EngineConfig.snapshot()
    executor.reset()
    yield
    executor.reset()
    EngineConfig.restore(saved)


def _model(name="exec_model", sleep_s=0.0):
    """Row-wise model; ``sleep_s`` injects host time at EXECUTION (via
    pure_callback), so tests can hold a launch in flight deterministically
    without fighting the scheduler."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(_ELEMENT[0], _FEATURES))
                    .astype(np.float32))

    def apply_fn(vs, x):
        if sleep_s:
            def slow_identity(a):
                time.sleep(sleep_s)
                return a
            x = jax.pure_callback(
                slow_identity,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return jnp.tanh(x @ vs)

    return ModelFunction(apply_fn, w, TensorSpec((None,) + _ELEMENT,
                                                 "float32"), name=name)


def _rows(n, seed=1):
    return np.random.default_rng(seed).normal(
        size=(n,) + _ELEMENT).astype(np.float32)


# ---------------------------------------------------------------------------
# Routing and the inline fast path
# ---------------------------------------------------------------------------


def test_solo_request_takes_inline_path_and_matches_apply_batch():
    mf = _model()
    x = _rows(5)
    expected = mf.apply_batch(x, batch_size=16)
    with Telemetry() as tel:
        out = executor.execute(mf, x, batch_size=16)
    np.testing.assert_array_equal(out, expected)
    # no coalescer launch happened: the coalesce histograms stayed empty
    hists = tel.metrics.snapshot()["histograms"]
    assert telemetry.M_COALESCE_REQUESTS not in hists
    assert telemetry.M_QUEUE_WAIT_S not in hists


def test_coalesce_off_and_oversize_and_empty_bypass_the_service():
    mf = _model()
    EngineConfig.coalesce = False
    x = _rows(4)
    np.testing.assert_array_equal(executor.execute(mf, x, batch_size=16),
                                  mf.apply_batch(x, batch_size=16))
    EngineConfig.coalesce = True
    big = _rows(40)  # > batch_size: the chunked path, never queued
    np.testing.assert_array_equal(executor.execute(mf, big, batch_size=16),
                                  mf.apply_batch(big, batch_size=16))
    empty = _rows(0)
    out = executor.execute(mf, empty, batch_size=16)
    assert out.shape == (0, _FEATURES)


def test_coalesce_max_rows_caps_one_launch():
    EngineConfig.coalesce_max_rows = 4
    mf = _model()
    x = _rows(6)  # > cap: bypasses the queue, still correct
    np.testing.assert_array_equal(executor.execute(mf, x, batch_size=16),
                                  mf.apply_batch(x, batch_size=16))


# ---------------------------------------------------------------------------
# Coalescing: bit-identical, order-preserving, observable
# ---------------------------------------------------------------------------


def _run_concurrent(mf, inputs, batch_size=32, tokens=None):
    """Submit every input from its own thread (barrier start); returns
    the per-thread results in input order."""
    results = [None] * len(inputs)
    errors = [None] * len(inputs)
    barrier = threading.Barrier(len(inputs))

    def work(i):
        try:
            barrier.wait()
            if tokens and tokens[i] is not None:
                with task_scope(tokens[i]):
                    results[i] = executor.execute(mf, inputs[i],
                                                  batch_size=batch_size)
            else:
                results[i] = executor.execute(mf, inputs[i],
                                              batch_size=batch_size)
        except BaseException as e:  # noqa: BLE001 - asserted by caller
            errors[i] = e

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(len(inputs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results, errors


def test_concurrent_requests_coalesce_bit_identical_per_requester():
    mf = _model(sleep_s=0.05)  # holds the inline launch in flight
    EngineConfig.coalesce_window_ms = 150.0
    inputs = [_rows(3, seed=i) for i in range(6)]
    expected = [mf.apply_batch(x, batch_size=32) for x in inputs]
    with Telemetry() as tel:
        results, errors = _run_concurrent(mf, inputs)
    assert errors == [None] * 6
    for got, want in zip(results, expected):
        np.testing.assert_array_equal(got, want)
    hists = tel.metrics.snapshot()["histograms"]
    coalesced = hists[telemetry.M_COALESCE_REQUESTS]
    # at least one multi-request launch happened (5 queued behind the
    # inline request coalesce within the window)
    assert coalesced["max"] >= 2
    assert hists[telemetry.M_COALESCE_ROWS]["count"] >= 1
    assert hists[telemetry.M_QUEUE_WAIT_S]["count"] >= 2


def test_multi_input_dict_models_coalesce():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(4, 2)).astype(np.float32))
    mf = ModelFunction(
        lambda vs, x: {"out": jnp.tanh(x["a"] @ vs) + x["b"]},
        w,
        {"a": TensorSpec((None, 4), "float32"),
         "b": TensorSpec((None, 2), "float32")},
        name="dict_model")
    mf_slow = ModelFunction(mf.apply_fn, mf.variables, mf.input_spec,
                            name="dict_model")
    inputs = [{"a": rng.normal(size=(3, 4)).astype(np.float32),
               "b": rng.normal(size=(3, 2)).astype(np.float32)}
              for _ in range(4)]
    expected = [mf.apply_batch(x, batch_size=16) for x in inputs]
    EngineConfig.coalesce_window_ms = 100.0
    results, errors = _run_concurrent(mf_slow, inputs, batch_size=16)
    assert errors == [None] * 4
    for got, want in zip(results, expected):
        np.testing.assert_array_equal(got["out"], want["out"])


def test_hedged_duplicate_dedups_before_coalescing():
    """Two attempts of the SAME task (shared token) submitting while a
    sibling holds the device: the duplicate shares the first attempt's
    pending request — its rows launch exactly once."""
    mf = _model(sleep_s=0.15)
    EngineConfig.coalesce_window_ms = 250.0
    x_busy = _rows(2, seed=0)
    x_task = _rows(3, seed=1)
    expected = mf.apply_batch(x_task, batch_size=32)
    token = ("task", 1234, 7)
    with Telemetry() as tel:
        # occupy the key so the tokened submissions queue (inline holds
        # the device for sleep_s)
        results = {}
        errors = []

        def busy():
            results["busy"] = executor.execute(mf, x_busy, batch_size=32)

        def attempt(name):
            try:
                with task_scope(token):
                    results[name] = executor.execute(mf, x_task,
                                                     batch_size=32)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        t_busy = threading.Thread(target=busy)
        t_busy.start()
        time.sleep(0.05)  # inline launch now in flight
        t_a = threading.Thread(target=attempt, args=("primary",))
        t_b = threading.Thread(target=attempt, args=("hedge",))
        t_a.start()
        time.sleep(0.02)  # primary queued mid-window
        t_b.start()
        for t in (t_busy, t_a, t_b):
            t.join()
    assert not errors
    np.testing.assert_array_equal(results["primary"], expected)
    np.testing.assert_array_equal(results["hedge"], expected)
    snap = tel.metrics.snapshot()
    assert snap["counters"][telemetry.M_COALESCE_DEDUP] == 1
    # the task's rows were launched once, not twice: every coalesced
    # launch's row total sums to busy-is-inline + one copy of the task
    rows_hist = snap["histograms"].get(telemetry.M_COALESCE_ROWS)
    assert rows_hist is not None and rows_hist["sum"] == len(x_task)


# ---------------------------------------------------------------------------
# Failure semantics
# ---------------------------------------------------------------------------


def test_oom_on_coalesced_launch_splits_per_request_bit_identical():
    mf = _model(sleep_s=0.05)
    EngineConfig.coalesce_window_ms = 150.0
    inputs = [_rows(3, seed=i) for i in range(5)]
    expected = [mf.apply_batch(x, batch_size=32) for x in inputs]
    # fires only on a multi-request launch: a solo request's valid rows
    # never reach 6
    inj = FaultInjector.seeded(
        0, device_oom=Fault(times=1, when=lambda c: c.get("valid", 0) >= 6))
    with inj, HealthMonitor() as mon:
        results, errors = _run_concurrent(mf, inputs)
    assert errors == [None] * 5
    assert inj.fired["device_oom"] == 1
    for got, want in zip(results, expected):
        np.testing.assert_array_equal(got, want)
    assert mon.count(health.OOM_RECHUNK) == 1


def test_fatal_failure_poisons_only_its_own_request():
    """A FATAL error on the coalesced launch splits per-request: the
    poisoned request raises its own error, siblings complete."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(_ELEMENT[0], _FEATURES))
                    .astype(np.float32))

    def apply_fn(vs, x):
        def check(a):
            time.sleep(0.05)
            if np.any(np.isnan(a)):
                # INVALID_ARGUMENT marker: classifies FATAL even through
                # the XlaRuntimeError wrapper jit re-raises callbacks in
                raise ValueError("INVALID_ARGUMENT: deliberate poison row")
            return a
        x = jax.pure_callback(check, jax.ShapeDtypeStruct(x.shape, x.dtype),
                              x)
        return jnp.tanh(x @ vs)

    mf = ModelFunction(apply_fn, w, TensorSpec((None,) + _ELEMENT,
                                               "float32"), name="poison")
    EngineConfig.coalesce_window_ms = 150.0
    inputs = [_rows(3, seed=i) for i in range(4)]
    poisoned = inputs[2].copy()
    poisoned[1, 0] = np.nan
    inputs[2] = poisoned
    results, errors = _run_concurrent(mf, inputs)
    clean = [i for i in range(4) if i != 2]
    # the poisoned request failed alone...
    assert isinstance(errors[2], Exception)
    assert resilience.classify(errors[2]) == resilience.FATAL
    # ...and every sibling completed with its own rows
    for i in clean:
        assert errors[i] is None, errors[i]
        np.testing.assert_array_equal(
            results[i], mf.apply_batch(inputs[i], batch_size=32))


def test_transient_failure_records_retry_and_replays_per_request():
    """A transient on the super-batch records CHUNK_RETRY (parity with
    the chunk path) and hands every request back to its own thread for
    replay — the retry backoff never sleeps on the coalescer thread, so
    queued siblings keep draining."""
    mf = _model(sleep_s=0.05)
    EngineConfig.coalesce_window_ms = 150.0
    inputs = [_rows(3, seed=i) for i in range(4)]
    expected = [mf.apply_batch(x, batch_size=32) for x in inputs]
    inj = FaultInjector.seeded(
        0, transfer_stall=Fault(times=1,
                                when=lambda c: c.get("valid", 0) >= 6))
    with inj, HealthMonitor() as mon:
        results, errors = _run_concurrent(mf, inputs)
    assert errors == [None] * 4
    assert inj.fired["transfer_stall"] == 1
    for got, want in zip(results, expected):
        np.testing.assert_array_equal(got, want)
    assert mon.count(health.CHUNK_RETRY) == 1


# ---------------------------------------------------------------------------
# Shutdown: no leaked futures (the kill-midwindow contract)
# ---------------------------------------------------------------------------


def test_shutdown_midwindow_every_request_completes_or_raises():
    mf = _model(sleep_s=0.4)
    EngineConfig.coalesce_window_ms = 30_000.0  # park the queued request
    x_busy = _rows(2, seed=0)
    x_queued = _rows(3, seed=1)
    outcome = {}

    def busy():
        outcome["busy"] = executor.execute(mf, x_busy, batch_size=32)

    def queued():
        try:
            outcome["queued"] = executor.execute(mf, x_queued,
                                                 batch_size=32)
        except BaseException as e:  # noqa: BLE001 - asserted below
            outcome["queued_error"] = e

    t_busy = threading.Thread(target=busy)
    t_busy.start()
    time.sleep(0.1)  # inline launch in flight
    t_q = threading.Thread(target=queued)
    t_q.start()
    time.sleep(0.1)  # queued mid-window (the window is 30 s)
    executor.shutdown()
    t_q.join(timeout=5.0)
    t_busy.join(timeout=5.0)
    assert not t_q.is_alive() and not t_busy.is_alive()
    # the in-flight inline request completed; the parked one raised — no
    # future was leaked
    np.testing.assert_array_equal(outcome["busy"],
                                  mf.apply_batch(x_busy, batch_size=32))
    assert isinstance(outcome.get("queued_error"), ExecutorShutdown)
    assert "queued" not in outcome


def test_submit_after_shutdown_raises():
    mf = _model(sleep_s=0.2)
    EngineConfig.coalesce_window_ms = 100.0
    # prime a state so the submit below takes the queued path, then close
    x = _rows(2)
    results, errors = _run_concurrent(mf, [x, _rows(2, seed=3)])
    assert errors == [None, None]
    executor.shutdown()
    with pytest.raises(ExecutorShutdown):
        executor.service().submit(mf, x, len(x), 32, None, 1,
                                  resilience.DEFAULT_INFERENCE_POLICY,
                                  None, 32, 0)


# ---------------------------------------------------------------------------
# Post-review hardening (ISSUE 5): dedup identity, per-request policy,
# fetch-time failure isolation
# ---------------------------------------------------------------------------


def test_task_token_sequence_prevents_cross_call_dedup():
    """The dedup identity is (task token, call sequence): a task whose op
    chain enters the device twice must not dedup call N onto call M; a
    fresh attempt (hedge) restarts the sequence so its call N matches the
    primary's call N."""
    from sparkdl_tpu.core.executor import current_task_token

    assert current_task_token() is None
    with task_scope(("t", 1)):
        assert current_task_token() == ("t", 1, 0)
        assert current_task_token() == ("t", 1, 1)  # second device call
        with task_scope(("t", 2)):  # nested scope: its own sequence
            assert current_task_token() == ("t", 2, 0)
        assert current_task_token() == ("t", 1, 2)  # outer resumes
    with task_scope(("t", 1)):  # a hedge attempt restarts at 0
        assert current_task_token() == ("t", 1, 0)
    assert current_task_token() is None


def test_hedge_reexecutes_independently_once_sibling_is_in_flight():
    """Dedup only shares PRE-launch (queued) requests: a hedge arriving
    while its primary's launch is already in flight (here: the inline
    path) re-runs the pure ops independently — that is what lets
    speculation win past a launch stalled on the device."""
    mf = _model(sleep_s=0.2)
    EngineConfig.coalesce_window_ms = 100.0
    x = _rows(3, seed=4)
    expected = mf.apply_batch(x, batch_size=32)
    token = ("task", 99, 0)
    results = {}
    errors = []

    def attempt(name):
        try:
            with task_scope(token):
                results[name] = executor.execute(mf, x, batch_size=32)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    with Telemetry() as tel:
        t_primary = threading.Thread(target=attempt, args=("primary",))
        t_primary.start()
        time.sleep(0.08)  # primary's inline launch now in flight
        t_hedge = threading.Thread(target=attempt, args=("hedge",))
        t_hedge.start()
        t_primary.join()
        t_hedge.join()
    assert not errors
    np.testing.assert_array_equal(results["primary"], expected)
    np.testing.assert_array_equal(results["hedge"], expected)
    snap = tel.metrics.snapshot()
    # no sharing happened — the hedge ran its own (queued, solo) launch
    assert snap["counters"].get(telemetry.M_COALESCE_DEDUP, 0) == 0
    assert snap["histograms"][telemetry.M_COALESCE_ROWS]["sum"] == len(x)


def test_mixed_shape_window_launches_per_shape_group():
    """One jitted fn can serve several input shapes; a drained window
    holding different element shapes must not concat them into one
    launch — each shape group launches (and succeeds) separately."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(3,)).astype(np.float32))

    def apply_fn(vs, x):
        def slow(a):
            time.sleep(0.05)
            return a
        x = jax.pure_callback(slow, jax.ShapeDtypeStruct(x.shape, x.dtype),
                              x)
        return jnp.tanh(x.reshape((x.shape[0], -1)).sum(axis=1,
                                                        keepdims=True) * vs)

    mf = ModelFunction(apply_fn, w, TensorSpec((None, None), "float32"),
                       name="anyshape")
    EngineConfig.coalesce_window_ms = 150.0
    # two element widths against the same model: (N, 4) and (N, 7)
    inputs = ([rng.normal(size=(3, 4)).astype(np.float32)
               for _ in range(3)]
              + [rng.normal(size=(3, 7)).astype(np.float32)
                 for _ in range(3)])
    expected = [mf.apply_batch(x, batch_size=32) for x in inputs]
    results, errors = _run_concurrent(mf, inputs)
    assert errors == [None] * 6, errors
    for got, want in zip(results, expected):
        np.testing.assert_array_equal(got, want)


def test_caller_retry_policy_honored_when_queued():
    """A caller's retry_policy rides the request into the coalescer: with
    max_retries=0 a transient failure on the super-batch is NOT retried —
    it splits to per-request sub-launches immediately (which then also
    run under the caller's policy)."""
    mf = _model(sleep_s=0.05)
    EngineConfig.coalesce_window_ms = 150.0
    no_retry = resilience.RetryPolicy(max_retries=0)
    inputs = [_rows(3, seed=i) for i in range(4)]
    expected = [mf.apply_batch(x, batch_size=32) for x in inputs]
    inj = FaultInjector.seeded(
        0, transfer_stall=Fault(times=1,
                                when=lambda c: c.get("valid", 0) >= 6))
    results = [None] * 4
    errors = [None] * 4
    barrier = threading.Barrier(4)

    def work(i):
        try:
            barrier.wait()
            results[i] = executor.execute(mf, inputs[i], batch_size=32,
                                          retry_policy=no_retry)
        except BaseException as e:  # noqa: BLE001
            errors[i] = e

    with inj, HealthMonitor() as mon:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert errors == [None] * 4
    assert inj.fired["transfer_stall"] == 1
    for got, want in zip(results, expected):
        np.testing.assert_array_equal(got, want)
    # max_retries=0: the transient was never retried, so no CHUNK_RETRY —
    # the window split straight to per-request sub-launches
    assert mon.count(health.CHUNK_RETRY) == 0


def test_fetch_time_failure_replays_the_request_alone():
    """Async dispatch can surface a real device failure only at the
    requester's fetch: _await classifies it and re-runs THIS request
    alone through apply_batch (OOM recorded, siblings unaffected)."""
    mf = _model()
    x = _rows(3, seed=5)
    svc = executor.service()
    fn = mf.jitted(mesh=None)
    state = svc._state(fn, mf, 32, None, 1)

    class _LateBoom:
        """Stands in for a device array whose execution failed: the
        error surfaces at np.asarray, not at dispatch."""

        def __array__(self, *a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory while "
                               "executing the coalesced launch")

    req = executor._Request(mf.stage_inputs(x), 3, None,
                            resilience.DEFAULT_INFERENCE_POLICY)
    req.future.set_result(_LateBoom())
    with HealthMonitor() as mon:
        out = svc._await(state, req, time.monotonic())
    np.testing.assert_array_equal(out, mf.apply_batch(x, batch_size=32))
    assert mon.count(health.OOM_RECHUNK) == 1


def test_reset_call_sequence_realigns_retry_attempts():
    """run_partition_task's classified retries re-run the op chain from
    the top inside ONE task_scope: reset_call_sequence restarts the
    device-call numbering so a retried attempt's call N dedups against a
    hedge's call N, never call M."""
    from sparkdl_tpu.core.executor import (current_task_token,
                                           reset_call_sequence)

    reset_call_sequence()  # outside any scope: a no-op
    assert current_task_token() is None
    with task_scope(("t", 3)):
        assert current_task_token() == ("t", 3, 0)
        assert current_task_token() == ("t", 3, 1)
        reset_call_sequence()  # next retry-loop attempt
        assert current_task_token() == ("t", 3, 0)
    assert current_task_token() is None


def test_solo_drained_window_replays_on_the_requester_thread():
    """A drained group of one (and every member of a terminal failure
    split) is handed BACK via the replay sentinel: apply_batch runs on
    the requester's own thread, never the coalescer's — the coalescer
    stays free to drain queued siblings instead of serializing device
    fetches and retry backoffs behind one request."""
    mf = _model(sleep_s=0.2)
    EngineConfig.coalesce_window_ms = 30.0
    apply_threads = []
    orig_apply = mf.apply_batch

    def recording_apply(*args, **kwargs):
        apply_threads.append(threading.current_thread().name)
        return orig_apply(*args, **kwargs)

    mf.apply_batch = recording_apply
    x_busy = _rows(2, seed=0)
    x_queued = _rows(3, seed=1)
    outcome = {}

    def busy():
        outcome["busy"] = executor.execute(mf, x_busy, batch_size=32)

    def queued():
        outcome["queued"] = executor.execute(mf, x_queued, batch_size=32)

    t_busy = threading.Thread(target=busy, name="requester-busy")
    t_busy.start()
    time.sleep(0.05)  # inline launch in flight
    t_q = threading.Thread(target=queued, name="requester-queued")
    t_q.start()  # queues; the 30 ms window drains it as a group of one
    t_busy.join()
    t_q.join()
    np.testing.assert_array_equal(
        outcome["busy"], orig_apply(x_busy, batch_size=32))
    np.testing.assert_array_equal(
        outcome["queued"], orig_apply(x_queued, batch_size=32))
    assert set(apply_threads) == {"requester-busy", "requester-queued"}
    assert not any(n.startswith("sparkdl-exec") for n in apply_threads)


# ---------------------------------------------------------------------------
# Spans inside execute (PR 30): queue wait, launch, device wait and fetch,
# each named, and the D2H byte counter
# ---------------------------------------------------------------------------

_INSIDE = (telemetry.SPAN_LAUNCH, profiling.DEVICE_SYNC,
           telemetry.SPAN_FETCH)


def test_lone_execute_records_launch_sync_fetch_in_order_on_the_caller():
    mf = _model()
    x = _rows(5)
    with Telemetry() as tel:
        with profiling.annotate("sparkdl.device_apply", rows=len(x)):
            executor.execute(mf, x, batch_size=16)
    (outer,) = tel.tracer.spans("sparkdl.device_apply")
    inside = sorted((s for s in tel.tracer.spans() if s["name"] in _INSIDE),
                    key=lambda s: s["start_ns"])
    assert [s["name"] for s in inside] == list(_INSIDE)
    me = threading.get_ident()
    for earlier, later in zip(inside, inside[1:]):
        assert earlier["end_ns"] <= later["start_ns"]
    for s in inside:
        assert s["thread_id"] == me
        assert s["parent_id"] == outer["span_id"]
        assert outer["start_ns"] <= s["start_ns"]
        assert s["end_ns"] <= outer["end_ns"]
    launch, sync, fetch = inside
    assert launch["attributes"] == {"rows": 8, "bytes": 8 * 6 * 4}
    assert sync["attributes"] == {"rows": 5}
    assert fetch["attributes"] == {"rows": 5, "bytes": 5 * _FEATURES * 4}
    # ran inline: it never queued, so there is no wait to name
    assert tel.tracer.spans(telemetry.SPAN_QUEUE_WAIT) == []


def test_queued_request_records_one_queue_wait_as_long_as_the_window():
    """A request that arrives while a launch is in flight queues; the
    coalescer holds it for the window, and that wait is one span on the
    REQUESTER's thread — followed there by its own launch/sync/fetch."""
    window_s = 0.15
    mf = _model(sleep_s=0.3)
    EngineConfig.coalesce_window_ms = window_s * 1e3
    idents = {}

    def run(name, x):
        idents[name] = threading.get_ident()
        executor.execute(mf, x, batch_size=32)

    with Telemetry() as tel:
        t_busy = threading.Thread(target=run, args=("busy", _rows(2)))
        t_busy.start()
        time.sleep(0.05)  # the inline launch is now in flight
        t_q = threading.Thread(target=run, args=("queued", _rows(3)))
        t_q.start()
        t_busy.join(timeout=30)
        t_q.join(timeout=30)
        assert not t_busy.is_alive() and not t_q.is_alive()
    (wait,) = tel.tracer.spans(telemetry.SPAN_QUEUE_WAIT)
    assert wait["thread_id"] == idents["queued"]
    assert wait["attributes"] == {"rows": 3,
                                  "priority": executor.PRIORITY_BULK}
    assert (wait["end_ns"] - wait["start_ns"]) / 1e9 >= 0.9 * window_s
    after = [s["name"] for s in sorted(tel.tracer.spans(),
                                       key=lambda s: s["start_ns"])
             if s["thread_id"] == idents["queued"] and s["name"] in _INSIDE]
    assert after == list(_INSIDE)
    # the histogram keeps its own meaning: requests that queued
    assert tel.metrics.snapshot()["histograms"][
        telemetry.M_QUEUE_WAIT_S]["count"] == 1


@pytest.mark.parametrize("rows,fetches", [(5, 1), (48, 1)],
                         ids=["single-chunk", "multi-chunk"])
def test_fetched_bytes_counts_what_execute_returned(rows, fetches):
    mf = _model()
    with Telemetry() as tel:
        out = executor.execute(mf, _rows(rows), batch_size=16)
    assert out.shape == (rows, _FEATURES)
    counters = tel.metrics.snapshot()["counters"]
    assert counters[telemetry.M_FETCHED_BYTES] == out.nbytes
    fetched = tel.tracer.spans(telemetry.SPAN_FETCH)
    assert len(fetched) == fetches
    assert sum(s["attributes"]["bytes"] for s in fetched) == out.nbytes
    assert len(tel.tracer.spans(telemetry.SPAN_LAUNCH)) == -(-rows // 16)


@pytest.mark.parametrize("rows", [5, 48])
def test_execute_bit_identical_with_and_without_a_scope(rows):
    mf = _model()
    x = _rows(rows, seed=7)
    plain = executor.execute(mf, x, batch_size=16)
    with Telemetry():
        traced = executor.execute(mf, x, batch_size=16)
    assert traced.dtype == plain.dtype
    np.testing.assert_array_equal(traced, plain)
