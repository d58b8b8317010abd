"""Overload protection for the device execution service (ISSUE 6
tentpole, core/executor.py): admission control (block vs shed),
deadline-aware shedding, priority lanes, the per-model circuit breaker,
read-time EngineConfig validation, and shutdown/reset idempotency."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.core import executor, health, resilience, telemetry
from sparkdl_tpu.core.executor import (
    ExecutorCircuitOpen,
    ExecutorOverloaded,
    ExecutorShutdown,
    deadline_scope,
    task_scope,
)
from sparkdl_tpu.core.health import HealthMonitor
from sparkdl_tpu.core.model_function import ModelFunction, TensorSpec
from sparkdl_tpu.core.resilience import Deadline, RetryPolicy
from sparkdl_tpu.core.telemetry import Telemetry
from sparkdl_tpu.engine.dataframe import EngineConfig
from sparkdl_tpu.engine.supervisor import run_partition_task

_ELEMENT = (6,)
_FEATURES = 3


@pytest.fixture(autouse=True)
def _fresh_executor_and_config():
    """Each test gets its own service instance and a full EngineConfig
    snapshot/restore (every public knob, so new overload knobs are
    covered without listing them)."""
    saved = EngineConfig.snapshot()
    executor.reset()
    yield
    executor.reset()
    EngineConfig.restore(saved)


def _model(name="overload_model", sleep_s=0.0, fail_flag=None):
    """Row-wise model; ``sleep_s`` injects host time at execution (via
    pure_callback) so a launch can be held in flight deterministically;
    ``fail_flag`` (a mutable [bool]) makes execution fail FATALLY while
    set — and heal when cleared — without recompiling."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(_ELEMENT[0], _FEATURES))
                    .astype(np.float32))

    def apply_fn(vs, x):
        if sleep_s or fail_flag is not None:
            def host_hook(a):
                if sleep_s:
                    time.sleep(sleep_s)
                if fail_flag is not None and fail_flag[0]:
                    raise ValueError(
                        "INVALID_ARGUMENT: deliberate terminal failure")
                return a
            x = jax.pure_callback(
                host_hook, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return jnp.tanh(x @ vs)

    return ModelFunction(apply_fn, w, TensorSpec((None,) + _ELEMENT,
                                                 "float32"), name=name)


def _rows(n, seed=1):
    return np.random.default_rng(seed).normal(
        size=(n,) + _ELEMENT).astype(np.float32)


def _record_apply_threads(mf):
    """Instrument apply_batch to record which thread ran it (and with
    which input object), returning (log, original_apply)."""
    log = []
    orig = mf.apply_batch

    def recording(tree, *args, **kwargs):
        log.append((threading.current_thread().name, id(tree)))
        return orig(tree, *args, **kwargs)

    mf.apply_batch = recording
    return log, orig


# ---------------------------------------------------------------------------
# Admission control: shed mode
# ---------------------------------------------------------------------------


def test_shed_mode_fails_fast_and_accounts_exactly():
    """Over the queue bound in shed mode: the overflow request raises
    ExecutorOverloaded (classified RETRYABLE) without queueing; every
    shed is one EXECUTOR_SHED health event, and the shed-rate and
    queue-depth gauges are live."""
    mf = _model(sleep_s=0.3)
    EngineConfig.coalesce_window_ms = 30_000.0  # park queued requests
    EngineConfig.executor_max_queued_requests = 1
    EngineConfig.executor_overload_mode = "shed"
    outcome = {}

    def busy():
        outcome["busy"] = executor.execute(mf, _rows(2, seed=0),
                                           batch_size=32)

    def queued(name):
        try:
            outcome[name] = executor.execute(mf, _rows(3, seed=1),
                                             batch_size=32)
        except BaseException as e:  # noqa: BLE001 - asserted below
            outcome[name + "_error"] = e

    with HealthMonitor() as mon, Telemetry() as tel:
        t_busy = threading.Thread(target=busy)
        t_busy.start()
        time.sleep(0.1)  # inline launch in flight
        t_a = threading.Thread(target=queued, args=("a",))
        t_a.start()
        time.sleep(0.05)  # a queued; queue is now at the bound
        t_b = threading.Thread(target=queued, args=("b",))
        t_b.start()
        t_b.join(timeout=5.0)
        assert not t_b.is_alive()
        # b was shed immediately — a is still parked in the window
        err = outcome.get("b_error")
        assert isinstance(err, ExecutorOverloaded)
        assert resilience.classify(err) == resilience.RETRYABLE
        executor.shutdown()  # release a from the parked window
        t_a.join(timeout=5.0)
        t_busy.join(timeout=5.0)
    assert isinstance(outcome.get("a_error"), ExecutorShutdown)
    assert mon.count(health.EXECUTOR_SHED) == 1
    snap = tel.metrics.snapshot()
    assert snap["counters"]["sparkdl.health." + health.EXECUTOR_SHED] == 1
    # 1 shed of 3 submits seen by bounded admission (busy inline + a + b)
    assert snap["gauges"][telemetry.M_EXECUTOR_SHED_RATE] == \
        pytest.approx(1 / 3)
    assert telemetry.M_EXECUTOR_QUEUE_DEPTH in snap["gauges"]


def test_queued_rows_bound_sheds_but_empty_queue_always_admits():
    mf = _model(sleep_s=0.25)
    EngineConfig.coalesce_window_ms = 30_000.0
    EngineConfig.executor_max_queued_rows = 4
    EngineConfig.executor_overload_mode = "shed"
    outcome = {}

    def run(name, n, seed):
        try:
            outcome[name] = executor.execute(mf, _rows(n, seed=seed),
                                             batch_size=32)
        except BaseException as e:  # noqa: BLE001 - asserted below
            outcome[name + "_error"] = e

    t_busy = threading.Thread(target=run, args=("busy", 2, 0))
    t_busy.start()
    time.sleep(0.08)
    # 6 rows > the 4-row bound, but the queue is EMPTY: always admitted
    # (a bound smaller than one request must not wedge)
    t_big = threading.Thread(target=run, args=("big", 6, 1))
    t_big.start()
    time.sleep(0.05)
    # now 6 rows are queued: any further queued rows exceed the bound
    t_over = threading.Thread(target=run, args=("over", 2, 2))
    t_over.start()
    t_over.join(timeout=5.0)
    assert isinstance(outcome.get("over_error"), ExecutorOverloaded)
    executor.shutdown()
    t_big.join(timeout=5.0)
    t_busy.join(timeout=5.0)
    assert isinstance(outcome.get("big_error"), ExecutorShutdown)


# ---------------------------------------------------------------------------
# Admission control: block (backpressure) mode
# ---------------------------------------------------------------------------


def test_block_mode_waits_for_room_and_completes():
    """Default overload mode: a submit over the bound BLOCKS until the
    coalescer drains the queue, then completes normally — backpressure,
    not failure."""
    mf = _model(sleep_s=0.1)
    EngineConfig.coalesce_window_ms = 50.0
    EngineConfig.executor_max_queued_requests = 1
    assert EngineConfig.executor_overload_mode == "block"  # the default
    inputs = [_rows(3, seed=i) for i in range(4)]
    expected = [mf.apply_batch(x, batch_size=32) for x in inputs]
    results = [None] * 4
    errors = [None] * 4
    barrier = threading.Barrier(4)

    def work(i):
        try:
            barrier.wait()
            results[i] = executor.execute(mf, inputs[i], batch_size=32)
        except BaseException as e:  # noqa: BLE001 - asserted below
            errors[i] = e

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20.0)
    assert not any(t.is_alive() for t in threads)
    assert errors == [None] * 4
    for got, want in zip(results, expected):
        np.testing.assert_array_equal(got, want)


def test_block_mode_backpressure_is_bounded_by_the_deadline():
    mf = _model(sleep_s=0.4)
    EngineConfig.coalesce_window_ms = 30_000.0  # nothing drains
    EngineConfig.executor_max_queued_requests = 1
    outcome = {}

    def run(name, seed, deadline=None):
        try:
            outcome[name] = executor.execute(mf, _rows(2, seed=seed),
                                             batch_size=32,
                                             deadline=deadline)
        except BaseException as e:  # noqa: BLE001 - asserted below
            outcome[name + "_error"] = e

    with HealthMonitor() as mon:
        t_busy = threading.Thread(target=run, args=("busy", 0))
        t_busy.start()
        time.sleep(0.1)
        t_a = threading.Thread(target=run, args=("a", 1))
        t_a.start()
        time.sleep(0.05)  # queue full; b must block...
        t0 = time.monotonic()
        t_b = threading.Thread(target=run, args=("b", 2, Deadline(0.25)))
        t_b.start()
        t_b.join(timeout=5.0)
        waited = time.monotonic() - t0
        assert not t_b.is_alive()
        err = outcome.get("b_error")
        assert isinstance(err, resilience.DeadlineExceeded)
        assert 0.15 < waited < 2.0  # blocked ~the deadline, not forever
        executor.shutdown()
        t_a.join(timeout=5.0)
        t_busy.join(timeout=5.0)
    assert mon.count(health.EXECUTOR_DEADLINE_SHED) == 1


# ---------------------------------------------------------------------------
# Deadline propagation: drop expired requests before paying for a launch
# ---------------------------------------------------------------------------


def test_expired_request_is_dropped_at_drain_time_without_a_launch():
    mf = _model(sleep_s=0.2)
    EngineConfig.coalesce_window_ms = 400.0
    apply_log, orig_apply = _record_apply_threads(mf)
    outcome = {}

    def busy():
        outcome["busy"] = executor.execute(mf, _rows(2, seed=0),
                                           batch_size=32)

    def doomed():
        t0 = time.monotonic()
        try:
            # expires while queued (the window is 400 ms, the budget 80)
            outcome["doomed"] = executor.execute(
                mf, _rows(3, seed=1), batch_size=32,
                deadline=Deadline(0.08))
        except BaseException as e:  # noqa: BLE001 - asserted below
            outcome["doomed_error"] = e
        outcome["doomed_s"] = time.monotonic() - t0

    with HealthMonitor() as mon:
        t_busy = threading.Thread(target=busy)
        t_busy.start()
        time.sleep(0.05)  # inline launch in flight
        t_d = threading.Thread(target=doomed)
        t_d.start()
        t_d.join(timeout=5.0)
        t_busy.join(timeout=5.0)
    err = outcome.get("doomed_error")
    assert isinstance(err, resilience.DeadlineExceeded)
    # and PROMPTLY: the queued deadline caps the coalescer's window wait,
    # so the caller fails at ~its 80 ms budget, not after the 400 ms
    # window (margin for CI scheduling jitter)
    assert outcome["doomed_s"] < 0.3, outcome["doomed_s"]
    assert mon.count(health.EXECUTOR_DEADLINE_SHED) == 1
    # the doomed request never paid for a launch: apply_batch ran only
    # for the busy inline request
    assert len(apply_log) == 1
    np.testing.assert_array_equal(outcome["busy"],
                                  orig_apply(_rows(2, seed=0),
                                             batch_size=32))


def test_already_expired_deadline_is_rejected_before_queueing():
    mf = _model(sleep_s=0.1)
    EngineConfig.coalesce_window_ms = 100.0
    dead = Deadline(0.0)
    time.sleep(0.01)
    # force the queued path (not inline) by keeping the state busy
    t_busy = threading.Thread(
        target=lambda: executor.execute(mf, _rows(2, seed=0),
                                        batch_size=32))
    t_busy.start()
    time.sleep(0.04)
    with HealthMonitor() as mon:
        with pytest.raises(resilience.DeadlineExceeded):
            executor.execute(mf, _rows(3, seed=1), batch_size=32,
                             deadline=dead)
    t_busy.join(timeout=5.0)
    assert mon.count(health.EXECUTOR_DEADLINE_SHED) == 1


def test_run_partition_task_threads_its_deadline_into_the_executor():
    """The supervisor's per-task Deadline rides into executor calls
    ambiently (deadline_scope), and Deadline(None) is NOT threaded —
    the unloaded hot path stays free of expiry checks."""
    seen = {}

    def op(batch):
        seen["deadline"] = executor.current_deadline()
        return batch

    fast = RetryPolicy(max_retries=0, base_delay_s=0.0, jitter=0.0)
    run_partition_task(0, "x", [op], policy=fast, deadline_s=5.0)
    assert seen["deadline"] is not None
    assert seen["deadline"].timeout_s == 5.0
    assert seen["deadline"].remaining() <= 5.0
    run_partition_task(0, "x", [op], policy=fast, deadline_s=None)
    assert seen["deadline"] is None
    assert executor.current_deadline() is None  # scope restored


# ---------------------------------------------------------------------------
# Priority lanes
# ---------------------------------------------------------------------------


def test_interactive_lane_drains_before_earlier_bulk_requests():
    """Three requests queue behind a busy launch: interactive arrives
    LAST but is drained into the first coalesced launch; the overflowing
    request (bulk, by lane order) replays alone in the next round. Had
    the drain been FIFO, the two bulk requests would have coalesced and
    the interactive one would have replayed."""
    mf = _model(sleep_s=0.25)
    EngineConfig.coalesce_window_ms = 400.0
    # cap 7: the window does NOT fill at the two bulk requests (6 rows),
    # so the late interactive arrival is present at drain time — and the
    # drain then fits exactly two of the three 3-row requests
    EngineConfig.coalesce_max_rows = 7
    apply_log, orig_apply = _record_apply_threads(mf)
    inputs = {"bulk1": _rows(3, seed=1), "bulk2": _rows(3, seed=2),
              "inter": _rows(3, seed=3)}
    # each answer is held to the oracle at ITS launch's shape: inter +
    # bulk1 go up together, 6 rows in a bucket of 7 (the cap), bulk2 alone
    # in a bucket of 8 — and XLA's CPU dot sums a row of a 7-row operand
    # in another order than the same row of an 8-row one (1 ulp, on some
    # CPUs only)
    together = orig_apply(np.concatenate([inputs["inter"], inputs["bulk1"]]),
                          batch_size=7)
    expected = {"inter": together[:3], "bulk1": together[3:],
                "bulk2": orig_apply(inputs["bulk2"], batch_size=32)}
    outcome = {}
    errors = []

    def run(name, priority):
        try:
            outcome[name] = executor.execute(mf, inputs[name],
                                             batch_size=32,
                                             priority=priority)
        except BaseException as e:  # noqa: BLE001
            errors.append((name, e))

    t_busy = threading.Thread(
        target=lambda: executor.execute(mf, _rows(2, seed=0),
                                        batch_size=32),
        name="requester-busy")
    t_busy.start()
    time.sleep(0.08)  # inline launch in flight
    threads = []
    for name, prio, delay in (("bulk1", "bulk", 0.0),
                              ("bulk2", "bulk", 0.04),
                              ("inter", "interactive", 0.08)):
        time.sleep(delay and 0.04)
        t = threading.Thread(target=run, args=(name, prio),
                             name=f"requester-{name}")
        t.start()
        threads.append(t)
    for t in threads + [t_busy]:
        t.join(timeout=10.0)
    assert not errors, errors
    for name, want in expected.items():
        np.testing.assert_array_equal(outcome[name], want)
    # interactive + bulk1 went up in the coalesced launch; only the busy
    # inline request and the displaced-to-next-round bulk2 ran through
    # apply_batch on their own threads
    replay_threads = {name for name, _ in apply_log}
    assert replay_threads == {"requester-busy", "requester-bulk2"}


def test_shed_mode_interactive_displaces_newest_queued_bulk():
    mf = _model(sleep_s=0.3)
    EngineConfig.coalesce_window_ms = 30_000.0
    EngineConfig.executor_max_queued_requests = 1
    EngineConfig.executor_overload_mode = "shed"
    outcome = {}

    def run(name, priority, seed):
        try:
            outcome[name] = executor.execute(mf, _rows(3, seed=seed),
                                             batch_size=32,
                                             priority=priority)
        except BaseException as e:  # noqa: BLE001 - asserted below
            outcome[name + "_error"] = e

    with HealthMonitor() as mon:
        t_busy = threading.Thread(target=run, args=("busy", "bulk", 0))
        t_busy.start()
        time.sleep(0.1)
        t_bulk = threading.Thread(target=run, args=("bulk", "bulk", 1))
        t_bulk.start()
        time.sleep(0.05)  # bulk queued; queue at the bound
        t_inter = threading.Thread(target=run,
                                   args=("inter", "interactive", 2))
        t_inter.start()
        # the bulk request is displaced IMMEDIATELY (not at drain time)
        t_bulk.join(timeout=5.0)
        assert not t_bulk.is_alive()
        err = outcome.get("bulk_error")
        assert isinstance(err, ExecutorOverloaded)
        assert "displaced" in str(err)
        executor.shutdown()  # release the interactive request (parked)
        t_inter.join(timeout=5.0)
        t_busy.join(timeout=5.0)
    # the interactive request took the queue slot (it was parked in the
    # 30s window until shutdown, proving it was queued, not shed)
    assert isinstance(outcome.get("inter_error"), ExecutorShutdown)
    sheds = mon.events(health.EXECUTOR_SHED)
    assert len(sheds) == 1 and sheds[0]["reason"] == "displaced"


# ---------------------------------------------------------------------------
# Per-model circuit breaker
# ---------------------------------------------------------------------------


def test_breaker_trips_fails_fast_probes_and_recovers():
    fail = [True]
    mf = _model(name="breaker_model", fail_flag=fail)
    EngineConfig.executor_breaker_threshold = 2
    EngineConfig.executor_breaker_window_s = 30.0
    EngineConfig.executor_breaker_cooldown_s = 0.15
    x = _rows(3, seed=1)
    calls = []
    orig = mf.apply_batch

    def counting(tree, *args, **kwargs):
        calls.append(1)
        return orig(tree, *args, **kwargs)

    mf.apply_batch = counting
    with HealthMonitor() as mon:
        # two terminal (FATAL) launch failures within the window trip it
        for _ in range(2):
            with pytest.raises(Exception) as ei:
                executor.execute(mf, x, batch_size=32)
            assert resilience.classify(ei.value) == resilience.FATAL
        assert mon.count(health.BREAKER_OPEN) == 1
        assert len(calls) == 2
        # open: fail fast WITHOUT touching the model or the queue
        with pytest.raises(ExecutorCircuitOpen) as ei:
            executor.execute(mf, x, batch_size=32)
        assert resilience.classify(ei.value) == resilience.RETRYABLE
        assert len(calls) == 2  # the fast-fail never reached the model
        # model heals; after the cooldown one half-open probe goes
        # through and recovery reopens traffic
        fail[0] = False
        time.sleep(0.2)
        out = executor.execute(mf, x, batch_size=32)
        np.testing.assert_array_equal(out, orig(x, batch_size=32))
        assert mon.count(health.BREAKER_PROBE) == 1
        assert mon.count(health.BREAKER_CLOSED) == 1
        # traffic flows again, no fast-fails
        np.testing.assert_array_equal(
            executor.execute(mf, x, batch_size=32),
            orig(x, batch_size=32))
    assert mon.count(health.BREAKER_OPEN) == 1


def test_breaker_failed_probe_reopens():
    fail = [True]
    mf = _model(name="breaker_reopen", fail_flag=fail)
    EngineConfig.executor_breaker_threshold = 1
    EngineConfig.executor_breaker_cooldown_s = 0.1
    x = _rows(2, seed=1)
    with HealthMonitor() as mon:
        with pytest.raises(Exception):
            executor.execute(mf, x, batch_size=32)
        assert mon.count(health.BREAKER_OPEN) == 1
        time.sleep(0.15)
        # the probe itself fails: breaker re-opens (probe=True trip)
        with pytest.raises(Exception) as ei:
            executor.execute(mf, x, batch_size=32)
        assert not isinstance(ei.value, ExecutorCircuitOpen)
        assert mon.count(health.BREAKER_PROBE) == 1
        assert mon.count(health.BREAKER_OPEN) == 2
        # and fails fast again while re-opened
        with pytest.raises(ExecutorCircuitOpen):
            executor.execute(mf, x, batch_size=32)
    assert mon.count(health.BREAKER_CLOSED) == 0


def test_probe_dying_in_queue_releases_the_probe_slot():
    """Regression: a half-open probe that EXPIRES in the queue — it never
    reached the device — must return the breaker to
    half-open-with-no-probe so the NEXT arrival probes, instead of
    wedging every future submit on 'probe in flight' forever."""
    def hooked(vs, x):
        def host_hook(a):
            if a[0, 0] >= 900.0:
                time.sleep(0.8)        # a launch held in flight
            if a[0, 0] <= -900.0:
                raise ValueError("INVALID_ARGUMENT: poisoned input")
            return a
        x = jax.pure_callback(host_hook,
                              jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(_ELEMENT[0], _FEATURES))
                        .astype(np.float32))
        return jnp.tanh(x @ w)

    mf = ModelFunction(hooked, jnp.zeros(()),
                       TensorSpec((None,) + _ELEMENT, "float32"),
                       name="probe_wedge")
    EngineConfig.executor_breaker_threshold = 1
    EngineConfig.executor_breaker_cooldown_s = 0.05
    EngineConfig.coalesce_window_ms = 150.0
    ok = _rows(2, seed=1)
    bad = ok.copy()
    bad[0, 0] = -999.0
    slow = ok.copy()
    slow[0, 0] = 999.0
    with HealthMonitor() as mon:
        with pytest.raises(Exception) as ei:
            executor.execute(mf, bad, batch_size=32)  # inline FATAL: trip
        assert resilience.classify(ei.value) == resilience.FATAL
        assert mon.count(health.BREAKER_OPEN) == 1
        time.sleep(0.1)  # past the cooldown
        # hold a launch in flight WITHOUT consuming the probe slot (the
        # breaker knobs are per-submit snapshots: this submit opts out)
        EngineConfig.executor_breaker_threshold = 0
        busy = threading.Thread(target=lambda: executor.execute(
            mf, slow, batch_size=32))
        busy.start()
        time.sleep(0.1)  # the inline launch is in flight
        EngineConfig.executor_breaker_threshold = 1
        # probe #1: admitted half-open, QUEUED behind the busy launch,
        # and expires in the queue before the window drains
        with pytest.raises(resilience.DeadlineExceeded):
            executor.execute(mf, ok, batch_size=32,
                             deadline=Deadline(0.03))
        assert mon.count(health.BREAKER_PROBE) == 1
        # the slot was released: the next arrival is probe #2 (it would
        # raise ExecutorCircuitOpen 'probe in flight' if wedged), and its
        # success closes the breaker
        out = executor.execute(mf, ok, batch_size=32)
        np.testing.assert_array_equal(out, mf.apply_batch(ok,
                                                          batch_size=32))
        busy.join(timeout=5.0)
        assert not busy.is_alive()
    assert mon.count(health.BREAKER_PROBE) == 2
    assert mon.count(health.BREAKER_CLOSED) == 1
    assert mon.count(health.EXECUTOR_DEADLINE_SHED) == 1


def test_stale_nonprobe_outcome_does_not_decide_half_open_probe():
    """Regression: a pre-trip launch resolving DURING half-open must not
    close or reopen the breaker — 'exactly one probe; ITS outcome
    decides'. A stale failure only joins the rolling window."""
    mf = _model(name="stale_halfopen")
    EngineConfig.executor_breaker_threshold = 1
    executor.execute(mf, _rows(2), batch_size=16)  # prime the fn state
    svc = executor.service()
    state = next(iter(svc._states.values()))
    with state.cond:
        state.breaker_state = "half_open"
        state.breaker_probe_inflight = True
    with HealthMonitor() as mon:
        svc._breaker_note(state, None)  # stale success: ignored
        assert state.breaker_state == "half_open"
        assert state.breaker_probe_inflight
        svc._breaker_note(state, RuntimeError("stale launch failure"))
        assert state.breaker_state == "half_open"
        assert state.breaker_probe_inflight
        svc._breaker_note(state, None, is_probe=True)  # the probe decides
        assert state.breaker_state == "closed"
        assert not state.breaker_probe_inflight
    assert mon.count(health.BREAKER_CLOSED) == 1
    assert mon.count(health.BREAKER_OPEN) == 0


def test_hedge_dedup_adopts_the_latest_deadline():
    """Regression: a hedge deduping onto its sibling's QUEUED request
    must not inherit the primary's nearly-expired deadline — the shared
    request lives as long as the latest waiter's budget, so the hedge
    can still rescue a straggling primary instead of dying with it."""
    mf = _model(sleep_s=0.15)
    EngineConfig.coalesce_window_ms = 300.0
    token = ("hedged-task", 7)
    x = _rows(3, seed=1)
    outcome = {}

    def busy():
        outcome["busy"] = executor.execute(mf, _rows(2, seed=0),
                                           batch_size=32)

    def primary():
        with task_scope(token):
            try:
                outcome["primary"] = executor.execute(
                    mf, x, batch_size=32, deadline=Deadline(0.08))
            except BaseException as e:  # noqa: BLE001 - asserted below
                outcome["primary_error"] = e

    def hedge():
        with task_scope(token):
            outcome["hedge"] = executor.execute(
                mf, x, batch_size=32, deadline=Deadline(10.0))

    t_busy = threading.Thread(target=busy)
    t_busy.start()
    time.sleep(0.05)  # inline launch in flight -> primary queues
    t_p = threading.Thread(target=primary)
    t_p.start()
    time.sleep(0.02)  # primary queued; hedge dedups onto it
    t_h = threading.Thread(target=hedge)
    t_h.start()
    for t in (t_busy, t_p, t_h):
        t.join(timeout=10.0)
        assert not t.is_alive()
    # the shared request survived past the primary's 80 ms budget and
    # delivered to BOTH waiters (without the deadline merge, the drain
    # at ~300 ms would have dropped it and failed both)
    expected = mf.apply_batch(x, batch_size=32)
    np.testing.assert_array_equal(outcome["hedge"], expected)
    assert "primary_error" not in outcome
    np.testing.assert_array_equal(outcome["primary"], expected)


def test_invalid_priority_raises_instead_of_hanging():
    """Regression: a typo'd lane on a direct execute()/submit() call must
    raise immediately — queued into an undrained lane it would park the
    caller forever."""
    mf = _model()
    with pytest.raises(ValueError, match="priority"):
        executor.execute(mf, _rows(2), batch_size=16,
                         priority="INTERACTIVE")


def test_breaker_disabled_by_default_never_records_events():
    fail = [True]
    mf = _model(name="no_breaker", fail_flag=fail)
    x = _rows(2, seed=1)
    assert EngineConfig.executor_breaker_threshold == 0
    with HealthMonitor() as mon:
        for _ in range(3):
            with pytest.raises(Exception) as ei:
                executor.execute(mf, x, batch_size=32)
            assert not isinstance(ei.value, ExecutorCircuitOpen)
    assert mon.count(health.BREAKER_OPEN) == 0


# ---------------------------------------------------------------------------
# Shutdown / reset idempotency and submit races (satellite)
# ---------------------------------------------------------------------------


def test_double_shutdown_and_double_reset_are_noops():
    mf = _model()
    executor.execute(mf, _rows(2), batch_size=16)  # prime a state
    executor.shutdown()
    executor.shutdown()  # idempotent: no error, no hang
    svc = executor.reset()
    assert executor.service() is svc
    svc2 = executor.reset()  # reset over a fresh service is fine too
    assert executor.service() is svc2
    # and the new service works
    np.testing.assert_array_equal(
        executor.execute(mf, _rows(2), batch_size=16),
        mf.apply_batch(_rows(2), batch_size=16))


def test_shutdown_racing_concurrent_submits_never_hangs_or_leaks():
    """Submitters hammer the service while it is shut down mid-flight:
    every submit either returns a correct result or raises
    ExecutorShutdown — never a hang, never a leaked future, and a
    post-shutdown submit on the SAME service always raises."""
    mf = _model(sleep_s=0.02)
    EngineConfig.coalesce_window_ms = 20.0
    x = _rows(3, seed=1)
    expected = mf.apply_batch(x, batch_size=32)
    bad = []
    done = []

    def submitter():
        while True:
            try:
                out = executor.execute(mf, x, batch_size=32)
                np.testing.assert_array_equal(out, expected)
            except ExecutorShutdown:
                done.append(1)
                return
            except BaseException as e:  # noqa: BLE001 - asserted below
                bad.append(e)
                return

    threads = [threading.Thread(target=submitter) for _ in range(6)]
    for t in threads:
        t.start()
    time.sleep(0.15)
    executor.shutdown()
    executor.shutdown()  # racing double-shutdown stays a no-op
    for t in threads:
        t.join(timeout=10.0)
    assert not any(t.is_alive() for t in threads)
    assert not bad, bad
    assert len(done) == 6
    with pytest.raises(ExecutorShutdown):
        executor.service().submit(mf, x, len(x), 32, None, 1,
                                  resilience.DEFAULT_INFERENCE_POLICY,
                                  None, 32, 0)


# ---------------------------------------------------------------------------
# EngineConfig read-time validation (satellite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("knob,value", [
    ("max_task_retries", -1),
    ("task_retry_delay_s", -0.5),
    ("task_timeout_s", -3.0),
    ("task_timeout_s", 0.0),
    ("speculation_quantile", 1.5),
    ("speculation_quantile", -0.1),
    ("speculation_multiplier", 0.0),
    ("speculation_min_runtime_s", -1.0),
    ("quarantine_max_fatal", 0),
    ("coalesce_window_ms", -5.0),
    ("coalesce_max_rows", 0),
    ("executor_max_queued_requests", 0),
    ("executor_max_queued_requests", -2),
    ("executor_max_queued_rows", 0),
    ("executor_overload_mode", "drop"),
    ("executor_default_priority", "realtime"),
    ("executor_breaker_threshold", -1),
    ("executor_breaker_window_s", 0.0),
    ("executor_breaker_cooldown_s", -1.0),
    ("inference_precision", "float16"),
    ("inference_precision", "fp32"),
    ("inference_precision", None),
    ("inference_donate_buffers", "yes"),
    ("inference_donate_buffers", 1),
    ("bucket_ladder", "adaptive"),
    ("bucket_ladder", None),
    ("cluster_workers", -1),
    ("cluster_inflight_partitions", 0),
    ("cluster_inflight_partitions", -3),
    ("max_workers", 0),
])
def test_engine_config_validation_rejects(knob, value):
    setattr(EngineConfig, knob, value)
    with pytest.raises(ValueError, match=knob):
        EngineConfig.validate()


def test_bad_knobs_fail_at_the_read_site_not_downstream():
    mf = _model()
    EngineConfig.executor_max_queued_requests = 0
    with pytest.raises(ValueError, match="executor_max_queued_requests"):
        executor.execute(mf, _rows(2), batch_size=16)
    EngineConfig.executor_max_queued_requests = None
    EngineConfig.task_timeout_s = -1.0
    from sparkdl_tpu.engine.dataframe import DataFrame

    df = DataFrame.fromRows([{"x": i} for i in range(4)], numPartitions=2)
    with pytest.raises(ValueError, match="task_timeout_s"):
        df.mapPartitions(lambda b: b).collect()


def test_defaults_validate_cleanly_and_stay_unbounded():
    EngineConfig.validate()  # the shipped defaults are always legal
    assert EngineConfig.executor_max_queued_requests is None
    assert EngineConfig.executor_max_queued_rows is None
    assert EngineConfig.executor_overload_mode == "block"
    assert EngineConfig.executor_default_priority == "bulk"
    assert EngineConfig.executor_breaker_threshold == 0


# ---------------------------------------------------------------------------
# Transformer priority param plumbing
# ---------------------------------------------------------------------------


def test_transformer_priority_param_validates_and_rides_to_execute(
        monkeypatch):
    import pyarrow as pa

    from sparkdl_tpu.core import executor as device_executor
    from sparkdl_tpu.engine.dataframe import DataFrame
    from sparkdl_tpu.ml.tensor_transformer import TPUTransformer

    with pytest.raises(TypeError, match="priority"):
        TPUTransformer(inputCol="x", outputCol="y", priority="realtime")

    mf = _model()
    t = TPUTransformer(inputCol="x", outputCol="y", modelFunction=mf,
                       batchSize=16, priority="interactive")
    assert t.getPriority() == "interactive"
    seen = []
    orig_execute = device_executor.execute

    def spying_execute(*args, **kwargs):
        seen.append(kwargs.get("priority"))
        return orig_execute(*args, **kwargs)

    monkeypatch.setattr(device_executor, "execute", spying_execute)
    df = DataFrame.fromColumns(
        {"x": _rows(5).reshape(5, -1)}, numPartitions=2)
    out = t.transform(df).collect()
    assert len(out) == 5
    assert seen and all(p == "interactive" for p in seen)
    # unset: the transformer defers to EngineConfig's default lane
    t2 = TPUTransformer(inputCol="x", outputCol="y", modelFunction=mf,
                        batchSize=16)
    assert t2.getPriority() is None
