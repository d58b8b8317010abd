"""Engine task supervision: classified retry, attempt history, deadline
watchdog, speculative hedging, quarantine (docs/RESILIENCE.md)."""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.core import health, resilience
from sparkdl_tpu.core.health import HealthMonitor
from sparkdl_tpu.core.resilience import (
    Fault,
    FaultInjector,
    RetryPolicy,
    WorkerFault,
    classify,
)
from sparkdl_tpu.engine import DataFrame, EngineConfig, TaskFailure
from sparkdl_tpu.engine.supervisor import run_partition_task

# full snapshot of every public knob (ISSUE 6: the overload knobs — and
# any future knob — are covered without listing them)
_DEFAULTS = EngineConfig.snapshot()


@pytest.fixture(autouse=True)
def _restore_engine_config():
    yield
    for k, v in _DEFAULTS.items():
        setattr(EngineConfig, k, v)


def make_df(n=12, parts=4):
    return DataFrame.fromRows([{"x": i} for i in range(n)],
                              numPartitions=parts)


FAST = RetryPolicy(max_retries=2, base_delay_s=0.0, jitter=0.0)


# -- classified retry at the task level --------------------------------------

def test_fatal_op_error_never_retried():
    calls = []
    df = make_df(6, 3)

    def bad(x):
        calls.append(x)
        if x == 3:  # lands in partition 1
            raise ValueError("deliberate shape error")
        return x

    out = df.withColumn("y", bad, ["x"], pa.int64())
    with pytest.raises(TaskFailure) as ei:
        out.collect()
    tf = ei.value
    assert tf.failure_kind == resilience.FATAL
    assert tf.retries() == 0
    assert len(tf.attempts) == 1 and tf.attempts[0].kind == resilience.FATAL
    assert "ValueError" in tf.attempts[0].error
    assert calls.count(3) == 1  # provably retried zero times
    # classified wrappers: upstream retry layers must see FATAL
    assert classify(tf) == resilience.FATAL


def test_oom_escaping_ops_not_retried_at_task_level():
    calls = []

    def oom(batch):
        calls.append(1)
        raise resilience.DeviceOOM()

    df = make_df(4, 2).mapPartitions(oom)
    with pytest.raises(TaskFailure) as ei:
        df.collect()
    assert ei.value.failure_kind == resilience.OOM
    assert classify(ei.value) == resilience.OOM
    # 2 partitions, one attempt each — no same-shape OOM replays
    assert len(calls) == 2


def test_retryable_errors_backed_off_with_history():
    failures = {"n": 2}
    lock = threading.Lock()

    def flaky(batch):
        with lock:
            if failures["n"] > 0:
                failures["n"] -= 1
                raise RuntimeError("UNAVAILABLE: worker lost")
        return batch

    with HealthMonitor() as mon:
        assert make_df(4, 1).mapPartitions(flaky).count() == 4
    assert mon.count(health.TASK_RETRIED) == 2


def test_retry_exhaustion_carries_full_attempt_history():
    def always(batch):
        raise RuntimeError("UNAVAILABLE: permanently lost")

    EngineConfig.max_task_retries = 2
    with pytest.raises(TaskFailure) as ei:
        make_df(4, 2).mapPartitions(always).collect()
    tf = ei.value
    assert tf.failure_kind == resilience.RETRYABLE
    assert len(tf.attempts) == 3  # initial + 2 retries
    assert all(a.kind == resilience.RETRYABLE for a in tf.attempts)
    assert all(a.duration_s >= 0 for a in tf.attempts)
    assert tf.index is not None


def test_run_partition_task_backoff_uses_policy(monkeypatch):
    slept = []
    attempts = {"n": 0}

    def flaky(batch):
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise resilience.TransferStall()
        return batch

    policy = RetryPolicy(max_retries=3, base_delay_s=1.0, jitter=0.0)
    out = run_partition_task(0, "batch", [flaky], policy=policy,
                             sleep=slept.append)
    assert out == "batch"
    assert slept == [1.0, 2.0]  # exponential, from the policy


# -- unified fault injection --------------------------------------------------

def test_engine_task_injection_point_recovers_via_retry():
    df = make_df(8, 2).withColumn("y", lambda x: x * 2, ["x"], pa.int64())
    with FaultInjector.seeded(0, engine_task=1) as inj:
        with HealthMonitor() as mon:
            rows = df.collect()
    assert [r["y"] for r in rows] == [2 * i for i in range(8)]
    assert inj.fired["engine_task"] == 1
    assert mon.count(health.TASK_RETRIED) == 1
    assert classify(WorkerFault()) == resilience.RETRYABLE


def test_engine_task_finish_phase_discards_computed_attempt():
    """A worker dying AFTER computing but before delivering its result:
    the retried attempt recomputes and the output is bit-identical."""
    calls = []

    def track(x):
        calls.append(x)
        return x + 1

    df = make_df(6, 1).withColumn("y", track, ["x"], pa.int64())
    with FaultInjector.seeded(0, engine_task=Fault(
            times=1, when=lambda c: c.get("phase") == "finish")) as inj:
        rows = df.collect()
    assert inj.fired["engine_task"] == 1
    assert [r["y"] for r in rows] == [i + 1 for i in range(6)]
    assert calls == list(range(6)) * 2  # attempt 0 discarded, attempt 1 kept


def test_legacy_fault_injector_shim_still_works():
    seen = []

    def injector(pidx, attempt):
        seen.append((pidx, attempt))
        if pidx == 1 and attempt == 0:
            raise RuntimeError("transient")

    EngineConfig.fault_injector = injector
    assert make_df(6, 3).withColumn(
        "y", lambda x: x, ["x"], pa.int64()).count() == 6
    assert (1, 0) in seen and (1, 1) in seen


# -- deadline watchdog --------------------------------------------------------

def test_stalled_task_fails_via_deadline_instead_of_hanging():
    EngineConfig.task_timeout_s = 0.3
    df = make_df(9, 3).withColumn("y", lambda x: x, ["x"], pa.int64())
    t0 = time.monotonic()
    with FaultInjector.seeded(0, task_stall=Fault(
            when=lambda c: c["partition"] == 1)) as inj:
        with HealthMonitor() as mon:
            with pytest.raises(TaskFailure, match="deadline"):
                df.collect()
    elapsed = time.monotonic() - t0
    assert inj.fired["task_stall"] == 1
    assert elapsed < 5.0  # the watchdog fired; no hang
    assert mon.count(health.TASK_DEADLINE_EXCEEDED) == 1
    ev = mon.events(health.TASK_DEADLINE_EXCEEDED)[0]
    assert ev["partition"] == 1


def test_deadline_failure_classified_fatal():
    """DeadlineExceeded is the retry budget — it must not be retried by
    the task loop or any upstream gang boundary."""
    EngineConfig.task_timeout_s = 0.2
    with FaultInjector.seeded(0, task_stall=Fault(
            when=lambda c: c["partition"] == 0)):
        with pytest.raises(TaskFailure) as ei:
            make_df(4, 2).withColumn(
                "y", lambda x: x, ["x"], pa.int64()).collect()
    assert ei.value.failure_kind == resilience.FATAL
    assert classify(ei.value) == resilience.FATAL


def test_cooperative_deadline_on_inline_path():
    """Inline (nested / limit) execution has no watchdog thread; the
    cooperative check between ops still bounds the task."""

    def slow(batch):
        time.sleep(0.3)
        return batch

    with pytest.raises(TaskFailure, match="deadline"):
        run_partition_task(0, pa.RecordBatch.from_pylist([{"x": 1}]),
                           [slow, slow], policy=FAST, deadline_s=0.2)


# -- speculative execution (hedging) ------------------------------------------

def test_straggler_partition_hedged_first_result_wins():
    EngineConfig.speculation = True
    EngineConfig.speculation_quantile = 0.5
    EngineConfig.speculation_min_runtime_s = 0.05
    # fresh, wide pool: a narrow or contaminated shared pool (a sleeper
    # left by an earlier test) would queue the hedge behind the straggler
    EngineConfig.max_workers = 9
    ran = set()
    lock = threading.Lock()

    def op(batch):
        first = batch.column(0)[0].as_py()
        with lock:
            hedge_run = (first in ran)
            ran.add(first)
        if first == 15 and not hedge_run:
            # the PRIMARY attempt of the last partition straggles
            # (environmental slowness: the re-executed copy is fast)
            time.sleep(2.0)
        return batch

    df = DataFrame.fromRows([{"x": i} for i in range(18)], numPartitions=6)
    baseline = df.collect()
    slow = df.mapPartitions(op)
    t0 = time.monotonic()
    with HealthMonitor() as mon:
        rows = slow.collect()
    elapsed = time.monotonic() - t0
    # bit-identical, order-preserving, deduplicated
    assert rows == baseline
    assert mon.count(health.TASK_HEDGED) == 1
    assert mon.count(health.HEDGE_WON) == 1
    assert mon.events(health.TASK_HEDGED)[0]["partition"] == 5
    assert elapsed < 1.5  # the hedge won; nobody waited out the straggler


def test_hedge_loser_bails_quietly_after_task_resolves():
    """A discarded loser must not keep retrying or record failure events
    for a task that already succeeded via its hedge."""
    EngineConfig.speculation = True
    EngineConfig.speculation_quantile = 0.5
    EngineConfig.speculation_min_runtime_s = 0.05
    EngineConfig.max_workers = 10  # fresh, wide pool (see straggler test)
    ran = set()
    lock = threading.Lock()

    def op(batch):
        first = batch.column(0)[0].as_py()
        with lock:
            hedge_run = (first in ran)
            ran.add(first)
        if first == 15 and not hedge_run:
            time.sleep(1.0)
            # the straggling primary then dies retryably — after the
            # hedge already won, this must be swallowed silently
            raise RuntimeError("UNAVAILABLE: straggler worker lost")
        return batch

    df = DataFrame.fromRows([{"x": i} for i in range(18)], numPartitions=6)
    baseline = df.collect()
    with HealthMonitor() as mon:
        rows = df.mapPartitions(op).collect()
        time.sleep(1.3)  # outlive the loser's wake-up with monitor active
    assert rows == baseline
    assert mon.count(health.HEDGE_WON) == 1
    assert mon.count(health.TASK_FAILED) == 0
    assert mon.count(health.TASK_RETRIED) == 0


def test_no_hedging_by_default():
    calls = []
    lock = threading.Lock()

    def op(batch):
        with lock:
            calls.append(1)
        time.sleep(0.05)
        return batch

    with HealthMonitor() as mon:
        make_df(8, 4).mapPartitions(op).collect()
    assert len(calls) == 4  # pure ops run exactly once per partition
    assert mon.count(health.TASK_HEDGED) == 0


# -- quarantine ---------------------------------------------------------------

def _poison_df():
    df = make_df(9, 3)

    def op(x):
        if 3 <= x < 6:  # partition 1's rows are poisoned
            raise ValueError(f"poisoned row {x}")
        return x * 10

    return df.withColumn("y", op, ["x"], pa.int64())


def test_quarantine_off_by_default_fatal_raises():
    with pytest.raises(TaskFailure):
        _poison_df().collect()


def test_quarantine_drops_poisoned_partition_and_records():
    EngineConfig.quarantine = True
    with HealthMonitor() as mon:
        out = _poison_df()
        rows = out.collect()
    # partition 1's rows dropped; survivors keep their values and order
    assert [r["x"] for r in rows] == [0, 1, 2, 6, 7, 8]
    assert [r["y"] for r in rows] == [0, 10, 20, 60, 70, 80]
    # schema intact (the zero-row stand-in ran the op chain)
    assert out.toArrow().schema.field("y").type == pa.int64()
    assert mon.count(health.TASK_QUARANTINED) == 1
    entry = mon.quarantined()[0]
    assert entry["partition"] == 1
    assert entry["attempts"] == [resilience.FATAL]
    # the report surfaces the registry
    assert mon.report()["quarantined"] == [entry]


def test_quarantine_streaming_yields_empty_standin():
    EngineConfig.quarantine = True
    out = _poison_df()
    parts = list(out.streamPartitions())
    assert [p.num_rows for p in parts] == [3, 0, 3]
    assert all("y" in p.schema.names for p in parts)


def test_quarantine_max_fatal_confirms_poison_before_dropping():
    """quarantine_max_fatal=2: the deterministic failure is replayed once
    to confirm the poison, then the partition drops with both fatal
    attempts on record."""
    EngineConfig.quarantine = True
    EngineConfig.quarantine_max_fatal = 2
    calls = []

    def bad(x):
        if 3 <= x < 6:
            calls.append(x)
            raise ValueError(f"poisoned row {x}")
        return x

    with HealthMonitor() as mon:
        rows = make_df(9, 3).withColumn("y", bad, ["x"], pa.int64()).collect()
    assert [r["x"] for r in rows] == [0, 1, 2, 6, 7, 8]
    assert calls == [3, 3]  # exactly two confirmation attempts
    entry = mon.quarantined()[0]
    assert entry["attempts"] == [resilience.FATAL, resilience.FATAL]


def test_deadline_failure_not_quarantined():
    """A timeout is slowness, not poison: quarantine must not silently
    drop a transiently stalled partition's rows."""
    EngineConfig.quarantine = True
    EngineConfig.task_timeout_s = 0.2
    with FaultInjector.seeded(0, task_stall=Fault(
            when=lambda c: c["partition"] == 1)):
        with HealthMonitor() as mon:
            with pytest.raises(TaskFailure, match="deadline"):
                make_df(6, 3).withColumn(
                    "y", lambda x: x, ["x"], pa.int64()).collect()
    assert mon.count(health.TASK_QUARANTINED) == 0


def test_cooperative_deadline_expiry_not_quarantined():
    """A task whose op chain crosses the budget BETWEEN watchdog ticks
    fails via the cooperative check — still a timeout, still excluded
    from quarantine (no silent row loss on a transient straggle)."""
    EngineConfig.quarantine = True
    EngineConfig.task_timeout_s = 0.15

    def slow(batch):
        time.sleep(0.05)
        return batch

    # 4 sequential ops x 50ms > 150ms: expiry hits the cooperative check
    df = make_df(4, 1)
    for _ in range(4):
        df = df.mapPartitions(slow)
    with HealthMonitor() as mon:
        with pytest.raises(TaskFailure, match="deadline") as ei:
            df.collect()
    assert ei.value.deadline_exceeded
    assert mon.count(health.TASK_QUARANTINED) == 0
    assert mon.count(health.TASK_DEADLINE_EXCEEDED) == 1


def test_watchdog_deadline_counted_once_after_stalled_thread_wakes():
    """The wedged worker thread must not record a second deadline event
    (or keep retrying) after the watchdog abandoned its task."""
    EngineConfig.task_timeout_s = 0.2
    df = make_df(6, 3).withColumn("y", lambda x: x, ["x"], pa.int64())
    with FaultInjector.seeded(0, task_stall=Fault(
            when=lambda c: c["partition"] == 1)):
        with HealthMonitor() as mon:
            with pytest.raises(TaskFailure, match="deadline"):
                df.collect()
            # outlive the stall's wake-up (~2x budget + margin) with the
            # monitor still active
            time.sleep(1.2)
    assert mon.count(health.TASK_DEADLINE_EXCEEDED) == 1
    assert mon.count(health.TASK_RETRIED) == 0


def test_quarantine_never_applies_to_retryable_exhaustion():
    EngineConfig.quarantine = True
    EngineConfig.max_task_retries = 1

    def flaky(batch):
        raise RuntimeError("UNAVAILABLE: still down")

    with pytest.raises(TaskFailure) as ei:
        make_df(4, 2).mapPartitions(flaky).collect()
    assert ei.value.failure_kind == resilience.RETRYABLE


# -- streamPartitions: cancellation + sharded supervision ---------------------

def test_abandoned_stream_cancels_unstarted_partitions():
    EngineConfig.max_workers = 1  # narrow pool: prefetch window queues
    executed = []
    lock = threading.Lock()

    def op(batch):
        with lock:
            executed.append(batch.column(0)[0].as_py())
        time.sleep(0.05)
        return batch

    df = DataFrame.fromRows([{"x": i} for i in range(12)],
                            numPartitions=6).mapPartitions(op)
    gen = df.streamPartitions(prefetch=4)
    next(gen)
    gen.close()  # early abandon: unstarted window tasks must be cancelled
    with lock:
        n = len(executed)
    assert n <= 3  # yielded head + at most the in-flight attempt(s)


def test_stream_order_and_process_sharding_survive_injected_faults():
    """A failing-then-recovering shard on one 'host' must not corrupt the
    round-robin assignment or reorder surviving partitions."""
    df = DataFrame.fromColumns({"v": np.arange(24, dtype=np.int64)},
                               numPartitions=8)
    df = df.withColumn("w", lambda v: v + 1, inputCols=["v"])
    order = [5, 2, 7, 0, 3, 6, 1, 4]
    expect = {p: [order[p::3][j] for j in range(len(order[p::3]))]
              for p in range(3)}

    def first_values(p, injector=None):
        if injector is None:
            return [b.column(0).to_pylist()
                    for b in df.streamPartitions(order=order, process_id=p,
                                                 num_processes=3)]
        with injector:
            return [b.column(0).to_pylist()
                    for b in df.streamPartitions(order=order, process_id=p,
                                                 num_processes=3)]

    clean = {p: first_values(p) for p in range(3)}
    # host 1's first task fails twice retryably, then recovers
    inj = FaultInjector.seeded(0, engine_task=2)
    faulted = {p: first_values(p, injector=inj if p == 1 else None)
               for p in range(3)}
    assert inj.fired["engine_task"] == 2
    assert faulted == clean
    # assignment partitions the dataset: disjoint + exhaustive
    seen = [v for host in faulted.values() for part in host for v in part]
    assert sorted(seen) == list(range(24))
    for p in range(3):
        starts = [part[0] for part in faulted[p]]
        natural = [b.column(0).to_pylist()[0]
                   for b in df.streamPartitions()]
        assert starts == [natural[i] for i in expect[p]]


def test_sharded_stream_quarantine_degrades_only_owning_host():
    EngineConfig.quarantine = True
    df = DataFrame.fromColumns({"v": np.arange(12, dtype=np.int64)},
                               numPartitions=4)

    def op(v):
        if v == 3:  # partition 1 is poisoned
            raise ValueError("poisoned")
        return v

    df = df.withColumn("w", op, inputCols=["v"])
    host0 = [b.column(0).to_pylist()
             for b in df.streamPartitions(process_id=0, num_processes=2)]
    host1 = [b.column(0).to_pylist()
             for b in df.streamPartitions(process_id=1, num_processes=2)]
    assert host0 == [[0, 1, 2], [6, 7, 8]]  # untouched
    assert host1 == [[], [9, 10, 11]]  # partition 1 dropped, order kept


def test_retry_loop_attempt_restarts_executor_call_sequence():
    """Each retry-loop attempt re-runs the op chain from the top, so its
    device calls restart at call 0 — run_partition_task must realign the
    executor's hedge-dedup sequence per attempt, or a retried primary's
    call 0 would sit at seq N and a hedge's call N could cross-dedup onto
    the wrong device call's output (core/executor.py)."""
    from sparkdl_tpu.core.executor import current_task_token, task_scope

    seen = []
    failures = {"n": 1}

    def device_call(batch):
        seen.append(current_task_token())
        if failures["n"] > 0:
            failures["n"] -= 1
            raise RuntimeError("UNAVAILABLE: transient")
        return batch

    with task_scope(("task", 7, 0)):
        out = run_partition_task(0, "rows", [device_call, device_call],
                                 FAST)
    assert out == "rows"
    # attempt 0: call 0 raised; attempt 1: calls 0 and 1 — the retried
    # attempt's sequence restarted at 0 instead of continuing at 1
    assert seen == [("task", 7, 0, 0), ("task", 7, 0, 0),
                    ("task", 7, 0, 1)]


# -- run_all's on_result: a finished task's result, while others run (PR 34) --

def _supervisor(probe=None, **config):
    import concurrent.futures as futures

    from sparkdl_tpu.engine.supervisor import (
        PartitionSupervisor,
        SupervisorConfig,
    )

    pool = futures.ThreadPoolExecutor(8, thread_name_prefix="sparkdl-part")
    return PartitionSupervisor(pool, SupervisorConfig(**config),
                               quarantine_probe=probe), pool


def _gated(n):
    """``n`` runners that return ``10 * index`` once their gate opens."""
    gates = [threading.Event() for _ in range(n)]

    def runner(i):
        def run(cancel):
            assert gates[i].wait(20), "the gate never opened"
            return 10 * i
        return run

    return gates, [(i, runner(i)) for i in range(n)]


@pytest.mark.parametrize("order", [(0, 1, 2, 3), (3, 2, 1, 0), (1, 3, 0, 2)])
def test_on_result_gets_each_result_as_it_resolves_but_the_last(order):
    sup, pool = _supervisor()
    gates, runners = _gated(4)
    handed = []

    def on_result(index, result):
        handed.append((index, result, threading.current_thread()))
        gates[order[len(handed)]].set()     # the next may finish now

    gates[order[0]].set()
    try:
        out = sup.run_all(runners, on_result)
    finally:
        pool.shutdown()
    assert out == [0, 10, 20, 30]           # input order, whatever resolved
    # on the calling thread, in resolving order; the last task to resolve
    # has nothing left to wait for and is the returned list's alone
    assert [(i, r) for i, r, _ in handed] == [(i, 10 * i) for i in order[:3]]
    assert {t for _, _, t in handed} == {threading.current_thread()}


def test_on_result_is_called_at_most_once_a_tick(monkeypatch):
    from sparkdl_tpu.engine.supervisor import PartitionSupervisor

    sup, pool = _supervisor()
    gates, runners = _gated(4)
    log = []
    checks = PartitionSupervisor._check_deadlines

    def check(self, tasks, outstanding):
        log.append("tick")
        return checks(self, tasks, outstanding)

    monkeypatch.setattr(PartitionSupervisor, "_check_deadlines", check)

    def on_result(index, result):
        log.append(index)
        if index == 2:
            gates[3].set()

    for gate in gates[:3]:      # three resolve before the first tick ends
        gate.set()
    time.sleep(0.2)
    try:
        assert sup.run_all(runners, on_result) == [0, 10, 20, 30]
    finally:
        pool.shutdown()
    calls = [i for i, entry in enumerate(log) if entry != "tick"]
    assert [log[i] for i in calls] == [0, 1, 2]
    # the watchdog and the hedger looked between any two calls
    assert all("tick" in log[a:b] for a, b in zip(calls, calls[1:]))


def _attempt(kind):
    from sparkdl_tpu.engine.supervisor import TaskAttempt

    return TaskAttempt(kind, "ValueError('poison')", 0.0)


def test_on_result_never_sees_a_failed_task_or_a_quarantine_stand_in():
    handed = []
    gates, runners = _gated(3)

    def poisoned(cancel):
        raise TaskFailure("poison", index=1, kind=resilience.FATAL, attempts=[
            _attempt(resilience.FATAL)])

    runners[1] = (1, poisoned)
    sup, pool = _supervisor(probe=lambda index: "stand-in", quarantine=True)
    gates[0].set()
    try:
        out = sup.run_all(
            runners, lambda i, r: (handed.append((i, r)), gates[2].set()))
    finally:
        pool.shutdown()
    assert out == [0, "stand-in", 20]
    assert handed == [(0, 0)]

    # without quarantine the failure raises as it did, after the barrier
    gates, runners = _gated(3)
    runners[1] = (1, poisoned)
    sup, pool = _supervisor()
    handed.clear()
    gates[0].set()
    try:
        with pytest.raises(TaskFailure, match="poison"):
            sup.run_all(runners,
                        lambda i, r: (handed.append((i, r)), gates[2].set()))
    finally:
        pool.shutdown()
    assert handed == [(0, 0)]


def test_on_result_gets_a_hedged_task_once_the_winner_only():
    sup, pool = _supervisor(speculation=True, speculation_quantile=0.5,
                            speculation_min_runtime_s=0.05)
    attempts = []
    lock = threading.Lock()
    release = threading.Event()

    def straggler(cancel):
        with lock:
            attempts.append(len(attempts))
            mine = attempts[-1]
        if mine == 0:           # the primary straggles, the hedge is fast
            release.wait(20)
            return "loser"
        return "winner"

    def quick(value):
        return lambda cancel: value

    handed = []
    runners = [(0, quick("a")), (1, quick("b")), (2, quick("c")),
               (3, straggler), (4, lambda cancel: release.wait(20) and "e")]

    def on_result(index, result):
        handed.append((index, result))
        if index == 3:
            release.set()

    try:
        out = sup.run_all(runners, on_result)
    finally:
        release.set()
        pool.shutdown()
    assert out == ["a", "b", "c", "winner", "e"]
    assert (3, "winner") in handed and (3, "loser") not in handed
    assert [i for i, _ in handed].count(3) == 1 and len(attempts) >= 2


def test_watchdog_still_fires_while_on_result_is_slow():
    sup, pool = _supervisor(task_timeout_s=0.3)
    hung = threading.Event()
    handed = []

    def on_result(index, result):
        handed.append(index)
        time.sleep(0.2)         # one call's duration: the most a check waits

    runners = [(0, lambda cancel: 0), (1, lambda cancel: 1),
               (2, lambda cancel: hung.wait(20))]
    t0 = time.monotonic()
    try:
        with HealthMonitor() as mon:
            with pytest.raises(TaskFailure, match="deadline") as failure:
                sup.run_all(runners, on_result)
    finally:
        hung.set()
        pool.shutdown()
    assert failure.value.index == 2 and failure.value.deadline_exceeded
    assert time.monotonic() - t0 < 2.0
    assert mon.count(health.TASK_DEADLINE_EXCEEDED) == 1
    assert handed == [0, 1]


def test_an_error_out_of_on_result_drains_then_propagates():
    sup, pool = _supervisor()
    started, finished = threading.Event(), []

    def slow(cancel):
        started.set()
        time.sleep(0.3)
        finished.append(1)
        return 1

    def on_result(index, result):
        assert started.wait(20)     # an attempt not yet started is dropped
        raise KeyError("assembly broke")

    try:
        with pytest.raises(KeyError, match="assembly broke"):
            sup.run_all([(0, lambda cancel: 0), (1, slow)], on_result)
        assert finished == [1]      # the barrier: no user op still running
    finally:
        pool.shutdown()


# -- run_all's on_idle: work done while no resolved result waits ---------

def test_a_resolved_result_is_handed_over_before_any_further_idle_slice():
    sup, pool = _supervisor()
    gates, runners = _gated(2)
    log = []

    def on_idle():
        log.append("idle")
        if log.count("idle") == 3:
            gates[0].set()
            time.sleep(0.1)     # task 0 resolves during this slice
        return log.count("idle") < 50

    def on_result(index, result):
        log.append(index)
        gates[1].set()

    try:
        assert sup.run_all(runners, on_result, on_idle) == [0, 10]
    finally:
        pool.shutdown()
    # the slice that saw task 0 resolve is followed by its hand-over
    assert log.index(0) == 3 and log[:3] == ["idle"] * 3


def test_on_idle_runs_without_blocking_until_it_has_no_work_left():
    sup, pool = _supervisor()       # poll_interval_s 0.5: a blocking tick
    gates, runners = _gated(2)
    calls = []
    gates[0].set()

    def on_idle():
        calls.append(time.monotonic())
        if len(calls) == 20:
            threading.Timer(0.3, gates[1].set).start()
        return len(calls) < 20

    t0 = time.monotonic()
    try:
        assert sup.run_all(runners, None, on_idle) == [0, 10]
    finally:
        pool.shutdown()
    # twenty calls back to back, none once it said it had no work left,
    # none after the last task resolved
    assert len(calls) == 20 and calls[-1] - t0 < 0.4


def test_on_idle_is_not_called_once_every_task_has_resolved():
    sup, pool = _supervisor()
    calls = []
    try:
        assert sup.run_all([(0, lambda cancel: 0)], None,
                           lambda: calls.append(1) or True) == [0]
        done = len(calls)
        assert sup.run_all([], None, lambda: calls.append(1) or True) == []
    finally:
        pool.shutdown()
    assert len(calls) == done


def test_an_error_out_of_on_idle_drains_then_propagates():
    sup, pool = _supervisor()
    started, finished = threading.Event(), []

    def slow(cancel):
        started.set()
        time.sleep(0.3)
        finished.append(1)
        return 1

    def on_idle():
        assert started.wait(20)
        raise KeyError("building ahead broke")

    try:
        with pytest.raises(KeyError, match="building ahead broke"):
            sup.run_all([(0, slow)], None, on_idle)
        assert finished == [1]      # the barrier: no user op still running
    finally:
        pool.shutdown()
