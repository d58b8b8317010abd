"""Chaos suite: composed fault injection across a full files→decode→
transform→fit pipeline (ISSUE 2 acceptance; docs/RESILIENCE.md).

One seeded FaultInjector fires `decode_error` → `engine_task` (worker
loss after compute) → `device_oom` → `transfer_stall` → `preemption` in a
single run; the pipeline must complete, produce results bit-identical to
the fault-free run, and the HealthMonitor report must match the injected
fault counts exactly.
"""

import json
import re
import time

import numpy as np
import pyarrow as pa
import pytest

import jax
import flax.linen as nn

from sparkdl_tpu.core import health, resilience, telemetry
from sparkdl_tpu.core.health import HealthMonitor
from sparkdl_tpu.core.telemetry import Telemetry
from sparkdl_tpu.core.model_function import ModelFunction, TensorSpec
from sparkdl_tpu.core.resilience import Fault, FaultInjector
from sparkdl_tpu.engine import DataFrame, EngineConfig, TaskFailure
from sparkdl_tpu.image import imageIO
from sparkdl_tpu.ml.image_transformer import TPUImageTransformer
from sparkdl_tpu.train import CheckpointManager, TPURunner, Trainer

pytestmark = pytest.mark.chaos

_N_IMAGES = 12
_FEATURES = 4


@pytest.fixture(autouse=True)
def _restore_engine_config():
    # full snapshot of every public knob (ISSUE 6: new overload knobs are
    # covered without listing them — future knobs too)
    saved = EngineConfig.snapshot()
    yield
    EngineConfig.restore(saved)


@pytest.fixture
def image_dir(tmp_path):
    from PIL import Image

    rng = np.random.default_rng(7)
    d = tmp_path / "imgs"
    d.mkdir()
    for i in range(_N_IMAGES):
        arr = rng.integers(0, 255, size=(8, 8, 3), dtype=np.uint8)
        Image.fromarray(arr).save(d / f"img_{i:02d}.png")
    return d


def _feature_model() -> ModelFunction:
    rng = np.random.default_rng(0)
    import jax.numpy as jnp

    w = jnp.asarray(rng.normal(size=(8 * 8 * 3, _FEATURES))
                    .astype(np.float32) * 0.01)
    return ModelFunction(
        lambda vs, x: jnp.tanh(x.reshape((x.shape[0], -1)) @ vs),
        w, TensorSpec((None, 8, 8, 3), "float32"), name="chaos_feat")


class _MLP(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        return jax.nn.softmax(nn.Dense(2)(nn.relu(nn.Dense(8)(x))), axis=-1)


_MODULE = _MLP()
_VARIABLES = _MODULE.init(jax.random.PRNGKey(0),
                          np.zeros((1, _FEATURES), np.float32))


def _run_pipeline(image_dir, ckpt_dir, feature_model=None):
    """files → decode (1 task) → transform (3 partitions) → fit (TPURunner
    gang, per-step checkpoints). Returns (features, labels, final_state,
    executed-step trace)."""
    # decode stage: one partition task so the composed decode_error +
    # engine_task(finish) faults deterministically hit the same attempt
    df = imageIO.readImages(str(image_dir), numPartition=1)
    df = df.withColumn(
        "label", lambda p: int(re.search(r"img_(\d+)", p).group(1)) % 2,
        ["filePath"], pa.int64())
    df = df.repartition(3)  # materializes the decode; transform fans out
    t = TPUImageTransformer(inputCol="image", outputCol="features",
                            modelFunction=feature_model or _feature_model(),
                            batchSize=8, outputMode="vector")
    rows = t.transform(df).select("features", "label").collect()
    assert all(r["features"] is not None for r in rows)
    x = np.asarray([r["features"] for r in rows], dtype=np.float32)
    y = np.eye(2, dtype=np.float32)[[r["label"] for r in rows]]
    batches = [(x[i:i + 4], y[i:i + 4]) for i in range(0, _N_IMAGES, 4)]
    steps_run = []

    def train_fn(mesh=None):
        trainer, state = Trainer.from_flax(_MODULE, _VARIABLES,
                                           optimizer="sgd",
                                           learning_rate=0.1, mesh=mesh)
        ckpt = CheckpointManager(str(ckpt_dir))
        # prefetch staging explicitly ON (ISSUE 3): the chaos composition
        # must survive background staging with identical health counts and
        # bit-identical outputs (assertions below are unchanged). NOTE:
        # on_step + checkpoint_every=1 force a sync every step here, so
        # this exercises the staging thread, not deferred sync; the
        # genuinely-deferred abort path (preemption between sync points)
        # is covered by tests/train/test_pipeline_fit.py::
        # test_preemption_abort_with_deferred_sync_resumes_exact
        state = trainer.fit(state, batches, epochs=2, checkpoint=ckpt,
                            checkpoint_every=1, on_step=steps_run.append,
                            prefetch=2, sync_every=2)
        ckpt.wait_until_finished()
        ckpt.close()
        return jax.device_get(state)

    final = TPURunner(np=2, max_restarts=2).run(train_fn)
    return x, y, final, steps_run


def test_chaos_pipeline_recovers_bit_identical(image_dir, tmp_path):
    """Acceptance: all five fault points fire in ONE run; the pipeline
    completes; features are bit-identical and trained params match the
    fault-free run; the health report equals the injected counts."""
    x0, y0, final0, steps0 = _run_pipeline(image_dir, tmp_path / "plain")

    inj = FaultInjector.seeded(
        0,
        # row 0's decode degrades to a null struct on the decode task's
        # first attempt...
        decode_error=1,
        # ...and the same attempt's worker dies after computing but before
        # delivering its result — the classified task retry re-decodes
        # everything cleanly (recovery makes decode_error bit-recoverable)
        engine_task=Fault(times=1, when=lambda c: (
            c.get("phase") == "finish" and c["attempt"] == 0)),
        # first full transform chunk OOMs → bucket-halving re-chunk
        device_oom=Fault(times=1, when=lambda c: c["rows"] >= 8),
        # one transient transfer failure → same-chunk retry
        transfer_stall=1,
        # gang preemption after step 3's checkpoint → restart + resume
        preemption=Fault(when=lambda c: c["step"] == 3),
    )
    with inj, HealthMonitor("chaos") as mon:
        x1, y1, final1, steps1 = _run_pipeline(image_dir, tmp_path / "chaos")

    # every armed point actually fired, exactly once
    assert inj.fired == {"decode_error": 1, "engine_task": 1,
                         "device_oom": 1, "transfer_stall": 1,
                         "preemption": 1}

    # bit-identical data-plane results vs the fault-free run
    np.testing.assert_array_equal(x1, x0)
    np.testing.assert_array_equal(y1, y0)
    # checkpoint-resumed training matches: every step executed once, and
    # final params agree with the uninterrupted run
    assert steps1 == steps0 == [1, 2, 3, 4, 5, 6]
    for a, b in zip(jax.tree.leaves(final0.params),
                    jax.tree.leaves(final1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)

    # the health report matches the injected fault counts exactly
    assert mon.count(health.DECODE_DEGRADED) == inj.fired["decode_error"]
    assert mon.count(health.TASK_RETRIED) == inj.fired["engine_task"]
    assert mon.count(health.OOM_RECHUNK) == inj.fired["device_oom"]
    assert mon.count(health.CHUNK_RETRY) == inj.fired["transfer_stall"]
    assert mon.count(health.GANG_RESTART) == inj.fired["preemption"]
    assert mon.count(health.FIT_RESUMED) == 1
    assert mon.count(health.FIT_COMPLETED) == 1
    assert mon.count(health.TASK_QUARANTINED) == 0
    assert mon.count(health.TASK_DEADLINE_EXCEEDED) == 0
    assert mon.count(health.GANG_FATAL) == 0


def test_chaos_run_under_telemetry_scope_produces_run_report(image_dir,
                                                             tmp_path):
    """ISSUE 4 acceptance: the full chaos pipeline under an active
    telemetry scope yields ONE RunReport JSON whose trace holds
    correctly-parented spans from >= 3 distinct threads, whose metric
    snapshot's retry/quarantine counters equal the HealthMonitor counts,
    and whose Chrome-trace export loads as valid JSON — while outputs
    stay bit-identical to the telemetry-off run."""
    x0, y0, final0, steps0 = _run_pipeline(image_dir, tmp_path / "plain")

    inj = FaultInjector.seeded(
        0,
        decode_error=1,
        engine_task=Fault(times=1, when=lambda c: (
            c.get("phase") == "finish" and c["attempt"] == 0)),
        device_oom=Fault(times=1, when=lambda c: c["rows"] >= 8),
        transfer_stall=1,
        preemption=Fault(when=lambda c: c["step"] == 3),
    )
    tel_dir = tmp_path / "tel"
    # monitor OUTSIDE the telemetry scope so the report (written at
    # telemetry exit) folds the still-active monitor in
    with inj, HealthMonitor("chaos-tel") as mon:
        with Telemetry("chaos", out_dir=str(tel_dir)) as tel:
            x1, y1, final1, steps1 = _run_pipeline(image_dir,
                                                   tmp_path / "chaos")
    assert sum(inj.fired.values()) == 5  # every fault actually fired

    # outputs bit-identical to the telemetry-off run
    np.testing.assert_array_equal(x1, x0)
    np.testing.assert_array_equal(y1, y0)
    assert steps1 == steps0
    for a, b in zip(jax.tree.leaves(final0.params),
                    jax.tree.leaves(final1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)

    # ONE run report, written at scope exit, valid JSON
    reports = sorted(tel_dir.glob("sparkdl_run_report_*.json"))
    assert len(reports) == 1
    report = json.load(open(reports[0]))
    assert report["run_id"] == tel.run_id

    # trace: correctly-parented spans from >= 3 distinct threads
    spans = tel.tracer.spans()
    ids = {s["span_id"] for s in spans}
    assert len({s["thread_id"] for s in spans}) >= 3
    for s in spans:
        assert s["trace_id"] == tel.run_id
        if s["name"] != telemetry.SPAN_RUN:
            assert s["parent_id"] in ids, s
    names = {s["name"] for s in spans}
    assert {"sparkdl.run", "sparkdl.materialize", "sparkdl.task",
            "sparkdl.fit", "sparkdl.train_step",
            "sparkdl.stage_batch"} <= names
    # the report's summary agrees with the live tracer
    assert report["trace"]["spans_recorded"] == len(spans)
    assert len(report["trace"]["threads"]) >= 3

    # metric snapshot counters equal the HealthMonitor counts
    counters = report["metrics"]["counters"]
    for event in (health.TASK_RETRIED, health.TASK_QUARANTINED,
                  health.OOM_RECHUNK, health.CHUNK_RETRY,
                  health.GANG_RESTART, health.DECODE_DEGRADED,
                  health.FIT_RESUMED, health.FIT_COMPLETED):
        assert counters.get(telemetry.HEALTH_METRIC_PREFIX + event, 0) \
            == mon.count(event), event
    assert counters["sparkdl.health.task_retried"] == 1
    assert counters.get("sparkdl.health.task_quarantined", 0) == 0
    assert report["health"]["counters"] == mon.report()["counters"]

    # Chrome-trace export loads as valid JSON with per-thread tracks
    trace = json.load(open(report["chrome_trace"]))
    complete = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(complete) == len(spans)
    assert len({e["tid"] for e in complete}) >= 3
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in complete)


def test_chaos_coalesced_transform_matches_plain_under_faults(image_dir):
    """ISSUE 5 satellite: seeded device_oom + task_stall under
    EngineConfig.coalesce=True yield bit-identical outputs and health
    counts equal to the non-coalesced run (the execution service is
    observationally transparent, faults included)."""
    t = TPUImageTransformer(inputCol="image", outputCol="features",
                            modelFunction=_feature_model(), batchSize=8,
                            outputMode="vector")

    def run(coalesce):
        EngineConfig.coalesce = coalesce
        inj = FaultInjector.seeded(
            0,
            # fires on the first ≥3-valid-row launch, whichever side
            # (coalesced super-batch or per-partition chunk) gets there
            # first — each partition stages 3 valid rows, so it fires in
            # both modes exactly once
            device_oom=Fault(times=1,
                             when=lambda c: c.get("valid", 0) >= 3),
            # partition 2's first task attempt hangs briefly; with no
            # deadline armed the stall surfaces retryable and the task
            # retry heals it
            task_stall=Fault(times=1,
                             when=lambda c: c["partition"] == 2))
        with inj, HealthMonitor() as mon:
            df = imageIO.readImages(str(image_dir), numPartition=4)
            rows = t.transform(df).select("features").collect()
        assert inj.fired == {"device_oom": 1, "task_stall": 1}
        return rows, mon.report()["counters"]

    rows_plain, health_plain = run(coalesce=False)
    rows_coalesced, health_coalesced = run(coalesce=True)
    assert rows_coalesced == rows_plain  # bit-identical, order-preserving
    assert health_coalesced == health_plain
    assert health_plain[health.OOM_RECHUNK] == 1
    assert health_plain[health.TASK_RETRIED] == 1


def test_chaos_pipeline_with_decode_pool_bit_identical(image_dir, tmp_path):
    """ISSUE 9 satellite: the FULL 5-fault chaos run with the
    multi-process decode pool armed (EngineConfig.decode_workers=2) —
    bit-identical outputs and the exact same health counters as the
    pool-off run, with zero worker respawns (no crash fault armed):
    the pool is observationally transparent, faults included."""
    from sparkdl_tpu.core import decode_pool

    x0, y0, final0, steps0 = _run_pipeline(image_dir, tmp_path / "plain")

    EngineConfig.decode_workers = 2
    inj = FaultInjector.seeded(
        0,
        decode_error=1,
        engine_task=Fault(times=1, when=lambda c: (
            c.get("phase") == "finish" and c["attempt"] == 0)),
        device_oom=Fault(times=1, when=lambda c: c["rows"] >= 8),
        transfer_stall=1,
        preemption=Fault(when=lambda c: c["step"] == 3),
    )
    try:
        with inj, HealthMonitor("chaos-pool") as mon:
            x1, y1, final1, steps1 = _run_pipeline(image_dir,
                                                   tmp_path / "chaos")
    finally:
        decode_pool.shutdown()

    assert inj.fired == {"decode_error": 1, "engine_task": 1,
                         "device_oom": 1, "transfer_stall": 1,
                         "preemption": 1}
    np.testing.assert_array_equal(x1, x0)
    np.testing.assert_array_equal(y1, y0)
    assert steps1 == steps0 == [1, 2, 3, 4, 5, 6]
    for a, b in zip(jax.tree.leaves(final0.params),
                    jax.tree.leaves(final1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # the same counter set the pool-off chaos run pins — the decode
    # fault fires in the SUBMITTING process, so pool on/off agree
    assert mon.count(health.DECODE_DEGRADED) == 1
    assert mon.count(health.TASK_RETRIED) == 1
    assert mon.count(health.OOM_RECHUNK) == 1
    assert mon.count(health.CHUNK_RETRY) == 1
    assert mon.count(health.GANG_RESTART) == 1
    assert mon.count(health.FIT_RESUMED) == 1
    assert mon.count(health.FIT_COMPLETED) == 1
    assert mon.count(health.TASK_QUARANTINED) == 0
    assert mon.count(health.DECODE_POOL_RESPAWN) == 0


def test_chaos_pipeline_columnar_fused_bit_identical(image_dir, tmp_path):
    """ISSUE 18 satellite: the FULL 5-fault chaos run with the zero-copy
    columnar plane, device-fused preprocess (a 6x6 model makes the fused
    resize REAL work, not a size-match no-op), AND the decode pool all
    armed — bit-identical to the fault-free run under the same data
    plane, with the exact per-fault health counter set."""
    from sparkdl_tpu.core import decode_pool

    import jax.numpy as jnp

    def small_model() -> ModelFunction:
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(6 * 6 * 3, _FEATURES))
                        .astype(np.float32) * 0.01)
        return ModelFunction(
            lambda vs, x: jnp.tanh(x.reshape((x.shape[0], -1)) @ vs),
            w, TensorSpec((None, 6, 6, 3), "float32"), name="chaos_feat6")

    EngineConfig.columnar_images = True
    EngineConfig.fused_preprocess = True
    x0, y0, final0, steps0 = _run_pipeline(image_dir, tmp_path / "plain",
                                           feature_model=small_model())

    EngineConfig.decode_workers = 2
    inj = FaultInjector.seeded(
        0,
        decode_error=1,
        engine_task=Fault(times=1, when=lambda c: (
            c.get("phase") == "finish" and c["attempt"] == 0)),
        device_oom=Fault(times=1, when=lambda c: c["rows"] >= 8),
        transfer_stall=1,
        preemption=Fault(when=lambda c: c["step"] == 3),
    )
    try:
        with inj, HealthMonitor("chaos-columnar") as mon:
            x1, y1, final1, steps1 = _run_pipeline(
                image_dir, tmp_path / "chaos", feature_model=small_model())
    finally:
        decode_pool.shutdown()

    assert inj.fired == {"decode_error": 1, "engine_task": 1,
                         "device_oom": 1, "transfer_stall": 1,
                         "preemption": 1}
    np.testing.assert_array_equal(x1, x0)
    np.testing.assert_array_equal(y1, y0)
    assert steps1 == steps0 == [1, 2, 3, 4, 5, 6]
    for a, b in zip(jax.tree.leaves(final0.params),
                    jax.tree.leaves(final1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    assert mon.count(health.DECODE_DEGRADED) == 1
    assert mon.count(health.TASK_RETRIED) == 1
    assert mon.count(health.OOM_RECHUNK) == 1
    assert mon.count(health.CHUNK_RETRY) == 1
    assert mon.count(health.GANG_RESTART) == 1
    assert mon.count(health.FIT_RESUMED) == 1
    assert mon.count(health.FIT_COMPLETED) == 1
    assert mon.count(health.TASK_QUARANTINED) == 0
    assert mon.count(health.DECODE_POOL_RESPAWN) == 0


def test_chaos_cluster_worker_kill_bit_identical(image_dir):
    """ISSUE 14 acceptance: the files→decode→featurize leg with the
    cluster plane armed (EngineConfig.cluster_workers=2) and ONE worker
    SIGKILLed mid-stream by the armed `cluster_worker_kill` injection —
    the run completes bit-identical to the in-process run, the death is
    exactly one `cluster_worker_lost` with its held partitions
    re-dispatched, and nothing leaks (no live worker processes, no
    shared-memory segments)."""
    import multiprocessing
    import os

    from sparkdl_tpu.cluster import router as cluster_router

    def featurize():
        df = imageIO.readImages(str(image_dir), numPartition=1)
        df = df.withColumn(
            "label", lambda p: int(re.search(r"img_(\d+)", p).group(1)) % 2,
            ["filePath"], pa.int64())
        df = df.repartition(3)
        t = TPUImageTransformer(inputCol="image", outputCol="features",
                                modelFunction=_feature_model(), batchSize=8,
                                outputMode="vector")
        rows = t.transform(df).select("features", "label").collect()
        x = np.asarray([r["features"] for r in rows], dtype=np.float32)
        y = np.asarray([r["label"] for r in rows], dtype=np.int64)
        return x, y

    def shm_segments():
        if not os.path.isdir("/dev/shm"):
            return set()
        return {n for n in os.listdir("/dev/shm") if n.startswith("psm_")}

    x0, y0 = featurize()  # in-process truth (cluster_workers=0)

    before = shm_segments()
    EngineConfig.cluster_workers = 2
    # dispatch #1 is the decode partition; the kill arms on dispatch #2 —
    # the first transform partition, with the stream mid-flight
    inj = FaultInjector.seeded(0, cluster_worker_kill=Fault(times=1,
                                                            after=1))
    try:
        with inj, HealthMonitor("chaos-cluster") as mon:
            x1, y1 = featurize()
    finally:
        cluster_router.shutdown()

    assert inj.fired == {"cluster_worker_kill": 1}
    np.testing.assert_array_equal(x1, x0)  # bit-identical through the kill
    np.testing.assert_array_equal(y1, y0)
    assert mon.count(health.CLUSTER_WORKER_STARTED) == 2
    assert mon.count(health.CLUSTER_WORKER_LOST) == 1  # ONE death event
    assert mon.count(health.CLUSTER_REDISPATCH) >= 1  # its held partitions
    assert mon.count(health.TASK_FAILED) == 0  # survivors absorbed it all

    # zero leaks: every worker process reaped, no stray cluster children,
    # no shared-memory segments beyond what preceded the run
    router = cluster_router._last_router
    assert all(not w.proc.is_alive() for w in router._workers)
    names = [p.name for p in multiprocessing.active_children()]
    assert not any(n.startswith("sparkdl-cluster") for n in names), names
    assert shm_segments() - before == set()


def test_chaos_pipeline_bf16_tuned_ladder_within_tolerance(image_dir,
                                                           tmp_path):
    """ISSUE 12 acceptance: the FULL 5-fault chaos run with the raw-speed
    inference path armed (bfloat16 featurize + tuned bucket ladder +
    donated buffers — the production defaults the test conftest pins
    off) — every fault fires exactly once, recovery stays DETERMINISTIC
    under low precision (bit-identical to the fault-free bf16 run), and
    the features stay inside the documented bf16 envelope vs the fp32
    fault-free truth (docs/PERF.md "Launch shaping & precision")."""
    from sparkdl_tpu.core import batching

    x_fp32, _, _, _ = _run_pipeline(image_dir, tmp_path / "fp32")

    EngineConfig.inference_precision = "bfloat16"
    EngineConfig.bucket_ladder = "tuned"
    EngineConfig.inference_donate_buffers = True
    batching.reset_planners()
    try:
        x0, y0, final0, steps0 = _run_pipeline(image_dir,
                                               tmp_path / "plain")
        inj = FaultInjector.seeded(
            0,
            decode_error=1,
            engine_task=Fault(times=1, when=lambda c: (
                c.get("phase") == "finish" and c["attempt"] == 0)),
            device_oom=Fault(times=1, when=lambda c: c["rows"] >= 8),
            transfer_stall=1,
            preemption=Fault(when=lambda c: c["step"] == 3),
        )
        with inj, HealthMonitor("chaos-bf16") as mon:
            x1, y1, final1, steps1 = _run_pipeline(image_dir,
                                                   tmp_path / "chaos")
    finally:
        batching.reset_planners()

    assert inj.fired == {"decode_error": 1, "engine_task": 1,
                         "device_oom": 1, "transfer_stall": 1,
                         "preemption": 1}
    # fault recovery is precision-agnostic: the chaos run reproduces the
    # fault-free bf16 run bit-for-bit (padding rows are masked out, so
    # OOM-halved buckets and retuned rungs cannot perturb valid rows)
    np.testing.assert_array_equal(x1, x0)
    np.testing.assert_array_equal(y1, y0)
    assert steps1 == steps0 == [1, 2, 3, 4, 5, 6]
    for a, b in zip(jax.tree.leaves(final0.params),
                    jax.tree.leaves(final1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # tolerance-compared against the fp32 truth: bounded (tanh) head
    np.testing.assert_allclose(x1, x_fp32, atol=0.05)
    # same health counts as the fp32 chaos run — the fast path changes
    # throughput, not the fault story
    assert mon.count(health.DECODE_DEGRADED) == 1
    assert mon.count(health.TASK_RETRIED) == 1
    assert mon.count(health.OOM_RECHUNK) == 1
    assert mon.count(health.CHUNK_RETRY) == 1
    assert mon.count(health.GANG_RESTART) == 1
    assert mon.count(health.FIT_RESUMED) == 1
    assert mon.count(health.FIT_COMPLETED) == 1
    assert mon.count(health.TASK_QUARANTINED) == 0


def test_chaos_fatal_transform_error_retried_zero_times(image_dir):
    """Acceptance: FATAL errors are provably retried zero times, end to
    end — the engine task fails once, and the gang boundary (classify on
    TaskFailure.failure_kind) would not restart it either."""
    df = imageIO.readImages(str(image_dir), numPartition=2)
    calls = []

    def bad(batch):
        calls.append(1)
        raise ValueError("deliberate contract violation")

    with pytest.raises(TaskFailure) as ei:
        df.mapPartitions(bad).collect()
    assert len(calls) == 2  # one attempt per partition, zero retries
    assert ei.value.retries() == 0
    assert resilience.classify(ei.value) == resilience.FATAL


def test_chaos_stalled_partition_fails_via_deadline(image_dir):
    """Acceptance: a deliberately stalled decode partition fails via
    Deadline instead of wedging the materialization."""
    EngineConfig.task_timeout_s = 0.4
    df = imageIO.readImages(str(image_dir), numPartition=3)
    t0 = time.monotonic()
    with FaultInjector.seeded(0, task_stall=Fault(
            when=lambda c: c["partition"] == 2)) as inj:
        with HealthMonitor() as mon:
            with pytest.raises(TaskFailure, match="deadline"):
                df.collect()
    assert inj.fired["task_stall"] == 1
    assert time.monotonic() - t0 < 5.0
    assert mon.count(health.TASK_DEADLINE_EXCEEDED) == 1


def test_chaos_overload_engine_flood_sheds_absorbed_bit_identical(image_dir):
    """ISSUE 6 satellite: the engine flooded with concurrent partitions
    under TINY executor queue caps in shed mode, plus seeded device_oom
    and task_stall — every shed classifies RETRYABLE, the engine's task
    retry absorbs the spike, and the output is bit-identical to the
    fault-free unbounded run. Accounting closes: every EXECUTOR_SHED
    event corresponds 1:1 to a classified task retry whose error was
    ExecutorOverloaded — no silent loss anywhere."""
    from sparkdl_tpu.core import executor as device_executor

    t = TPUImageTransformer(inputCol="image", outputCol="features",
                            modelFunction=_feature_model(), batchSize=8,
                            outputMode="vector")
    df = imageIO.readImages(str(image_dir), numPartition=6)
    baseline = t.transform(df).select("features").collect()

    device_executor.reset()
    EngineConfig.executor_max_queued_requests = 1
    EngineConfig.executor_overload_mode = "shed"
    EngineConfig.coalesce_window_ms = 10.0
    EngineConfig.max_task_retries = 30   # the retry budget absorbs sheds
    EngineConfig.task_retry_delay_s = 0.01
    EngineConfig.max_workers = 6         # all six partitions race
    inj = FaultInjector.seeded(
        0,
        device_oom=Fault(times=1, when=lambda c: c.get("valid", 0) >= 2),
        task_stall=Fault(times=1, when=lambda c: c["partition"] == 2))
    try:
        with inj, HealthMonitor() as mon:
            rows = t.transform(df).select("features").collect()
    finally:
        device_executor.reset()
    assert inj.fired == {"device_oom": 1, "task_stall": 1}

    # no silent loss: bit-identical, order-preserving vs the fault-free run
    assert rows == baseline
    counters = mon.report()["counters"]
    assert counters[health.OOM_RECHUNK] == 1
    assert counters.get(health.TASK_FAILED, 0) == 0
    assert counters.get(health.TASK_QUARANTINED, 0) == 0
    # every shed surfaced as exactly one classified task retry
    shed_retries = [e for e in mon.events(health.TASK_RETRIED)
                    if e.get("error") == "ExecutorOverloaded"]
    assert counters.get(health.EXECUTOR_SHED, 0) == len(shed_retries)
    stall_retries = [e for e in mon.events(health.TASK_RETRIED)
                     if e.get("error") == "TransferStall"]
    assert len(stall_retries) == 1


def test_chaos_overload_accounting_closes_and_breaker_cycles(tmp_path):
    """ISSUE 6 acceptance: one telemetry+health scope over (a) a direct
    executor flood under tiny caps with per-request deadlines and (b) a
    full circuit-breaker trip→fast-fail→probe→recover cycle. The
    accounting closes exactly — submitted == delivered-bit-identical +
    classified-shed + classified-deadline — and the written run report
    shows the whole overload episode: shed/deadline/breaker counters
    equal to the observed outcomes plus live queue-depth and shed-rate
    gauges."""
    import threading

    import jax.numpy as jnp

    from sparkdl_tpu.core import executor as device_executor
    from sparkdl_tpu.core.executor import ExecutorCircuitOpen, \
        ExecutorOverloaded
    from sparkdl_tpu.core.model_function import ModelFunction, TensorSpec
    from sparkdl_tpu.core.resilience import Deadline

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(6, _FEATURES)).astype(np.float32))
    fail = [False]

    def apply_fn(vs, x):
        def host_hook(a):
            time.sleep(0.05)
            if fail[0]:
                raise ValueError("INVALID_ARGUMENT: poisoned model")
            return a
        x = jax.pure_callback(host_hook,
                              jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return jnp.tanh(x @ vs)

    mf = ModelFunction(apply_fn, w, TensorSpec((None, 6), "float32"),
                       name="overload_chaos")
    device_executor.reset()
    EngineConfig.executor_max_queued_requests = 2
    EngineConfig.executor_overload_mode = "shed"
    # window longer than the per-request deadline: whatever made it into
    # the queue EXPIRES there and must be dropped before a launch — the
    # flood deterministically produces all three outcome classes (one
    # inline delivery, two queued-then-expired, the rest shed)
    EngineConfig.coalesce_window_ms = 100.0
    n = 16
    inputs = [rng.normal(size=(3, 6)).astype(np.float32)
              for _ in range(n)]
    expected = [mf.apply_batch(x, batch_size=32) for x in inputs]
    results = [None] * n
    errors = [None] * n
    barrier = threading.Barrier(n)

    def work(i):
        try:
            barrier.wait()
            results[i] = device_executor.execute(
                mf, inputs[i], batch_size=32, deadline=Deadline(0.03))
        except BaseException as e:  # noqa: BLE001 - partitioned below
            errors[i] = e

    tel_dir = tmp_path / "tel"
    with HealthMonitor("overload") as mon:
        with Telemetry("overload", out_dir=str(tel_dir)) as tel:
            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30.0)
            assert not any(t.is_alive() for t in threads)

            # -- the breaker cycle, same scope: trip, fast-fail, recover
            EngineConfig.executor_breaker_threshold = 2
            EngineConfig.executor_breaker_cooldown_s = 0.15
            fail[0] = True
            for _ in range(2):
                with pytest.raises(Exception) as ei:
                    device_executor.execute(mf, inputs[0], batch_size=32)
                assert resilience.classify(ei.value) == resilience.FATAL
            with pytest.raises(ExecutorCircuitOpen):
                device_executor.execute(mf, inputs[0], batch_size=32)
            fail[0] = False
            time.sleep(0.2)
            out = device_executor.execute(mf, inputs[0], batch_size=32)
            np.testing.assert_array_equal(out, expected[0])
    device_executor.reset()

    # -- the accounting closes: submitted == delivered + shed + deadline
    delivered = [i for i in range(n) if errors[i] is None]
    shed = [i for i in range(n)
            if isinstance(errors[i], ExecutorOverloaded)]
    deadline_shed = [i for i in range(n)
                     if isinstance(errors[i], resilience.DeadlineExceeded)]
    assert len(delivered) + len(shed) + len(deadline_shed) == n, errors
    # the episode genuinely exercised every outcome class
    assert delivered and shed and deadline_shed, (
        len(delivered), len(shed), len(deadline_shed))
    for i in delivered:
        np.testing.assert_array_equal(results[i], expected[i])
    counters = mon.report()["counters"]
    assert counters.get(health.EXECUTOR_SHED, 0) == len(shed)
    assert counters.get(health.EXECUTOR_DEADLINE_SHED, 0) \
        == len(deadline_shed)
    # the breaker tripped and recovered, visible as health events
    assert counters[health.BREAKER_OPEN] == 1
    assert counters[health.BREAKER_PROBE] == 1
    assert counters[health.BREAKER_CLOSED] == 1

    # -- the run report shows the whole episode
    reports = sorted(tel_dir.glob("sparkdl_run_report_*.json"))
    assert len(reports) == 1
    report = json.load(open(reports[0]))
    assert report["run_id"] == tel.run_id
    rep_counters = report["metrics"]["counters"]
    for event, want in ((health.EXECUTOR_SHED, len(shed)),
                        (health.EXECUTOR_DEADLINE_SHED,
                         len(deadline_shed)),
                        (health.BREAKER_OPEN, 1),
                        (health.BREAKER_PROBE, 1),
                        (health.BREAKER_CLOSED, 1)):
        assert rep_counters.get(
            telemetry.HEALTH_METRIC_PREFIX + event, 0) == want, event
    gauges = report["metrics"]["gauges"]
    assert telemetry.M_EXECUTOR_QUEUE_DEPTH in gauges
    assert telemetry.M_EXECUTOR_SHED_RATE in gauges
    assert report["health"]["counters"] == mon.report()["counters"]


def test_chaos_straggler_hedged_and_deduplicated(image_dir):
    """Acceptance: a straggler decode partition is hedged; the duplicate's
    result is deduplicated deterministically (output equals the
    unhedged run's, each row exactly once)."""
    EngineConfig.speculation = True
    EngineConfig.speculation_quantile = 0.5
    EngineConfig.speculation_min_runtime_s = 0.05
    # fresh, wide pool so the hedge isn't queued behind the straggler
    EngineConfig.max_workers = 9
    df = imageIO.readImages(str(image_dir), numPartition=6)
    baseline = df.collect()
    stalled = set()
    import threading

    lock = threading.Lock()

    def slow_once(batch):
        key = batch.column(0)[0].as_py()
        with lock:
            again = key in stalled
            stalled.add(key)
        if key.endswith("img_10.png") and not again:
            time.sleep(2.0)  # environmental slowness on the primary only
        return batch

    t0 = time.monotonic()
    with HealthMonitor() as mon:
        rows = df.mapPartitions(slow_once).collect()
    assert rows == baseline  # identical, order-preserving, no duplicates
    assert mon.count(health.TASK_HEDGED) == 1
    assert mon.count(health.HEDGE_WON) == 1
    assert time.monotonic() - t0 < 1.5


def test_chaos_overload_slo_timeline_breach_and_recovery(tmp_path):
    """ISSUE 7 satellite: the overload/shed chaos scenario inside a
    Telemetry scope with a short export interval. The periodic snapshot
    timeline must show the shed-rate SLO firing during the flood and
    recovering after — exactly one slo_breach/slo_recovered pair for
    the violated rule — with the windowed view diverging from the
    cumulative one once the flood ages out, and every count consistent
    with the HealthMonitor report."""
    import threading

    import jax.numpy as jnp

    from sparkdl_tpu.core import executor as device_executor
    from sparkdl_tpu.core import slo
    from sparkdl_tpu.core.executor import ExecutorOverloaded

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(6, _FEATURES)).astype(np.float32))

    def apply_fn(vs, x):
        def host_hook(a):
            time.sleep(0.05)  # a slow model keeps the queue full
            return a
        x = jax.pure_callback(host_hook,
                              jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return jnp.tanh(x @ vs)

    mf = ModelFunction(apply_fn, w, TensorSpec((None, 6), "float32"),
                       name="slo_chaos")
    device_executor.reset()
    EngineConfig.executor_max_queued_requests = 2
    EngineConfig.executor_overload_mode = "shed"
    EngineConfig.coalesce_window_ms = 20.0
    n = 16
    inputs = [rng.normal(size=(3, 6)).astype(np.float32)
              for _ in range(n)]
    errors = [None] * n
    barrier = threading.Barrier(n)

    def work(i):
        try:
            barrier.wait()
            device_executor.execute(mf, inputs[i], batch_size=32)
        except BaseException as e:  # noqa: BLE001 - partitioned below
            errors[i] = e

    # second-scale windows so breach AND recovery land inside one test;
    # the queue-wait threshold is raised so only the shed-rate rule can
    # fire (the acceptance wants one pair per VIOLATED rule)
    rules = slo.default_rules(window_s=0.6, shed_rate_per_s=0.5,
                              queue_wait_p99_s=5.0)
    tel_dir = tmp_path / "tel"
    try:
        with HealthMonitor("slo-chaos") as mon:
            with Telemetry("slo-chaos", out_dir=str(tel_dir),
                           export_interval_s=0.05, window_s=0.6,
                           window_buckets=6, slo_rules=rules) as tel:
                threads = [threading.Thread(target=work, args=(i,))
                           for i in range(n)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=30.0)
                assert not any(t.is_alive() for t in threads)
                # the breach surfaces LIVE, on an exporter tick
                deadline = time.monotonic() + 10.0
                while (mon.count(health.SLO_BREACH) < 1
                       and time.monotonic() < deadline):
                    time.sleep(0.02)
                assert mon.count(health.SLO_BREACH) == 1
                # quiet down: the window slides past the flood
                deadline = time.monotonic() + 10.0
                while (mon.count(health.SLO_RECOVERED) < 1
                       and time.monotonic() < deadline):
                    time.sleep(0.02)
                assert mon.count(health.SLO_RECOVERED) == 1
                # queue waits are recorded at DRAIN time (later than the
                # admission sheds), so their window empties later — wait
                # for it so the final flush proves the windowed view is
                # clean while the cumulative one still holds the episode
                deadline = time.monotonic() + 10.0
                while (tel.metrics.window_snapshot()["histograms"]
                       .get(telemetry.M_QUEUE_WAIT_S,
                            {"count": 0})["count"] > 0
                       and time.monotonic() < deadline):
                    time.sleep(0.02)
    finally:
        device_executor.reset()

    sheds = [e for e in errors if isinstance(e, ExecutorOverloaded)]
    assert sheds  # the flood genuinely shed past the tiny cap
    assert all(e is None or isinstance(e, ExecutorOverloaded)
               for e in errors), errors

    # exactly one breach/recovered pair, and only for the shed rule
    assert mon.count(health.SLO_BREACH) == 1
    assert mon.count(health.SLO_RECOVERED) == 1
    (breach_ev,) = mon.events(health.SLO_BREACH)
    (rec_ev,) = mon.events(health.SLO_RECOVERED)
    assert breach_ev["rule"] == rec_ev["rule"] == "executor_shed_rate"
    assert breach_ev["observed"] >= 0.5
    assert breach_ev["threshold"] == 0.5

    # >= 3 periodic snapshot lines with monotone sequence numbers, and
    # the timeline shows breach -> recovery in order
    lines = [json.loads(line)
             for line in open(tel.exporter.snapshot_path)]
    assert len(lines) >= 3
    assert [line["seq"] for line in lines] == \
        list(range(1, len(lines) + 1))
    breached_at = [i for i, line in enumerate(lines)
                   if line["slo"]["executor_shed_rate"]["breached"]]
    assert breached_at, "no snapshot captured the breach"
    assert any(not line["slo"]["executor_shed_rate"]["breached"]
               for line in lines[breached_at[-1] + 1:] or [lines[-1]]), \
        "no snapshot captured the recovery"

    # the windowed view diverges from the cumulative one after the
    # flood: last-window sheds are zero while the cumulative counter
    # still carries the episode (same for queue-wait p99 — the
    # "current vs forever" split this plane exists for)
    last = lines[-1]
    shed_metric = telemetry.HEALTH_METRIC_PREFIX + health.EXECUTOR_SHED
    assert last["windowed"]["counters"][shed_metric]["count"] == 0
    assert last["cumulative"]["counters"][shed_metric] == len(sheds)
    qw = telemetry.M_QUEUE_WAIT_S
    cum_qw = last["cumulative"]["histograms"].get(qw)
    if cum_qw and cum_qw["count"]:
        assert last["windowed"]["histograms"][qw]["count"] == 0
        assert last["windowed"]["histograms"][qw]["p99"] is None
        assert cum_qw["p99"] is not None
    # during the flood at least one snapshot saw live windowed sheds
    assert any(line["windowed"]["counters"]
               .get(shed_metric, {"count": 0})["count"] > 0
               for line in lines)
    # executor state rode along in every snapshot
    assert all(line["executor"] is not None for line in lines)

    # counts consistent with the HealthMonitor report, and the run
    # report's mirrors agree with the monitor exactly
    counters = mon.report()["counters"]
    assert counters[health.EXECUTOR_SHED] == len(sheds)
    report = json.load(open(tel.report_path))
    for event in (health.EXECUTOR_SHED, health.SLO_BREACH,
                  health.SLO_RECOVERED):
        assert report["metrics"]["counters"].get(
            telemetry.HEALTH_METRIC_PREFIX + event, 0) \
            == counters[event], event
    assert report["timeline"]["snapshots"] == len(lines)
    assert any(e.get("slo_breached") == ["executor_shed_rate"]
               for e in report["timeline"]["entries"])


def _scaled_feature_model(scale: float, name: str) -> ModelFunction:
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(8 * 8 * 3, _FEATURES))
                    .astype(np.float32) * 0.01 * scale)
    return ModelFunction(
        lambda vs, x: jnp.tanh(x.reshape((x.shape[0], -1)) @ vs),
        w, TensorSpec((None, 8, 8, 3), "float32"), name=name)


def _run_serving_pipeline(image_dir, ckpt_dir):
    """ISSUE 13 chaos leg: the SAME files→decode→infer→fit shape as
    _run_pipeline, with the inference stage served ONLINE — a
    sequential stream of row-level ModelServer.predict requests with a
    v1→v2 hot-swap armed at a FIXED request index (and v2 shadowing at
    0.5 before the swap). Sequential requests + the deterministic
    shadow accumulator make the swap point, the shadow set and every
    output reproducible across runs. Returns (outputs, versions,
    final_state, steps_run)."""
    from sparkdl_tpu.serving import ModelRegistry, ModelServer

    # decode stage: one partition task, same fault surface as the
    # engine pipeline (decode_error degrades a row; engine_task kills
    # the attempt after compute; the classified retry re-decodes)
    df = imageIO.readImages(str(image_dir), numPartition=1)
    df = df.withColumn(
        "label", lambda p: int(re.search(r"img_(\d+)", p).group(1)) % 2,
        ["filePath"], pa.int64())
    rows = df.select("image", "label").collect()
    x = np.stack([imageIO.imageStructToArray(r["image"]).astype(np.float32)
                  for r in rows])
    y = np.eye(2, dtype=np.float32)[[r["label"] for r in rows]]

    # serving stage: v1 active, v2 shadowed at 0.5 — 6 requests of 12
    # rows each (>= 8-row launches so device_oom/transfer_stall hit the
    # serving path), hot-swap to v2 before request index 3
    reg = ModelRegistry()
    srv = ModelServer(reg)
    reg.deploy("chaos_served", "v1",
               model=_scaled_feature_model(1.0, "chaos_v1"),
               batch_size=8)
    reg.deploy("chaos_served", "v2",
               model=_scaled_feature_model(2.0, "chaos_v2"),
               batch_size=8)
    reg.shadow("chaos_served", "v2", fraction=0.5)
    outputs, versions = [], []
    for i in range(6):
        if i == 3:
            reg.cutover("chaos_served", "v2")  # mid-stream hot-swap
        got = srv.predict("chaos_served", x)
        outputs.append(np.asarray(got.output))
        versions.append(got.version)

    # fit stage on the v1-served features (identical across runs): the
    # gang preemption + checkpoint resume ride along unchanged
    feats = outputs[0]
    batches = [(feats[i:i + 4], y[i:i + 4])
               for i in range(0, _N_IMAGES, 4)]
    steps_run = []

    def train_fn(mesh=None):
        trainer, state = Trainer.from_flax(_MODULE, _VARIABLES,
                                           optimizer="sgd",
                                           learning_rate=0.1, mesh=mesh)
        ckpt = CheckpointManager(str(ckpt_dir))
        state = trainer.fit(state, batches, epochs=2, checkpoint=ckpt,
                            checkpoint_every=1, on_step=steps_run.append)
        ckpt.wait_until_finished()
        ckpt.close()
        return jax.device_get(state)

    final = TPURunner(np=2, max_restarts=2).run(train_fn)
    return outputs, versions, final, steps_run


def test_chaos_serving_hot_swap_bit_identical(image_dir, tmp_path):
    """ISSUE 13 satellite: the 5-fault chaos composition through
    ModelServer.predict with a mid-stream v1→v2 hot-swap armed — zero
    dropped requests, per-version outputs bit-identical to the
    fault-free swap run, and serving/fit health counts equal to the
    fault-free swap run (the faults add ONLY their recovery events)."""
    from sparkdl_tpu.core import executor as device_executor

    with HealthMonitor("serving-plain") as mon0:
        out0, ver0, final0, steps0 = _run_serving_pipeline(
            image_dir, tmp_path / "plain")
    device_executor.reset()  # a fresh service for the chaos run

    inj = FaultInjector.seeded(
        0,
        decode_error=1,
        engine_task=Fault(times=1, when=lambda c: (
            c.get("phase") == "finish" and c["attempt"] == 0)),
        # the serving launches are 12-row batches chunked at 8: the OOM
        # halves the serving chunk, the stall retries it — both INSIDE
        # a predict call
        device_oom=Fault(times=1, when=lambda c: c["rows"] >= 8),
        transfer_stall=1,
        preemption=Fault(when=lambda c: c["step"] == 3),
    )
    try:
        with inj, HealthMonitor("serving-chaos") as mon:
            out1, ver1, final1, steps1 = _run_serving_pipeline(
                image_dir, tmp_path / "chaos")
    finally:
        device_executor.reset()

    # every armed fault actually fired, exactly once
    assert inj.fired == {"decode_error": 1, "engine_task": 1,
                         "device_oom": 1, "transfer_stall": 1,
                         "preemption": 1}

    # zero dropped / double-served: 6 answers, one per request, with
    # the swap landing at the same fixed index in both runs
    assert len(out1) == len(out0) == 6
    assert ver1 == ver0 == ["v1", "v1", "v1", "v2", "v2", "v2"]
    # per-version outputs bit-identical to the fault-free swap run
    for a, b in zip(out1, out0):
        np.testing.assert_array_equal(a, b)
    # and the two versions genuinely disagree (the swap is observable)
    assert not np.array_equal(out1[0], out1[3])

    # the fit leg resumed to the same result
    assert steps1 == steps0 == [1, 2, 3, 4, 5, 6]
    for a, b in zip(jax.tree.leaves(final0.params),
                    jax.tree.leaves(final1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)

    # serving + fit health counts EQUAL to the fault-free swap run:
    # one cutover, the same deterministic shadow set (requests 1 only:
    # 0.5 accumulates to a fire every 2nd pre-swap request), the same
    # per-version cold starts, one completed fit
    for event in (health.SERVING_CUTOVER, health.SERVING_SHADOW_COMPARED,
                  health.SERVING_COLD_START, health.SERVING_SHED,
                  health.SERVING_SHADOW_ERROR, health.FIT_COMPLETED):
        assert mon.count(event) == mon0.count(event), event
    assert mon.count(health.SERVING_CUTOVER) == 1
    assert mon.count(health.SERVING_SHADOW_COMPARED) == 1
    assert mon.count(health.SERVING_COLD_START) == 2  # v1 + v2, once
    assert mon.count(health.SERVING_SHED) == 0

    # the faults added ONLY their recovery events
    assert mon.count(health.DECODE_DEGRADED) == 1
    assert mon.count(health.TASK_RETRIED) == 1
    assert mon.count(health.OOM_RECHUNK) == 1
    assert mon.count(health.CHUNK_RETRY) == 1
    assert mon.count(health.GANG_RESTART) == 1
    assert mon.count(health.FIT_RESUMED) == 1
    assert mon.count(health.TASK_QUARANTINED) == 0
    assert mon0.count(health.OOM_RECHUNK) == 0
    assert mon0.count(health.GANG_RESTART) == 0
