"""Partitioned, columnar DataFrame over Arrow record batches.

This is the rebuild's replacement for the reference's L1 JVM data plane
(Spark core/SQL + TensorFrames; SURVEY.md §1, §2.3). Design points, chosen
for the TPU data path rather than translated from Spark:

- **Columnar storage**: each partition is a ``pyarrow.RecordBatch``; image
  bytes stay contiguous so host staging before ``device_put`` is zero-copy.
- **Lazy plans**: transformations append ops to a plan; ``collect`` /
  ``toArrow`` / transformer execution materialize partition-by-partition in
  one pass (op fusion per partition, like Spark's pipelined narrow stages).
- **Partition-parallel execution with supervision**: a thread pool maps
  partitions under task-level supervision (``engine/supervisor.py``) — the
  engine analog of Spark task retry/speculation (SURVEY.md §5.3):
  failures are classified through ``core.resilience`` (FATAL never
  retried, RETRYABLE backed off, OOM surfaced), hung tasks fail via a
  deadline watchdog, stragglers can be speculatively hedged, and poisoned
  partitions can be quarantined. Ops must be pure/idempotent, which every
  op built by this framework is.
- **No JVM, no shuffle**: the workloads this framework serves (per-row model
  application, featurize, fit) are narrow; wide shuffles are out of scope,
  matching the reference's actual usage of Spark.
"""

from __future__ import annotations

import concurrent.futures as _futures
import itertools
import os
import threading
from typing import (Any, Callable, Container, Dict, Iterable, List, Optional,
                    Sequence, Tuple, Union)

import numpy as np
import pandas as pd
import pyarrow as pa

from sparkdl_tpu.core import durability, profiling, resilience, telemetry
from sparkdl_tpu.engine import supervisor as _sup
from sparkdl_tpu.engine.supervisor import (  # noqa: F401 - re-exported API
    PartitionSupervisor,
    SupervisorConfig,
    TaskAttempt,
    TaskFailure,
)


class EngineConfig:
    """Engine-wide knobs (no globals beyond this explicit, test-overridable one)."""

    # -- task retry (engine/supervisor.run_partition_task) -------------------
    max_task_retries: int = 2
    # Backoff between retryable attempts; 0 keeps the historical
    # retry-immediately behavior. task_retry_policy overrides both.
    task_retry_delay_s: float = 0.0
    task_retry_policy: Optional[resilience.RetryPolicy] = None
    # -- deadline watchdog ----------------------------------------------------
    # Per-task wall-clock budget (seconds); None disables. Enforced
    # cooperatively inside the task and preemptively by the supervisor's
    # watchdog, so a hung op fails the task instead of wedging the run.
    task_timeout_s: Optional[float] = None
    # -- speculative execution (straggler hedging) ----------------------------
    # Off by default (Spark's spark.speculation default): hedging re-runs
    # ops, which must be pure — results are identical, but op side effects
    # (counters in tests) would double.
    speculation: bool = False
    speculation_quantile: float = 0.75
    speculation_multiplier: float = 1.5
    speculation_min_runtime_s: float = 0.05
    # -- quarantine (opt-in skip-and-degrade) ---------------------------------
    # Drop a partition that fails FATALLY (after quarantine_max_fatal
    # classified-fatal attempts) instead of failing the job: a zero-row
    # batch with the op chain's output schema stands in, and the drop is
    # recorded in the active HealthMonitor.
    quarantine: bool = False
    quarantine_max_fatal: int = 1
    # -- cross-partition dynamic batch coalescing (core/executor.py) ----------
    # The inference data plane's device execution service: concurrent
    # partition tasks submitting small chunks against the same compiled fn
    # are coalesced into one padded bucket-ladder launch (docs/PERF.md
    # "Cross-partition coalescing"). Default ON for inference; the
    # training path (Trainer.fit) never routes through the service. A solo
    # request under no contention takes the inline path unchanged.
    coalesce: bool = True
    # Bounded wait (milliseconds) for sibling requests before launching;
    # None = adaptive (a fraction of the observed request latency).
    coalesce_window_ms: Optional[float] = None
    # Row cap of one coalesced launch; None = the request's batch_size.
    coalesce_max_rows: Optional[int] = None
    # -- raw-speed inference (docs/PERF.md "Launch shaping & precision") ------
    # Numeric width of the featurize/transform path, applied at the
    # executor choke point via ModelFunction.with_dtype. "bfloat16"
    # (default): bf16 compute, outputs cast back to float32 (per-element
    # tolerance contract in docs/PERF.md); "float32": the one-knob escape
    # hatch, bit-identical to the pre-knob behavior; "int8": weight-only
    # symmetric per-channel post-training quantization, bf16 activations.
    inference_precision: str = "bfloat16"
    # Donate each staged input batch to its launch so XLA reuses the
    # input's HBM for the outputs — peak memory drops by ~one batch,
    # which is direct headroom for the executor_max_queued_rows shed
    # thresholds above.
    inference_donate_buffers: bool = True
    # Tail-bucket ladder: "tuned" (default) arms the per-model
    # telemetry-tuned BucketPlanner (core/batching.py) — identical to
    # the blind ladder until enough launches are observed, then rungs
    # move to the observed size distribution; "pow2" restores the blind
    # power-of-two ladder everywhere.
    bucket_ladder: str = "tuned"
    # -- executor overload protection (core/executor.py, docs/RESILIENCE.md
    # "Overload & graceful degradation") ---------------------------------------
    # Admission control: per-compiled-fn bounds on queued requests / queued
    # rows. None (default) = unbounded — today's behavior.
    executor_max_queued_requests: Optional[int] = None
    executor_max_queued_rows: Optional[int] = None
    # Over the bound: "block" (default) waits with backpressure, bounded by
    # the caller's task deadline; "shed" fails fast with ExecutorOverloaded
    # (classified RETRYABLE — the engine task retry absorbs the spike).
    executor_overload_mode: str = "block"
    # Priority lane for requests that don't say ("interactive" > "bulk"):
    # interactive drains first and sheds last. Transformers override per
    # instance via their `priority` param.
    executor_default_priority: str = "bulk"
    # Per-model circuit breaker: trip open after this many terminal launch
    # failures within executor_breaker_window_s; fail fast for
    # executor_breaker_cooldown_s, then admit one half-open probe. 0
    # (default) disables the breaker entirely.
    executor_breaker_threshold: int = 0
    executor_breaker_window_s: float = 30.0
    executor_breaker_cooldown_s: float = 1.0
    # Idle coalescing-state retirement: a model's compiled-fn state (and
    # the strong reference pinning its weights) is dropped after this
    # many seconds without a request. The serving residency manager
    # (sparkdl_tpu/serving/residency.py) and tests lower it to make
    # eviction prompt; 5 s is the historical hard-coded value.
    executor_idle_retire_s: float = 5.0
    # -- parallel host decode pool (core/decode_pool.py, docs/PERF.md
    # "Parallel host ingest") --------------------------------------------------
    # Spawn-context worker PROCESSES for the image-decode fan-out (JPEG
    # decode on the PIL fallback is GIL-bound, so the partition thread
    # pool cannot parallelize it). 0 (default) keeps today's inline
    # decode, bit-identical; N > 0 shares one process-wide pool across
    # every ingest path (readImages, loadImagesInternal, streaming fit).
    decode_workers: int = 0
    # Max in-flight decode chunks pool-wide (backpressure bound on host
    # memory for decoded-but-unconsumed pixels); None = 2 * decode_workers.
    decode_pool_inflight: Optional[int] = None
    # -- zero-copy columnar image plane (image/imageIO.py, docs/PERF.md
    # "Columnar data plane") ---------------------------------------------------
    # Build image-struct columns COLUMNAR: a uniform decoded batch packs
    # into ONE contiguous values buffer wrapped zero-copy as the Arrow
    # column's binary child (imageIO.imageArraysToStructColumn — no
    # per-row dict, no per-row tobytes), which arrowImageBatch views
    # back as one NHWC batch downstream, again without copying. The
    # column's logical values are identical to the per-row builder's;
    # ragged batches fall back to it, and False restores it everywhere.
    columnar_images: bool = True
    # Fuse resize into the device program: the uniform fast path ships
    # raw HWC uint8 at SOURCE size and the compiled fn runs cast →
    # resize → normalize → forward as one XLA program
    # (ModelFunction.resized; composes with inference_precision and
    # donation at the executor choke point). False restores the
    # measured r3 host-resize downscale policy
    # (ml/image_transformer._resize_uniform_batch).
    fused_preprocess: bool = True
    # -- durable job recovery (core/durability.py, docs/RESILIENCE.md
    # "Durable recovery") ------------------------------------------------------
    # Root directory for write-ahead partition journals + atomic spills.
    # None (default) = no durability: every path is byte- and
    # behavior-identical to before the knob existed. Set, each
    # materialize/streamPartitions job derives a stable job id (hash of
    # plan + config) under this root and survives kill -9: on restart
    # committed partitions load from verified spill, only uncommitted
    # ones recompute, and rows re-emit in original order.
    durable_dir: Optional[str] = None
    # -- cluster inference plane (sparkdl_tpu/cluster/, docs/DISTRIBUTED.md
    # "Cluster inference") -----------------------------------------------------
    # Spawn-context worker PROCESSES, each hosting a full per-process
    # inference stack (own device runtime, DeviceExecutor + compiled-fn
    # cache, telemetry pinned to the coordinator's run id); supervised
    # materialize/stream partitions route to the least-loaded worker,
    # with retry/hedging/quarantine/deadlines preserved coordinator-side.
    # 0 (default) keeps today's in-process path byte-identical — the
    # cluster package is never even imported.
    cluster_workers: int = 0
    # Max in-flight partition dispatches router-wide (backpressure bound
    # on coordinator memory for shipped-but-unconsumed partitions);
    # None = 2 * cluster_workers.
    cluster_inflight_partitions: Optional[int] = None
    # -- elastic capacity (cluster autoscaler + graceful drain,
    # docs/DISTRIBUTED.md "Elastic capacity") ----------------------------------
    # Arm the router's autoscaler: grow/shrink the live worker set
    # between cluster_min_workers and cluster_max_workers from windowed
    # queue-wait p99 and outstanding rows per worker. False (default)
    # keeps the worker set exactly cluster_workers — byte-identical to
    # before the knob existed. Always forced off INSIDE workers.
    cluster_autoscale: bool = False
    cluster_min_workers: int = 1
    cluster_max_workers: int = 8
    # Telemetry window the scaling signals are computed over, and the
    # minimum quiet period between two scaling actions (cooldown — paired
    # with the high/low hysteresis gap below so the set never flaps).
    autoscale_window_s: float = 5.0
    autoscale_cooldown_s: float = 5.0
    # Scale UP when windowed queue-wait p99 exceeds the high-water mark
    # (or rows-in-flight per worker exceed theirs); scale DOWN only when
    # p99 is below the much lower low-water mark AND a worker sits idle.
    autoscale_queue_wait_high_s: float = 0.5
    autoscale_queue_wait_low_s: float = 0.05
    autoscale_rows_per_worker_high: int = 4096
    # -- live metrics federation (cluster/aggregate.ClusterMetricsView,
    # docs/OBSERVABILITY.md "Cluster metrics federation") ----------------------
    # Cadence (seconds) at which each cluster worker ships a bounded
    # windowed-metrics frame over its result pipe; the coordinator folds
    # the frames into a live cluster-wide view (merged percentiles,
    # summed rates) that the federated SLO watchdog, the autoscaler, and
    # the exporter read mid-run. None (default) disables federation —
    # no frames ship, no view exists, all artifacts byte-identical.
    # NOT forced off inside workers: the worker loop reads this knob to
    # drive its frame cadence.
    cluster_federation_s: Optional[float] = None
    # -- cluster serving plane (sparkdl_tpu/serving/cluster.py,
    # docs/SERVING.md "Cluster serving") ---------------------------------------
    # Route ModelServer.predict through the cluster router: deployments
    # replicate across the cluster workers, requests route with
    # load/locality awareness, worker death re-admits in-flight predicts
    # to survivors within the caller's deadline, and hot-swap becomes a
    # cluster-atomic two-phase cutover. Requires cluster_workers > 0;
    # False (default) keeps the single-process serving path
    # byte-identical — serving/cluster.py is never even imported.
    # Always forced off INSIDE workers (a replica must not recurse).
    serving_cluster: bool = False
    # Per-worker HBM residency budget for replicated deployments; None
    # gives each worker-side registry an unbudgeted cache (models stay
    # resident until retired).
    serving_worker_residency_bytes: Optional[int] = None
    # How many times one in-flight predict may be re-admitted after
    # replica deaths before failing with ServingReplicaLost (the
    # caller's deadline bounds it anyway; this bounds pathological
    # rolling-death churn).
    serving_failover_max: int = 2
    # -- AOT bucket-ladder warmup (serving/registry.py, docs/PERF.md
    # "AOT bucket-ladder warmup") -----------------------------------------------
    # AOT-compile a deployment's full bucket ladder at deploy/prepare
    # time so the first request pays zero compile: wired into
    # ModelRegistry.deploy, ResidencyManager cold loads, and the cluster
    # srv_prepare phase (a replica acks prepared only after its ladder
    # is warm). False (default) keeps today's lazy first-request compile.
    serving_warmup: bool = False
    # -- per-tenant fair queueing (core/executor.py, docs/RESILIENCE.md
    # "Tenant fairness") --------------------------------------------------------
    # Relative deficit-round-robin weights per tenant tag; tenants absent
    # from the dict (and all tenants when None) get weight 1. A tenant
    # with weight 2 drains twice the rows per round of a weight-1 tenant
    # when both have queued work — a flooding tenant saturates only its
    # share.
    executor_tenant_weights: Optional[Dict[str, int]] = None
    # Tenant tag assigned to requests that don't carry one (explicit
    # execute(tenant=...) > ambient executor.tenant_scope > this).
    executor_default_tenant: str = "default"
    # Tenant tag stamped on this job's PARTITION dispatches (engine
    # materialize/stream through the cluster router); None leaves
    # partition work on the default tenant.
    job_tenant: Optional[str] = None
    max_workers: int = max(2, (os.cpu_count() or 4) // 2)
    # DEPRECATED test hook (SURVEY.md §5.3 fault injection):
    # callable(partition_index, attempt) that may raise to simulate a task
    # failure. Kept as a compat shim — new code arms the unified
    # resilience.FaultInjector "engine_task" / "task_stall" points, which
    # share the injector's seeding story.
    fault_injector: Optional[Callable[[int, int], None]] = None

    @classmethod
    def snapshot(cls) -> Dict[str, Any]:
        """Every public knob's current value — the ONE save/restore idiom
        for fixtures and bench legs that mutate the class-wide config
        (new knobs are covered without listing them). Callable knob
        values (a set ``fault_injector``) are deliberately excluded, as
        are the classmethods themselves."""
        return {k: getattr(cls, k) for k in vars(cls)
                if not k.startswith("_") and not callable(getattr(cls, k))}

    @classmethod
    def restore(cls, saved: Dict[str, Any]) -> None:
        """Reapply a :meth:`snapshot`."""
        for k, v in saved.items():
            setattr(cls, k, v)

    # last-validated knob values: validate() is called per device entry,
    # so an unchanged config must cost one tuple build + compare, not the
    # full check battery. Underscore-prefixed: excluded from the test
    # fixtures' public-knob snapshots.
    _validated_knobs: Optional[tuple] = None

    @classmethod
    def validate(cls) -> None:
        """Validate every knob at READ time with a clear ``ValueError``
        (instead of undefined downstream behavior: a negative timeout
        silently expiring every task, a zero queue cap wedging admission,
        an out-of-range quantile never hedging). Called by the knob
        consumers — ``_supervisor_config`` per materialization and
        ``core.executor.execute`` per device entry; memoized on the knob
        values, so the per-entry cost of a steady config is one tuple
        compare."""
        knobs = (cls.max_task_retries, cls.task_retry_delay_s,
                 cls.task_timeout_s, cls.speculation_quantile,
                 cls.speculation_multiplier, cls.speculation_min_runtime_s,
                 cls.quarantine_max_fatal, cls.coalesce_window_ms,
                 cls.coalesce_max_rows, cls.inference_precision,
                 cls.inference_donate_buffers, cls.bucket_ladder,
                 cls.executor_max_queued_requests,
                 cls.executor_max_queued_rows, cls.executor_overload_mode,
                 cls.executor_default_priority,
                 cls.executor_breaker_threshold,
                 cls.executor_breaker_window_s,
                 cls.executor_breaker_cooldown_s,
                 cls.executor_idle_retire_s, cls.decode_workers,
                 cls.decode_pool_inflight, cls.columnar_images,
                 cls.fused_preprocess, cls.cluster_workers,
                 cls.cluster_inflight_partitions, cls.cluster_autoscale,
                 cls.cluster_min_workers, cls.cluster_max_workers,
                 cls.autoscale_window_s, cls.autoscale_cooldown_s,
                 cls.autoscale_queue_wait_high_s,
                 cls.autoscale_queue_wait_low_s,
                 cls.autoscale_rows_per_worker_high,
                 cls.cluster_federation_s,
                 cls.serving_cluster, cls.serving_worker_residency_bytes,
                 cls.serving_failover_max, cls.serving_warmup,
                 (None if cls.executor_tenant_weights is None
                  else tuple(sorted(cls.executor_tenant_weights.items()))),
                 cls.executor_default_tenant, cls.job_tenant,
                 cls.durable_dir, cls.max_workers)
        if knobs == cls._validated_knobs:
            return

        def positive(name, value, allow_none=True, minimum=0.0,
                     exclusive=True):
            if value is None:
                if not allow_none:
                    raise ValueError(f"EngineConfig.{name} must be set")
                return
            bad = value <= minimum if exclusive else value < minimum
            if bad:
                op = ">" if exclusive else ">="
                raise ValueError(
                    f"EngineConfig.{name} must be {op} {minimum} (or "
                    f"None), got {value!r}")

        if cls.max_task_retries < 0:
            raise ValueError("EngineConfig.max_task_retries must be >= 0, "
                             f"got {cls.max_task_retries!r}")
        positive("task_retry_delay_s", cls.task_retry_delay_s,
                 exclusive=False)
        positive("task_timeout_s", cls.task_timeout_s)
        if not 0.0 <= cls.speculation_quantile <= 1.0:
            raise ValueError(
                "EngineConfig.speculation_quantile must be in [0, 1], "
                f"got {cls.speculation_quantile!r}")
        positive("speculation_multiplier", cls.speculation_multiplier)
        positive("speculation_min_runtime_s", cls.speculation_min_runtime_s,
                 exclusive=False)
        if cls.quarantine_max_fatal < 1:
            raise ValueError(
                "EngineConfig.quarantine_max_fatal must be >= 1, got "
                f"{cls.quarantine_max_fatal!r}")
        positive("coalesce_window_ms", cls.coalesce_window_ms,
                 exclusive=False)
        positive("coalesce_max_rows", cls.coalesce_max_rows)
        if cls.inference_precision not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                "EngineConfig.inference_precision must be 'float32', "
                "'bfloat16' or 'int8', got "
                f"{cls.inference_precision!r}")
        if not isinstance(cls.inference_donate_buffers, bool):
            raise ValueError(
                "EngineConfig.inference_donate_buffers must be a bool, "
                f"got {cls.inference_donate_buffers!r}")
        if cls.bucket_ladder not in ("tuned", "pow2"):
            raise ValueError(
                "EngineConfig.bucket_ladder must be 'tuned' or 'pow2', "
                f"got {cls.bucket_ladder!r}")
        positive("executor_max_queued_requests",
                 cls.executor_max_queued_requests)
        positive("executor_max_queued_rows", cls.executor_max_queued_rows)
        if cls.executor_overload_mode not in ("block", "shed"):
            raise ValueError(
                "EngineConfig.executor_overload_mode must be 'block' or "
                f"'shed', got {cls.executor_overload_mode!r}")
        if cls.executor_default_priority not in ("interactive", "bulk"):
            raise ValueError(
                "EngineConfig.executor_default_priority must be "
                "'interactive' or 'bulk', got "
                f"{cls.executor_default_priority!r}")
        if cls.executor_breaker_threshold < 0:
            raise ValueError(
                "EngineConfig.executor_breaker_threshold must be >= 0 "
                f"(0 disables), got {cls.executor_breaker_threshold!r}")
        positive("executor_breaker_window_s", cls.executor_breaker_window_s)
        positive("executor_breaker_cooldown_s",
                 cls.executor_breaker_cooldown_s, exclusive=False)
        positive("executor_idle_retire_s", cls.executor_idle_retire_s,
                 allow_none=False)
        if cls.decode_workers < 0:
            raise ValueError(
                "EngineConfig.decode_workers must be >= 0 (0 disables "
                f"the decode pool), got {cls.decode_workers!r}")
        positive("decode_pool_inflight", cls.decode_pool_inflight)
        if not isinstance(cls.columnar_images, bool):
            raise ValueError(
                "EngineConfig.columnar_images must be a bool, got "
                f"{cls.columnar_images!r}")
        if not isinstance(cls.fused_preprocess, bool):
            raise ValueError(
                "EngineConfig.fused_preprocess must be a bool, got "
                f"{cls.fused_preprocess!r}")
        if cls.cluster_workers < 0:
            raise ValueError(
                "EngineConfig.cluster_workers must be >= 0 (0 disables "
                f"the cluster plane), got {cls.cluster_workers!r}")
        positive("cluster_inflight_partitions",
                 cls.cluster_inflight_partitions)
        if not isinstance(cls.cluster_autoscale, bool):
            raise ValueError(
                "EngineConfig.cluster_autoscale must be a bool, got "
                f"{cls.cluster_autoscale!r}")
        if cls.cluster_min_workers < 1:
            raise ValueError(
                "EngineConfig.cluster_min_workers must be >= 1, got "
                f"{cls.cluster_min_workers!r}")
        if cls.cluster_max_workers < cls.cluster_min_workers:
            raise ValueError(
                "EngineConfig.cluster_max_workers must be >= "
                f"cluster_min_workers ({cls.cluster_min_workers}), got "
                f"{cls.cluster_max_workers!r}")
        positive("autoscale_window_s", cls.autoscale_window_s,
                 allow_none=False)
        positive("autoscale_cooldown_s", cls.autoscale_cooldown_s,
                 allow_none=False, exclusive=False)
        positive("autoscale_queue_wait_high_s",
                 cls.autoscale_queue_wait_high_s, allow_none=False)
        positive("autoscale_queue_wait_low_s",
                 cls.autoscale_queue_wait_low_s, allow_none=False)
        if cls.autoscale_queue_wait_low_s >= cls.autoscale_queue_wait_high_s:
            raise ValueError(
                "EngineConfig.autoscale_queue_wait_low_s must be < "
                "autoscale_queue_wait_high_s "
                f"({cls.autoscale_queue_wait_high_s}), got "
                f"{cls.autoscale_queue_wait_low_s!r} — the hysteresis "
                "gap is what keeps the worker set from flapping")
        if cls.autoscale_rows_per_worker_high < 1:
            raise ValueError(
                "EngineConfig.autoscale_rows_per_worker_high must be "
                f">= 1, got {cls.autoscale_rows_per_worker_high!r}")
        positive("cluster_federation_s", cls.cluster_federation_s)
        if not isinstance(cls.serving_cluster, bool):
            raise ValueError(
                "EngineConfig.serving_cluster must be a bool, got "
                f"{cls.serving_cluster!r}")
        positive("serving_worker_residency_bytes",
                 cls.serving_worker_residency_bytes)
        if cls.serving_failover_max < 0:
            raise ValueError(
                "EngineConfig.serving_failover_max must be >= 0 (0 "
                "fails a moved request on first replica death), got "
                f"{cls.serving_failover_max!r}")
        if not isinstance(cls.serving_warmup, bool):
            raise ValueError(
                "EngineConfig.serving_warmup must be a bool, got "
                f"{cls.serving_warmup!r}")
        if cls.executor_tenant_weights is not None:
            if not isinstance(cls.executor_tenant_weights, dict):
                raise ValueError(
                    "EngineConfig.executor_tenant_weights must be None "
                    "or a dict of tenant -> positive int weight, got "
                    f"{cls.executor_tenant_weights!r}")
            for t, w in cls.executor_tenant_weights.items():
                if not isinstance(t, str) or not t:
                    raise ValueError(
                        "EngineConfig.executor_tenant_weights keys must "
                        f"be non-empty tenant strings, got {t!r}")
                if not isinstance(w, int) or isinstance(w, bool) or w < 1:
                    raise ValueError(
                        "EngineConfig.executor_tenant_weights values "
                        f"must be positive ints, got {t!r}={w!r}")
        if (not isinstance(cls.executor_default_tenant, str)
                or not cls.executor_default_tenant):
            raise ValueError(
                "EngineConfig.executor_default_tenant must be a "
                f"non-empty string, got {cls.executor_default_tenant!r}")
        if cls.job_tenant is not None and (
                not isinstance(cls.job_tenant, str) or not cls.job_tenant):
            raise ValueError(
                "EngineConfig.job_tenant must be None or a non-empty "
                f"tenant string, got {cls.job_tenant!r}")
        if cls.durable_dir is not None and (
                not isinstance(cls.durable_dir, str) or not cls.durable_dir):
            raise ValueError(
                "EngineConfig.durable_dir must be None or a non-empty "
                f"directory path, got {cls.durable_dir!r}")
        if cls.max_workers < 1:
            raise ValueError("EngineConfig.max_workers must be >= 1, got "
                             f"{cls.max_workers!r}")
        cls._validated_knobs = knobs


def _task_policy() -> resilience.RetryPolicy:
    if EngineConfig.task_retry_policy is not None:
        return EngineConfig.task_retry_policy
    return resilience.RetryPolicy(
        max_retries=EngineConfig.max_task_retries,
        base_delay_s=EngineConfig.task_retry_delay_s, jitter=0.0)


def _supervisor_config() -> SupervisorConfig:
    EngineConfig.validate()  # read-time knob validation
    return SupervisorConfig(
        task_timeout_s=EngineConfig.task_timeout_s,
        speculation=EngineConfig.speculation,
        speculation_quantile=EngineConfig.speculation_quantile,
        speculation_multiplier=EngineConfig.speculation_multiplier,
        speculation_min_runtime_s=EngineConfig.speculation_min_runtime_s,
        quarantine=EngineConfig.quarantine,
        quarantine_max_fatal=EngineConfig.quarantine_max_fatal)


# Process-wide partition executor, reused across materializations (VERDICT
# r2 weak #7: a fresh ThreadPoolExecutor per materialize). Rebuilt if
# EngineConfig.max_workers changes (test hook).
_pool: Optional[_futures.ThreadPoolExecutor] = None
_pool_workers: Optional[int] = None
_pool_lock = threading.Lock()


def _executor() -> _futures.ThreadPoolExecutor:
    global _pool, _pool_workers
    with _pool_lock:
        if _pool is None or _pool_workers != EngineConfig.max_workers:
            if _pool is not None:
                _pool.shutdown(wait=False)
            _pool = _futures.ThreadPoolExecutor(
                EngineConfig.max_workers,
                thread_name_prefix="sparkdl-part")
            _pool_workers = EngineConfig.max_workers
        return _pool


def _run_partition(index: int, batch: pa.RecordBatch,
                   ops: Sequence[Callable[[pa.RecordBatch], pa.RecordBatch]],
                   cancelled: Optional[threading.Event] = None
                   ) -> pa.RecordBatch:
    """One partition task: classified retry per engine/supervisor.py
    (FATAL never retried, OOM surfaced, RETRYABLE backed off; terminal
    TaskFailure carries the per-attempt history). ``cancelled`` is the
    supervisor watchdog's abandonment signal (None on inline paths)."""
    out = _sup.run_partition_task(
        index, batch, ops, policy=_task_policy(),
        deadline_s=EngineConfig.task_timeout_s,
        legacy_injector=EngineConfig.fault_injector,
        max_fatal_attempts=(EngineConfig.quarantine_max_fatal
                            if EngineConfig.quarantine else 1),
        cancelled=cancelled)
    if cancelled is None and telemetry.active() is not None:
        # inline (unsupervised) execution paths only — supervised tasks
        # are counted once per WINNING attempt by the supervisor's
        # resolve (a hedge loser running to completion must not
        # double-count the partition's rows)
        telemetry.count(telemetry.M_ENGINE_ROWS_OUT, out.num_rows)
        telemetry.count(telemetry.M_ENGINE_BYTES_OUT, out.nbytes)
    return out


def _cluster_dispatch() -> Callable[..., pa.RecordBatch]:
    """The partition runner for the supervised paths: in-process
    ``_run_partition`` at the default ``cluster_workers=0`` (the cluster
    package is never even imported — the byte-identity gate), or the
    process-wide :meth:`ClusterRouter.run_partition` drop-in when the
    cluster plane is armed. Resolved once per materialization/stream,
    not per task. The nested-inline guard paths stay ``_run_partition``
    unconditionally: a partition task already running ON a cluster
    worker must not recurse into the coordinator's router."""
    if not EngineConfig.cluster_workers:
        return _run_partition
    from sparkdl_tpu.cluster import router as _cluster_router

    router = _cluster_router.maybe_router()
    return _run_partition if router is None else router.run_partition


# Rows collect() assembled while another partition of the same collect() was
# still unresolved: the assembly hidden under the later partitions' H2D and
# device work; 0 where collect() took the whole-table path. Declared here and
# not in core/telemetry.py's table: the engine alone writes it, and the
# catalog module is imported by paths that never see a DataFrame.
M_COLLECT_OVERLAPPED_ROWS = telemetry.declare_metric(
    "sparkdl.collect.overlapped_rows", "counter")
# Rows whose carried cells (the columns the ops pass through from the source
# partition unchanged) collect() built while the partition's task still ran,
# and took once it resolved: only the computed columns were left to convert.
M_COLLECT_CARRIED_ROWS = telemetry.declare_metric(
    "sparkdl.collect.carried_rows", "counter")

# Rows of one slice of the carried cells built ahead: a resolved partition
# waits for at most one slice, and the partitions' threads for the GIL no
# longer than the interpreter's own switch interval, about 5 ms.
_AHEAD_ROWS = 64


def _row_assembly(table: Union[pa.Table, pa.RecordBatch], to: str,
                  **attributes: Any):
    """The ``sparkdl.row_assembly`` span (and phase timer) around turning an
    Arrow table — the whole frame's, or one partition's in ``collect()`` —
    into Python rows or pandas: ``collect()``'s and ``toPandas()``'s own
    cost, apart from computing the partitions."""
    return profiling.annotate(telemetry.SPAN_ROW_ASSEMBLY,
                              rows=table.num_rows, bytes=table.nbytes, to=to,
                              **attributes)


def _is_vector_type(t: pa.DataType) -> bool:
    """A list column whose cells ``ndarray.tolist()`` turns into the very
    objects Arrow's ``as_py()`` gives: the integers and float32/float64
    (tests/engine/test_dataframe.py holds each to it). float16, decimals,
    temporal and bit-packed values stay with Arrow."""
    if not (pa.types.is_list(t) or pa.types.is_large_list(t)
            or pa.types.is_fixed_size_list(t)):
        return False
    v = t.value_type
    return (pa.types.is_integer(v) or pa.types.is_float32(v)
            or pa.types.is_float64(v))


def _vector_cells(chunk: pa.Array) -> Optional[Tuple[List[Any], int]]:
    """One chunk of a vector column as Python lists, through numpy: one
    ``tolist()`` over the chunk's values instead of an Arrow scalar per
    element. Returns the cells (``None`` for a null row) and the number
    of values in them, or ``None`` when a value inside a valid row is
    null (Arrow's ``to_pylist()`` keeps those)."""
    # flatten(): the values of the valid rows alone, back to back — it
    # minds the chunk's offset, a sliced chunk's first offset and whatever
    # a null row's slot covers
    flat = chunk.flatten()
    if flat.null_count:
        return None
    values = flat.to_numpy(zero_copy_only=True)
    valid = (chunk.is_valid().to_numpy(zero_copy_only=False)
             if chunk.null_count else None)
    n_valid = len(chunk) - chunk.null_count
    if pa.types.is_fixed_size_list(chunk.type):
        lengths = np.full(n_valid, chunk.type.list_size, dtype=np.int64)
    else:
        lengths = np.diff(chunk.offsets.to_numpy())
        if valid is not None:
            lengths = lengths[valid]
    if n_valid and lengths[0] > 0 and (lengths == lengths[0]).all():
        rows = values.reshape(n_valid, lengths[0]).tolist()
    else:  # ragged, or zero-length lists: a slice a row
        items = values.tolist()
        ends = np.cumsum(lengths).tolist()
        rows = [items[a:b] for a, b in zip([0] + ends, ends)]
    if valid is None:
        return rows, values.size
    cells: List[Any] = [None] * len(chunk)
    for i, row in zip(np.flatnonzero(valid).tolist(), rows):
        cells[i] = row
    return cells, values.size


def _column_cells(chunks: Iterable[pa.Array]
                  ) -> Tuple[List[Any], bool, int]:
    """One column's cells, chunk after chunk, as ``to_pylist()`` gives
    them: a vector column (:func:`_is_vector_type`) through numpy, every
    other through Arrow's own ``to_pylist()``. Returns the cells, whether
    every chunk went through numpy, and the leaf values numpy converted."""
    cells: List[Any] = []
    whole, values = True, 0
    for chunk in chunks:
        got = _vector_cells(chunk) if _is_vector_type(chunk.type) else None
        if got is None:
            whole = False
            # sparkdl: allow(columnar-hot-path): collect's CONTRACT is
            # per-row Python dicts (Spark Row analog); batch callers use
            # streamPartitions/toArrow
            cells.extend(chunk.to_pylist())
        else:
            cells.extend(got[0])
            values += got[1]
    return cells, whole, values


def _append_rows(rows: List[Dict[str, Any]],
                 data: Union[pa.Table, pa.RecordBatch]) -> Tuple[int, int]:
    """Append ``data.to_pylist()`` to ``rows``, assembled column by column
    (:func:`_column_cells`). Returns the number of columns that went
    through numpy whole and the leaf values numpy converted."""
    columns, vector_columns, values = [], 0, 0
    for column in data.columns:
        cells, whole, converted = _column_cells(
            getattr(column, "chunks", [column]))
        vector_columns += whole and _is_vector_type(column.type)
        values += converted
        columns.append(cells)
    names = data.schema.names
    rows.extend(dict(zip(names, row)) for row in zip(*columns))
    return vector_columns, values


def _assemble_rows(table: pa.Table, **attributes: Any
                   ) -> List[Dict[str, Any]]:
    """``collect()``'s rows of ``table`` (:func:`_append_rows`) under one
    ``sparkdl.row_assembly`` span; ``attributes`` ride on the span."""
    rows: List[Dict[str, Any]] = []
    with _row_assembly(table, "pylist", **attributes) as span:
        vector_columns, values = _append_rows(rows, table)
        span.set_attribute("vector_columns", vector_columns)
        span.set_attribute("fallback_columns",
                           table.num_columns - vector_columns)
        telemetry.count(telemetry.M_COLLECT_VECTORIZED_VALUES, values)
        return rows


def _same_data(a: pa.Array, b: pa.Array) -> bool:
    """Whether two arrays are views of the same memory: the same type,
    offset and length, the same addresses and sizes of their buffers, and
    so of their children's. No value is compared."""
    if a.type != b.type or a.offset != b.offset or len(a) != len(b):
        return False
    if [None if x is None else (x.address, x.size) for x in a.buffers()] != \
            [None if y is None else (y.address, y.size) for y in b.buffers()]:
        return False
    # a child has an offset of its own: the same buffers from elsewhere in
    # them would be other cells
    t = a.type
    if pa.types.is_struct(t):
        return all(_same_data(a.field(i), b.field(i))
                   for i in range(t.num_fields))
    if (pa.types.is_list(t) or pa.types.is_large_list(t)
            or pa.types.is_fixed_size_list(t) or pa.types.is_map(t)):
        return _same_data(a.values, b.values)
    if pa.types.is_dictionary(t):
        return _same_data(a.dictionary, b.dictionary)
    return True


class _CarriedRows:
    """``collect()``'s rows of the columns its frame's ops carry through
    from the source partitions unchanged, built from the source partitions
    while their tasks run, and completed with the computed columns once a
    partition resolves.

    Built ahead only where the declared schema is every column of the
    source partitions, in their order, followed by the columns the ops
    compute — what ``withColumn``, ``withColumnBatch`` and an op that
    declares its schema with ``_schema_with`` give for a new name. A
    partition's cells are taken only if its result's leading columns are
    the source's own Arrow data (:func:`_same_data`); otherwise — a column
    an op replaced, a cluster worker's or a journal's new buffers — the
    partition is assembled as it was before, from its result alone."""

    def __init__(self, partitions: Sequence[pa.RecordBatch], width: int):
        self._sources = partitions
        self._width = width
        self._rows: Dict[int, List[Dict[str, Any]]] = {}   # built so far
        self._values: Dict[int, int] = {}   # leaf values numpy converted
        self._next = 0      # no partition before it has work left
        self.started = False
        self.taken = 0      # rows built ahead and taken

    @classmethod
    def plan(cls, partitions: Sequence[pa.RecordBatch], schema: pa.Schema
             ) -> Optional["_CarriedRows"]:
        """What the frame's ops carry, or ``None`` where they may carry no
        column or compute none after those they carry."""
        if not partitions:
            return None
        source = partitions[0].schema
        width = len(source)
        if not 0 < width < len(schema) or any(
                p.schema != source for p in partitions[1:]):
            return None
        if any(schema.field(j) != source.field(j) for j in range(width)):
            return None
        return cls(partitions, width)

    def step(self, done: Container[int]) -> bool:
        """Build one slice of at most ``_AHEAD_ROWS`` rows of the first
        partition, in index order, that is not ``done`` and not yet built.
        Returns whether any is left."""
        self.started = True
        while self._next < len(self._sources):
            index, built = self._next, len(self._rows.get(self._next, ()))
            if index in done or built >= self._sources[index].num_rows:
                self._next += 1
                continue
            part = self._sources[index].slice(built, _AHEAD_ROWS)
            with _row_assembly(part, "pylist", partition=index,
                               ahead=True) as span:
                vector_columns, values = _append_rows(
                    self._rows.setdefault(index, []), part)
                self._values[index] = self._values.get(index, 0) + values
                span.set_attribute("vector_columns", vector_columns)
                span.set_attribute("fallback_columns",
                                   self._width - vector_columns)
            return True
        return False

    def drop(self) -> None:
        """Build nothing more and take nothing built."""
        self._rows.clear()
        self._next = len(self._sources)

    def take(self, index: int, table: pa.Table
             ) -> Optional[List[Dict[str, Any]]]:
        """The partition's rows: the cells built ahead, the rest of the
        carried ones and the computed columns of ``table``, its result —
        or ``None`` where nothing was built ahead or the result does not
        carry the source's columns."""
        rows = self._rows.pop(index, None)
        values = self._values.pop(index, 0)
        source = self._sources[index]
        if not rows or not all(
                _same_data(table.column(j).chunk(0), source.column(j))
                for j in range(self._width)):
            return None
        ahead = len(rows)
        computed = table.select(range(self._width, table.num_columns))
        with _row_assembly(computed, "pylist", partition=index) as span:
            if ahead < source.num_rows:     # resolved before all was built
                values += _append_rows(rows, source.slice(ahead))[1]
            vector_columns = 0
            for name, column in zip(computed.column_names, computed.columns):
                cells, whole, converted = _column_cells(column.chunks)
                vector_columns += whole and _is_vector_type(column.type)
                values += converted
                for row, cell in zip(rows, cells):
                    row[name] = cell
            span.set_attribute("vector_columns", vector_columns)
            span.set_attribute("fallback_columns",
                               computed.num_columns - vector_columns)
        telemetry.count(telemetry.M_COLLECT_VECTORIZED_VALUES, values)
        self.taken += ahead
        return rows


def _as_record_batches(table: pa.Table, num_partitions: int) -> List[pa.RecordBatch]:
    n = max(1, table.num_rows)
    num_partitions = max(1, min(num_partitions, n))
    rows_per = -(-n // num_partitions)  # ceil
    out = []
    for start in range(0, table.num_rows, rows_per):
        chunk = table.slice(start, rows_per).combine_chunks()
        out.extend(chunk.to_batches())
    if not out:  # empty table: keep one empty batch so schema survives
        out = table.to_batches() or [
            pa.RecordBatch.from_arrays(
                [pa.array([], type=f.type) for f in table.schema],
                schema=table.schema)
        ]
    return out


class DataFrame:
    """Immutable, lazily-evaluated partitioned columnar frame."""

    def __init__(self, partitions: List[pa.RecordBatch], schema: pa.Schema,
                 ops: Optional[List[Callable]] = None):
        self._partitions = partitions
        self._schema = schema
        self._ops = list(ops or [])
        self._materialized: Optional[List[pa.RecordBatch]] = None
        self._lock = threading.Lock()
        # (process_id, num_processes) when this frame is one host's
        # round-robin partition share (processShard); propagated through
        # lazy ops so downstream transforms don't re-shard and
        # gatherProcesses can reassemble the original partition order.
        self._process_shard: Optional[Tuple[int, int]] = None

    # -- constructors --------------------------------------------------------

    @classmethod
    def fromArrow(cls, table: pa.Table, numPartitions: Optional[int] = None
                  ) -> "DataFrame":
        parts = _as_record_batches(table, numPartitions or EngineConfig.max_workers)
        return cls(parts, table.schema)

    @classmethod
    def fromPandas(cls, pdf: pd.DataFrame, numPartitions: Optional[int] = None
                   ) -> "DataFrame":
        return cls.fromArrow(pa.Table.from_pandas(pdf, preserve_index=False),
                             numPartitions)

    @classmethod
    def fromRows(cls, rows: List[Dict[str, Any]], schema: Optional[pa.Schema] = None,
                 numPartitions: Optional[int] = None) -> "DataFrame":
        if schema is not None:
            table = pa.Table.from_pylist(rows, schema=schema)
        else:
            table = pa.Table.from_pylist(rows)
        return cls.fromArrow(table, numPartitions)

    @classmethod
    def fromColumns(cls, columns: Dict[str, Any],
                    numPartitions: Optional[int] = None) -> "DataFrame":
        """Build from {name: numpy-or-list}; N-D arrays become FixedSizeList cols."""
        arrays, fields = [], []
        for name, values in columns.items():
            arr = to_arrow_array(values)
            arrays.append(arr)
            fields.append(pa.field(name, arr.type))
        table = pa.Table.from_arrays(arrays, schema=pa.schema(fields))
        return cls.fromArrow(table, numPartitions)

    # -- metadata ------------------------------------------------------------

    @property
    def schema(self) -> pa.Schema:
        return self._schema

    @property
    def columns(self) -> List[str]:
        return [f.name for f in self._schema]

    @property
    def numPartitions(self) -> int:
        return len(self._partitions)

    def __repr__(self) -> str:
        cols = ", ".join(f"{f.name}: {f.type}" for f in self._schema)
        return f"DataFrame[{cols}] ({self.numPartitions} partitions)"

    # -- execution -----------------------------------------------------------

    def _quarantine_probe(self, index: int) -> pa.RecordBatch:
        """Zero-row stand-in for a quarantined partition: the op chain run
        on an empty slice keeps the chain's output schema and partition
        alignment while dropping the poisoned rows (data-dependent
        failures don't fire on zero rows; if even this fails, the
        supervisor propagates the original TaskFailure)."""
        out = self._partitions[index].slice(0, 0)
        for op in self._ops:
            out = op(out)
        return out

    def _materialize(self, on_result: Optional[
            Callable[[int, pa.RecordBatch], None]] = None,
            on_idle: Optional[Callable[[], bool]] = None
            ) -> List[pa.RecordBatch]:
        """The computed partitions, in order; computed once and kept.
        ``on_result`` and ``on_idle`` are
        :meth:`PartitionSupervisor.run_all`'s, and are used only where the
        partitions run as supervised tasks of this call: a frame already
        materialized or without ops, a nested call run inline and the
        durable path hand nothing over and call nothing."""
        with self._lock:
            if self._materialized is not None:
                return self._materialized
            if not self._ops:
                self._materialized = self._partitions
                return self._materialized
            if threading.current_thread().name.startswith("sparkdl-part"):
                # nested materialization from inside a partition task: run
                # inline — waiting on the shared pool from one of its own
                # threads could deadlock. Classified retry still applies;
                # deadline enforcement is cooperative only (no watchdog).
                self._materialized = [
                    _run_partition(i, b, self._ops)
                    for i, b in enumerate(self._partitions)]
                return self._materialized
            # Supervised parallel execution (engine/supervisor.py):
            # classified retry per task, deadline watchdog, optional
            # straggler hedging and quarantine. The supervisor keeps the
            # old barrier semantics on FAILURE — it waits out attempts
            # still running user ops (the shared pool outlives this call),
            # skipping only watchdog-failed tasks, whose threads may be
            # wedged on the hung op. A clean run may leave a hedge
            # loser's discarded pure ops finishing in the background.
            ops = self._ops
            journal = durability.maybe_journal(self._partitions,
                                               self._schema, ops)
            if journal is not None:
                with telemetry.span(telemetry.SPAN_MATERIALIZE,
                                    partitions=len(self._partitions),
                                    ops=len(ops), durable=True):
                    self._materialized = self._materialize_durable(journal,
                                                                   ops)
                return self._materialized
            sup = PartitionSupervisor(_executor(), _supervisor_config(),
                                      quarantine_probe=self._quarantine_probe)
            dispatch = _cluster_dispatch()
            # the span is open while tasks are CREATED, so every
            # partition task's trace context parents under it
            with telemetry.span(telemetry.SPAN_MATERIALIZE,
                                partitions=len(self._partitions),
                                ops=len(ops)):
                self._materialized = sup.run_all(
                    [(i, lambda cancel, i=i, b=b: dispatch(i, b, ops,
                                                           cancel))
                     for i, b in enumerate(self._partitions)], on_result,
                    on_idle)
            return self._materialized

    def _durable_supervisor(self, journal) -> PartitionSupervisor:
        """Supervisor whose quarantine verdicts COMMIT: a poisoned
        partition's zero-row stand-in is journaled (quarantined=True), so
        a restarted job honors the verdict from spill instead of
        re-poisoning the gang."""
        return PartitionSupervisor(
            _executor(), _supervisor_config(),
            quarantine_probe=lambda i: journal.commit(
                i, self._quarantine_probe(i), quarantined=True))

    def _durable_runner(self, journal, i: int, ops,
                        dispatch: Callable[..., pa.RecordBatch]
                        = _run_partition):
        """A partition runner that journals: count the attempt, run the
        op chain (in-process or via the cluster router — the journal
        wraps OUTSIDE the dispatch, so a cluster re-dispatch after a
        worker death is zero-recompute for committed partitions), spill
        + commit the result before handing it back."""
        b = self._partitions[i]

        def run(cancel=None, i=i, b=b):
            journal.note_attempt(i)
            return journal.commit(i, dispatch(i, b, ops, cancel))

        return run

    def _materialize_durable(self, journal, ops) -> List[pa.RecordBatch]:
        """Durable materialization (docs/RESILIENCE.md "Durable
        recovery"): verified-committed partitions load from spill, only
        uncommitted ones run through the supervisor, each committing
        through the write-ahead journal as it completes. Output order
        and bytes are identical to an uninterrupted run."""
        committed = journal.resume()
        todo = [i for i in range(len(self._partitions)) if i not in committed]
        results: Dict[int, pa.RecordBatch] = {}
        if todo:
            sup = self._durable_supervisor(journal)
            dispatch = _cluster_dispatch()
            computed = sup.run_all(
                [(i, self._durable_runner(journal, i, ops,
                                          dispatch=dispatch))
                 for i in todo])
            results.update(zip(todo, computed))
        for i in committed:
            results[i] = journal.load(i)
        return [results[i] for i in range(len(self._partitions))]

    def _stream_durable(self, journal, indices: List[int], prefetch: int
                        ) -> Iterable[pa.RecordBatch]:
        """Durable streaming: restored partitions serve from spill,
        uncommitted ones stream through the supervisor (same bounded
        prefetch), interleaved back into the requested visit order."""
        committed = journal.resume()
        ops = self._ops
        todo = [i for i in indices if i not in committed]
        sup = self._durable_supervisor(journal)
        dispatch = _cluster_dispatch()

        def runners():
            for i in todo:
                yield i, self._durable_runner(journal, i, ops,
                                              dispatch=dispatch)

        stream = sup.run_stream(runners(), prefetch=prefetch)
        try:
            for i in indices:
                if i in committed:
                    yield journal.load(i)
                else:
                    yield next(stream)
        finally:
            stream.close()

    def toArrow(self) -> pa.Table:
        batches = self._materialize()
        try:
            return pa.Table.from_batches(batches, schema=self._schema)
        except (pa.ArrowInvalid, pa.ArrowTypeError):
            # Declared schema can be imprecise when a withColumn had no
            # explicit outputType (type inferred at materialization); unify
            # the materialized batch schemas, preferring non-null types.
            unified = pa.unify_schemas([b.schema for b in batches],
                                       promote_options="permissive")
            casted = [b.cast(unified) for b in batches]
            return pa.Table.from_batches(casted, schema=unified)

    def toPandas(self) -> pd.DataFrame:
        table = self.toArrow()
        with _row_assembly(table, "pandas"):
            return table.to_pandas()

    def collect(self) -> List[Dict[str, Any]]:
        """The frame's rows: a list of dicts keyed by column name in the
        schema's order, equal to ``toArrow().to_pylist()`` cell types
        included — a vector cell is a list of Python ``int``/``float``, a
        ``binary`` cell ``bytes``, a struct a dict, a null cell ``None``
        (tests/engine/test_dataframe.py holds ``collect()`` to it).

        Where the partitions are computed by this call, as supervised
        tasks, a partition's rows are assembled as soon as its task has
        resolved, on the calling thread, while the partitions behind it
        are still in H2D or on the device (``run_all``'s ``on_result``;
        what that costs the supervision is said there), and the lists are
        put together in partition order at the end, whatever order the
        partitions finished in. Each assembled partition has its own
        ``sparkdl.row_assembly`` span (attribute ``partition``);
        ``sparkdl.collect.overlapped_rows`` counts the rows assembled
        with another partition still unresolved. That is done only while
        every batch has the frame's declared schema, since then the
        table's rows are the partitions' rows, one after the other. On
        the first batch that has another one (a ``withColumn`` without
        ``outputType``) what was assembled is dropped and the rows are
        those of ``toArrow()``'s unified, cast table, assembled whole
        under one span — as they are for a frame already materialized or
        without ops, one partition, a nested ``collect()`` from a
        partition's thread and the durable path, where nothing runs
        beside the assembly. A failing partition raises what
        ``toArrow()`` raises, and the rows assembled until then go with
        it.

        Before a partition resolves, while the supervised tasks run and no
        resolved partition waits to be handed over (``run_all``'s
        ``on_idle``), the cells of the columns the ops carry through from
        the source partition unchanged are built from the source
        partition, in slices of ``_AHEAD_ROWS`` rows, partition after
        partition in index order (:class:`_CarriedRows`: where they are
        taken and where they are dropped); each slice has its own
        ``sparkdl.row_assembly`` span (attributes ``partition`` and
        ``ahead``). A partition whose carried cells were built is
        assembled on its own even where the run handed it nothing over,
        and only its computed columns are left to convert once it
        resolves; ``sparkdl.collect.carried_rows`` counts the rows whose
        carried cells were built ahead and taken. Every cell and every
        dict is this call's own: nothing is kept from one call to the
        next."""
        assembled: Dict[int, List[Dict[str, Any]]] = {}
        declared = True     # every batch so far has the declared schema
        carried = _CarriedRows.plan(self._partitions, self._schema)

        def assemble(index: int, batch: pa.RecordBatch) -> None:
            nonlocal declared
            if not declared:
                return
            try:    # Table.from_batches' own test in toArrow()
                table = pa.Table.from_batches([batch], schema=self._schema)
            except (pa.ArrowInvalid, pa.ArrowTypeError):
                declared = False
                if carried is not None:
                    carried.drop()
                return
            rows = None if carried is None else carried.take(index, table)
            assembled[index] = (_assemble_rows(table, partition=index)
                                if rows is None else rows)

        batches = self._materialize(
            assemble, None if carried is None else
            lambda: carried.step(assembled))
        # what run_all handed over, it handed over beside unresolved tasks
        overlapped = sum(map(len, assembled.values()))
        if assembled or (carried is not None and carried.started):
            for index, batch in enumerate(batches):
                if index not in assembled:
                    assemble(index, batch)
        if not (assembled and declared):
            return _assemble_rows(self.toArrow())
        telemetry.count(M_COLLECT_OVERLAPPED_ROWS, overlapped)
        if carried is not None and carried.started:
            telemetry.count(M_COLLECT_CARRIED_ROWS, carried.taken)
        return list(itertools.chain.from_iterable(
            assembled[index] for index in range(len(batches))))

    def count(self) -> int:
        return sum(b.num_rows for b in self._materialize())

    def isEmpty(self) -> bool:
        return self.count() == 0

    def show(self, n: int = 20) -> None:
        print(self.limit(n).toPandas())

    def foreachPartition(self, fn: Callable[[pa.RecordBatch], None]) -> None:
        for batch in self._materialize():
            fn(batch)

    def partitionsIter(self) -> Iterable[pa.RecordBatch]:
        """Iterate materialized partitions (streaming consumption order)."""
        yield from self._materialize()

    def streamPartitions(self, prefetch: int = 2,
                         order: Optional[Sequence[int]] = None,
                         process_id: Optional[int] = None,
                         num_processes: Optional[int] = None
                         ) -> Iterable[pa.RecordBatch]:
        """Compute and yield partitions one at a time WITHOUT caching.

        Memory stays bounded by ``prefetch + 1`` computed partitions (the
        streaming-``fit`` contract, SURVEY.md §3.3: the reference
        ``collect()``-ed the dataset to the driver — its scalability
        cliff). Re-iterating recomputes the op chain (use ``cache()``
        first to trade memory for decode-once). Already-materialized
        frames yield their cached partitions directly. ``order``: visit
        partitions in this index order (per-epoch shuffle of a streaming
        train loop).

        ``process_id``/``num_processes`` (SURVEY.md §2.5, multi-host data
        plane): restrict this process to its round-robin share of the
        (possibly permuted) visit order — host ``p`` computes/decodes only
        positions ``p, p+n, p+2n, …``, the engine analog of Spark
        assigning partitions to executors. Every process must pass the
        same ``order`` (derive it from a shared seed) for the assignment
        to partition the dataset.
        """
        indices = list(order) if order is not None else list(range(
            len(self._partitions)))
        if num_processes is not None and num_processes > 1:
            if process_id is None or not 0 <= process_id < num_processes:
                raise ValueError(
                    f"process_id must be in [0, {num_processes}), got "
                    f"{process_id}")
            indices = indices[process_id::num_processes]
        with self._lock:
            materialized = self._materialized
        if materialized is not None:
            for i in indices:
                yield materialized[i]
            return
        if not self._ops:
            for i in indices:
                yield self._partitions[i]
            return
        if threading.current_thread().name.startswith("sparkdl-part"):
            # nested streaming from inside a partition task: run inline —
            # waiting on the shared pool from one of its own threads could
            # deadlock (same guard as _materialize)
            for i in indices:
                yield _run_partition(i, self._partitions[i], self._ops)
            return
        journal = durability.maybe_journal(self._partitions, self._schema,
                                           self._ops)
        if journal is not None:
            yield from self._stream_durable(journal, indices, prefetch)
            return
        # Supervised bounded-prefetch streaming on the shared process-wide
        # executor (VERDICT r3 weak #6: no per-epoch pool churn). In-flight
        # work is capped by `prefetch`, not by pool width; tasks get the
        # same classified retry / deadline watchdog / hedging / quarantine
        # as _materialize. Abandoned iteration (early break / error)
        # CANCELS unstarted attempts before draining the running ones, so
        # an early break doesn't silently compute (and decode) the rest of
        # the epoch.
        sup = PartitionSupervisor(_executor(), _supervisor_config(),
                                  quarantine_probe=self._quarantine_probe)
        parts, ops = self._partitions, self._ops
        dispatch = _cluster_dispatch()

        def runners():
            for i in indices:
                yield i, (lambda cancel, i=i: dispatch(
                    i, parts[i], ops, cancel))

        yield from sup.run_stream(runners(), prefetch=prefetch)

    # -- transformations (lazy) ----------------------------------------------

    def _with_op(self, op: Callable[[pa.RecordBatch], pa.RecordBatch],
                 schema: pa.Schema) -> "DataFrame":
        # Reuse already-materialized results (e.g. after cache()) so derived
        # frames don't recompute the upstream op chain.
        if self._materialized is not None and self._ops:
            out = DataFrame(self._materialized, schema, [op])
        else:
            out = DataFrame(self._partitions, schema, self._ops + [op])
        out._process_shard = self._process_shard
        return out

    def mapPartitions(self, fn: Callable[[pa.RecordBatch], pa.RecordBatch],
                      schema: Optional[pa.Schema] = None) -> "DataFrame":
        return self._with_op(fn, schema or self._schema)

    def select(self, *cols: str) -> "DataFrame":
        names = list(cols)
        for name in names:
            if name not in self.columns:
                raise KeyError(f"No such column: {name!r}")
        schema = pa.schema([self._schema.field(n) for n in names])

        def op(batch: pa.RecordBatch) -> pa.RecordBatch:
            cols = [batch.column(batch.schema.get_field_index(n)) for n in names]
            # Use the batch's actual types, not the declared schema: an
            # upstream withColumn without explicit outputType only learns its
            # type at materialization.
            actual = pa.schema([pa.field(n, c.type) for n, c in zip(names, cols)])
            return pa.RecordBatch.from_arrays(cols, schema=actual)

        return self._with_op(op, schema)

    def drop(self, *cols: str) -> "DataFrame":
        keep = [c for c in self.columns if c not in cols]
        return self.select(*keep)

    def selectExpr(self, *exprs: str) -> "DataFrame":
        """SQL projection over columns, literals and registered UDFs.

        Supports ``col``, ``col as alias``, ``*``, numeric/'string'
        literals, and nested multi-argument UDF calls
        (``udf1(udf2(image), other_col) as out``) — the engine analog of
        the reference's model-as-SQL-UDF serving path (SURVEY.md §3.4).
        UDFs resolve against ``sparkdl_tpu.udf.udf_registry``; the grammar
        lives in ``engine/sql_expr.py``.
        """
        from sparkdl_tpu.engine import sql_expr

        frame = self
        temp_counter = [0]
        # (source_col_on_frame, output_name); rename happens only in the
        # final projection — temp columns drop by omission — so one source
        # column can feed several outputs.
        projection: List[Tuple[str, str]] = []

        def fresh_temp() -> str:
            temp_counter[0] += 1
            return f"__sdl_expr_{temp_counter[0]}"

        def evaluate(node) -> str:
            """Materialize the expression as a column; returns its name."""
            nonlocal frame
            if isinstance(node, sql_expr.Column):
                if node.name not in self.columns:
                    raise KeyError(f"No such column: {node.name!r}")
                return node.name
            if isinstance(node, sql_expr.Literal):
                tmp = fresh_temp()
                frame = frame.withConstantColumn(tmp, node.value)
                return tmp
            if isinstance(node, sql_expr.Call):
                from sparkdl_tpu.udf import udf_registry  # lazy: layering

                arg_cols = [evaluate(a) for a in node.args]
                tmp = fresh_temp()
                frame = udf_registry.get(node.fn).apply(frame, arg_cols, tmp)
                return tmp
            raise ValueError(f"Cannot evaluate {node!r}")

        for expr in exprs:
            node, alias = sql_expr.parse(expr)
            if isinstance(node, sql_expr.Star):
                projection.extend((c, c) for c in self.columns)
                continue
            src = evaluate(node)
            out = alias or (src if isinstance(node, sql_expr.Column)
                            else sql_expr.default_name(expr))
            projection.append((src, out))

        def project(batch: pa.RecordBatch) -> pa.RecordBatch:
            cols = [batch.column(batch.schema.get_field_index(src))
                    for src, _ in projection]
            actual = pa.schema([pa.field(out, c.type)
                                for (_, out), c in zip(projection, cols)])
            return pa.RecordBatch.from_arrays(cols, schema=actual)

        schema = pa.schema([
            pa.field(out, frame._schema.field(src).type
                     if src in frame._schema.names else pa.null())
            for src, out in projection])
        return frame._with_op(project, schema)

    def withConstantColumn(self, name: str, value: Any) -> "DataFrame":
        """Add a column holding ``value`` in every row (literal support)."""
        arrow_type = pa.scalar(value).type

        def op(batch: pa.RecordBatch) -> pa.RecordBatch:
            arr = pa.array([value] * batch.num_rows, type=arrow_type)
            return _set_column(batch, name, arr)

        return self._with_op(op, _schema_with(self._schema, name, arrow_type))

    def withColumnRenamed(self, existing: str, new: str) -> "DataFrame":
        if existing not in self.columns:
            raise KeyError(f"No such column: {existing!r}")
        schema = pa.schema([
            pa.field(new, f.type) if f.name == existing else f
            for f in self._schema])

        def op(batch: pa.RecordBatch) -> pa.RecordBatch:
            actual = pa.schema([
                pa.field(new, c.type) if n == existing else pa.field(n, c.type)
                for n, c in zip(batch.schema.names, batch.columns)])
            return pa.RecordBatch.from_arrays(list(batch.columns), schema=actual)

        return self._with_op(op, schema)

    def withColumn(self, name: str, fn: Callable, inputCols: Sequence[str],
                   outputType: Optional[pa.DataType] = None) -> "DataFrame":
        """Row-wise UDF column: ``fn(*input_values) -> value``.

        The engine analog of a Spark Python UDF ``withColumn``. For
        vectorized device work use :meth:`withColumnBatch`.
        """
        out_type = outputType

        def op(batch: pa.RecordBatch) -> pa.RecordBatch:
            # sparkdl: allow(columnar-hot-path): row-wise UDF semantics —
            # fn receives Python values by contract; vectorized work
            # belongs in withColumnBatch
            inputs = [batch.column(batch.schema.get_field_index(c)).to_pylist()
                      for c in inputCols]
            values = [fn(*row) for row in zip(*inputs)] if inputs else []
            if out_type is not None:
                arr = pa.array(values, type=out_type)
            else:
                arr = pa.array(values)
            return _set_column(batch, name, arr)

        schema = _schema_with(self._schema, name,
                              out_type if out_type is not None else pa.null())
        return self._with_op(op, schema)

    def withColumnBatch(self, name: str, fn: Callable[[pa.RecordBatch], pa.Array],
                        outputType: Optional[pa.DataType] = None) -> "DataFrame":
        """Vectorized column: ``fn(record_batch) -> pa.Array`` (len == num_rows).

        This is the hook model transformers use: fn stages the whole
        partition to the device in one transfer and returns a columnar
        result — the TensorFrames ``map_blocks`` analog (SURVEY.md §3.2).
        """
        out_type = outputType

        def op(batch: pa.RecordBatch) -> pa.RecordBatch:
            arr = fn(batch)
            if not isinstance(arr, (pa.Array, pa.ChunkedArray)):
                arr = pa.array(arr, type=out_type)
            elif out_type is not None and arr.type != out_type:
                arr = arr.cast(out_type)
            if len(arr) != batch.num_rows:
                raise ValueError(
                    f"withColumnBatch fn returned {len(arr)} values for "
                    f"{batch.num_rows} rows")
            return _set_column(batch, name, arr)

        schema = _schema_with(self._schema, name,
                              out_type if out_type is not None else pa.null())
        return self._with_op(op, schema)

    def where(self, expr: str) -> "DataFrame":
        """SQL row filter: ``df.where("label = 1 AND score > 0.5")``.

        The filter side of the serving surface (SURVEY.md §3.4):
        comparisons (``= != <> < <= > >=``), ``AND/OR/NOT``, grouping
        parens and ``IS [NOT] NULL`` over columns and literals, with SQL
        null semantics (a comparison against NULL is not-true — the row
        drops). Grammar in ``engine/sql_expr.py``; UDF calls belong in
        ``selectExpr``, not here.
        """
        from sparkdl_tpu.engine import sql_expr

        node = sql_expr.parse_bool(expr)
        cols = sql_expr.bool_columns(node)
        for c in cols:
            if c not in self.columns:
                raise KeyError(f"No such column: {c!r}")

        def pred(*vals) -> bool:
            return sql_expr.eval_bool(node, dict(zip(cols, vals))) is True

        return self.filter(pred, inputCols=cols)

    def createOrReplaceTempView(self, name: str) -> None:
        """Register this frame under ``name`` for ``engine.sql()`` queries
        (the analog of Spark's temp-view registry, SURVEY.md §3.4)."""
        if not name or not name.replace("_", "").isalnum():
            raise ValueError(f"Bad view name {name!r}")
        _temp_views[name] = self

    def filter(self, predicate: Callable, inputCols: Sequence[str]) -> "DataFrame":
        def op(batch: pa.RecordBatch) -> pa.RecordBatch:
            if not inputCols:
                # constant predicate (e.g. where("1 = 1")): zip(*[]) would
                # yield a zero-length mask regardless of num_rows
                keep = bool(predicate())
                mask = pa.array([keep] * batch.num_rows, type=pa.bool_())
                return batch.filter(mask)
            # sparkdl: allow(columnar-hot-path): row-wise predicate
            # semantics — the user callable receives Python values
            inputs = [batch.column(batch.schema.get_field_index(c)).to_pylist()
                      for c in inputCols]
            mask = pa.array([bool(predicate(*row)) for row in zip(*inputs)],
                            type=pa.bool_())
            return batch.filter(mask)

        return self._with_op(op, self._schema)

    def dropna(self, subset: Optional[Sequence[str]] = None) -> "DataFrame":
        cols = list(subset or self.columns)

        def op(batch: pa.RecordBatch) -> pa.RecordBatch:
            mask = np.ones(batch.num_rows, dtype=bool)
            for c in cols:
                arr = batch.column(batch.schema.get_field_index(c))
                mask &= np.asarray(arr.is_valid())
            return batch.filter(pa.array(mask))

        return self._with_op(op, self._schema)

    # -- materializing transformations ---------------------------------------

    def repartition(self, numPartitions: int) -> "DataFrame":
        return DataFrame.fromArrow(self.toArrow(), numPartitions)

    def limit(self, n: int) -> "DataFrame":
        """First n rows, materializing only as many partitions as needed."""
        if self._materialized is not None:
            return DataFrame.fromArrow(self.toArrow().slice(0, n),
                                       numPartitions=1)
        taken: List[pa.RecordBatch] = []
        count = 0
        for i, part in enumerate(self._partitions):
            batch = _run_partition(i, part, self._ops)
            taken.append(batch)
            count += batch.num_rows
            if count >= n:
                break
        if not taken:
            return DataFrame(self._partitions, self._schema, self._ops)
        table = pa.Table.from_batches(taken, schema=taken[0].schema).slice(0, n)
        return DataFrame.fromArrow(table, numPartitions=1)

    def union(self, other: "DataFrame") -> "DataFrame":
        table = pa.concat_tables([self.toArrow(), other.toArrow()])
        return DataFrame.fromArrow(
            table, numPartitions=self.numPartitions + other.numPartitions)

    def orderBy(self, *cols: str, ascending: Union[bool, Sequence[bool]] = True
                ) -> "DataFrame":
        """Global sort (materializing, like Spark's orderBy shuffle)."""
        if not cols:
            raise ValueError("orderBy needs at least one column")
        if isinstance(ascending, bool):
            ascending = [ascending] * len(cols)
        if len(ascending) != len(cols):
            raise ValueError("ascending must match the number of columns")
        for c in cols:
            if c not in self.columns:
                raise KeyError(f"No such column: {c!r}")
        keys = [(c, "ascending" if a else "descending")
                for c, a in zip(cols, ascending)]
        return DataFrame.fromArrow(self.toArrow().sort_by(keys),
                                   numPartitions=self.numPartitions)

    def groupBy(self, *cols: str) -> "GroupedData":
        """Grouped aggregation (Arrow-native group_by under the hood)."""
        for c in cols:
            if c not in self.columns:
                raise KeyError(f"No such column: {c!r}")
        return GroupedData(self, list(cols))

    def join(self, other: "DataFrame", on: Union[str, Sequence[str]],
             how: str = "inner") -> "DataFrame":
        """Equi-join on key column(s) (Spark's ``df.join(other, on, how)``;
        ``inner`` or ``left``).

        Materializing hash join sized to this framework's workloads:
        the RIGHT side builds the hash table (metadata/label frames —
        keep the small side on the right), the left streams through it.
        Key columns appear once (Spark's USING semantics); other
        name collisions raise rather than silently disambiguate.
        Row multiplicity matches SQL: matching left×right pairs multiply.
        """
        from collections import defaultdict

        keys = [on] if isinstance(on, str) else list(on)
        if how not in ("inner", "left"):
            raise ValueError(f"how must be 'inner' or 'left', got {how!r}")
        for k in keys:
            if k not in self.columns:
                raise KeyError(f"No such column on left: {k!r}")
            if k not in other.columns:
                raise KeyError(f"No such column on right: {k!r}")
        left_other = [c for c in self.columns if c not in keys]
        right_other = [c for c in other.columns if c not in keys]
        clash = set(left_other) & set(right_other)
        if clash:
            raise ValueError(
                f"join would duplicate columns {sorted(clash)}; rename "
                "one side first (withColumnRenamed)")

        # build side: the right frame, fully materialized once. Keys are
        # frozen (nested list/struct/binary keys hash like distinct()'s).
        right_table = other.toArrow()
        build: Dict[Any, List[Dict[str, Any]]] = defaultdict(list)
        # sparkdl: allow(columnar-hot-path): hash-join build side needs
        # hashable Python keys — documented metadata-frame operation
        for r in right_table.to_pylist():
            key = tuple(_freeze_value(r[k]) for k in keys)
            if any(v is None for v in key):
                continue  # SQL: null keys never match
            build[key].append({c: r[c] for c in right_other})

        # probe side streams per materialized partition; the output uses
        # an EXPLICIT schema (actual left types + right types) in one
        # fixed column order, so dtypes survive instead of being
        # re-inferred from Python values (an all-null right column under
        # a left join would otherwise degrade to pa.null()).
        left_batches = self._materialize()
        left_schema = (pa.unify_schemas([b.schema for b in left_batches],
                                        promote_options="permissive")
                       if left_batches else self._schema)
        joined_schema = pa.schema(
            [left_schema.field(name) for name in left_schema.names]
            + [right_table.schema.field(c) for c in right_other])

        out_tables: List[pa.Table] = []
        for batch in left_batches:
            out_rows: List[Dict[str, Any]] = []
            # sparkdl: allow(columnar-hot-path): hash-join probe side —
            # same Python-key hashing as the build side above
            for r in batch.to_pylist():
                key = tuple(_freeze_value(r[k]) for k in keys)
                matches = ([] if any(v is None for v in key)
                           else build.get(key, []))
                if matches:
                    for m in matches:
                        out_rows.append({**r, **m})
                elif how == "left":
                    out_rows.append(
                        {**r, **{c: None for c in right_other}})
            if out_rows:
                out_tables.append(
                    pa.Table.from_pylist(out_rows, schema=joined_schema))
        if not out_tables:
            empty = pa.Table.from_pylist([], schema=joined_schema)
            return DataFrame.fromArrow(empty, numPartitions=1)
        return DataFrame.fromArrow(pa.concat_tables(out_tables),
                                   numPartitions=max(1, self.numPartitions))

    def distinct(self) -> "DataFrame":
        """Deduplicated rows (Spark's distinct; materializing, order of
        first occurrence).

        Cost note: rows convert to Python objects for hashing — O(dataset)
        driver-side work, like Spark's own shuffle-dedup. Meant for
        metadata frames (labels, uris), not image-blob columns.
        """
        table = self.toArrow()
        if table.num_rows == 0:
            return DataFrame.fromArrow(table, numPartitions=1)
        seen = set()
        keep = []
        # sparkdl: allow(columnar-hot-path): distinct() hashes Python
        # values by design (documented metadata-frame cost note above)
        for i, row in enumerate(table.to_pylist()):
            key = tuple(_freeze_value(v) for v in row.values())
            if key not in seen:
                seen.add(key)
                keep.append(i)
        return DataFrame.fromArrow(
            table.take(pa.array(keep, type=pa.int64())),
            numPartitions=max(1, self.numPartitions))

    def sample(self, fraction: float, seed: int = 0) -> "DataFrame":
        """Seeded Bernoulli row sample without replacement (Spark's
        ``sample(fraction, seed)``; materializing)."""
        if not 0.0 <= fraction <= 1.0:
            raise ValueError(f"fraction must be in [0, 1], got {fraction}")
        table = self.toArrow()
        mask = np.random.default_rng(seed).random(table.num_rows) < fraction
        return DataFrame.fromArrow(
            table.take(pa.array(np.nonzero(mask)[0], type=pa.int64())),
            numPartitions=max(1, self.numPartitions))

    def randomSplit(self, weights: Sequence[float],
                    seed: int = 0) -> List["DataFrame"]:
        """Split rows into len(weights) disjoint frames (Spark's
        randomSplit: weights normalize; assignment is a seeded global
        permutation, so splits are deterministic, disjoint, exhaustive —
        the backbone of CrossValidator/TrainValidationSplit)."""
        if not weights or any(w <= 0 for w in weights):
            raise ValueError(f"weights must be positive, got {weights}")
        table = self.toArrow()
        n = table.num_rows
        perm = np.random.default_rng(seed).permutation(n)
        total = float(sum(weights))
        bounds = np.cumsum([w / total for w in weights])
        out: List["DataFrame"] = []
        start = 0
        for i, b in enumerate(bounds):
            stop = n if i == len(weights) - 1 else int(round(b * n))
            idx = np.sort(perm[start:stop])
            out.append(DataFrame.fromArrow(
                table.take(pa.array(idx, type=pa.int64())),
                numPartitions=max(1, self.numPartitions)))
            start = stop
        return out

    def cache(self) -> "DataFrame":
        self._materialize()
        return self

    # -- multi-host data plane (SURVEY.md §2.4/§2.5) -------------------------

    def processShard(self, process_id: Optional[int] = None,
                     num_processes: Optional[int] = None) -> "DataFrame":
        """This process's round-robin share of the partitions, lazily.

        The transform-side analog of ``streamPartitions(process_id=...)``
        (Spark assigned partitions to executors for exactly this path,
        SURVEY.md §3.1): host ``p`` keeps partitions ``p, p+n, p+2n, …``;
        the op chain (decode, model apply) then only ever runs on the
        local share. Defaults come from the jax process group. Idempotent:
        an already-sharded frame (or any lazy derivative of one) returns
        itself, so chained transformers never double-shard.
        """
        if num_processes is None or process_id is None:
            import jax

            num_processes = (jax.process_count() if num_processes is None
                             else num_processes)
            process_id = (jax.process_index() if process_id is None
                          else process_id)
        if not 0 <= process_id < max(1, num_processes):
            # validate BEFORE the no-op returns: a bad id on a
            # single-process run should fail here, not first on the
            # multi-host deployment
            raise ValueError(
                f"process_id must be in [0, {num_processes}), got "
                f"{process_id}")
        if num_processes <= 1 or self._process_shard is not None:
            return self
        out = DataFrame(self._partitions[process_id::num_processes],
                        self._schema, self._ops)
        if self._materialized is not None:
            out._materialized = self._materialized[process_id::num_processes]
        out._process_shard = (process_id, num_processes)
        return out

    def gatherProcesses(self) -> "DataFrame":
        """Allgather every host's shard into the FULL frame on all hosts.

        The opt-in assembly step after a multi-host transform (per-host
        output stays host-local by default, mirroring multi-host ``fit``):
        each host materializes its local partitions, ships them as Arrow
        IPC bytes through a jax process allgather, and every host
        reassembles the partitions in the ORIGINAL pre-shard order — so
        ``shard-transform-gather`` row order equals the single-process
        transform's. Requires shard provenance: call it on the (possibly
        lazily transformed) frame produced by :meth:`processShard`.
        """
        import jax

        if jax.process_count() <= 1:
            return self
        if self._process_shard is None:
            raise ValueError(
                "gatherProcesses needs shard provenance: call it on the "
                "frame produced by processShard (or a lazy transform of "
                "it) — materializing ops like repartition/union drop it")
        process_id, num_processes = self._process_shard
        if (num_processes != jax.process_count()
                or process_id != jax.process_index()):
            # the allgather below has exactly process_count participants;
            # a shard cut for a different topology would mis-index it
            raise ValueError(
                f"shard provenance (process {process_id} of "
                f"{num_processes}) does not match the live process group "
                f"({jax.process_index()} of {jax.process_count()}); "
                "gatherProcesses only reassembles shards cut for this "
                "group")
        batches = self._materialize()
        payload = _serialize_batches(batches, self._schema)
        from jax.experimental import multihost_utils

        data = np.frombuffer(payload, dtype=np.uint8)
        lengths = multihost_utils.process_allgather(
            np.asarray([len(data)], dtype=np.int64))
        max_len = int(lengths.max())
        padded = np.zeros(max_len, dtype=np.uint8)
        padded[:len(data)] = data
        gathered = multihost_utils.process_allgather(padded)
        per_host = [
            _deserialize_batches(gathered[p, :int(lengths[p])].tobytes())
            for p in range(num_processes)]
        parts, schema = _reinterleave_shards(per_host, self._schema)
        return DataFrame(parts, schema)


class GroupedData:
    """``df.groupBy(cols)`` result: Spark-shaped aggregations lowered onto
    pyarrow's native ``Table.group_by`` (columnar, no Python row loop)."""

    _AGGS = {"sum", "mean", "avg", "min", "max", "count"}

    def __init__(self, df: "DataFrame", cols: List[str]) -> None:
        self._df = df
        self._cols = cols

    def count(self) -> "DataFrame":
        grouped = self._df.toArrow().group_by(self._cols).aggregate(
            [([], "count_all")])
        # Rename by the grouped table's ACTUAL column names — pyarrow's
        # key/aggregate column order has differed across releases and
        # pyproject leaves pyarrow unpinned (ADVICE r4).
        return DataFrame.fromArrow(grouped.rename_columns(
            ["count" if n == "count_all" else n
             for n in grouped.column_names]))

    def agg(self, exprs: Dict[str, str]) -> "DataFrame":
        """``{"column": "sum"|"mean"|"avg"|"min"|"max"|"count"}`` →
        one row per group with ``<agg>(<column>)`` result columns
        (Spark's dict-form ``agg``)."""
        aggs = []
        rename = {}
        for col, fn in exprs.items():
            fn = fn.lower()
            if fn not in self._AGGS:
                raise ValueError(
                    f"Unsupported aggregate {fn!r}; supported: "
                    f"{sorted(self._AGGS)}")
            if col not in self._df.columns:
                raise KeyError(f"No such column: {col!r}")
            arrow_fn = {"avg": "mean"}.get(fn, fn)
            aggs.append((col, arrow_fn))
            rename[f"{col}_{arrow_fn}"] = f"{fn}({col})"
        grouped = self._df.toArrow().group_by(self._cols).aggregate(aggs)
        # Map pyarrow's deterministic result names ("<col>_<fn>") to
        # Spark's "<fn>(<col>)" by NAME, not position (ADVICE r4: older
        # pyarrow put aggregates before keys, silently mislabeling both).
        return DataFrame.fromArrow(grouped.rename_columns(
            [rename.get(n, n) for n in grouped.column_names]))

    def mean(self, *cols: str) -> "DataFrame":
        return self.agg({c: "mean" for c in cols})

    def sum(self, *cols: str) -> "DataFrame":
        return self.agg({c: "sum" for c in cols})


def _freeze_value(v):
    """Row value → hashable key for distinct(): lists/dicts/bytes nest
    arbitrarily in Arrow columns (image structs hold binary data fields)."""
    if isinstance(v, list):
        return tuple(_freeze_value(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze_value(x)) for k, x in v.items()))
    if isinstance(v, (bytes, bytearray)):
        return bytes(v)
    return v


# ---------------------------------------------------------------------------
# Temp views + sql() (the reference's SQL serving entry, SURVEY.md §3.4)
# ---------------------------------------------------------------------------

_temp_views: Dict[str, "DataFrame"] = {}


def table(name: str) -> "DataFrame":
    """The frame registered under ``name`` (createOrReplaceTempView)."""
    try:
        return _temp_views[name]
    except KeyError:
        raise KeyError(
            f"No temp view {name!r}; registered: {sorted(_temp_views)}"
        ) from None


def sql(query: str) -> "DataFrame":
    """``SELECT <exprs> FROM <view> [WHERE <condition>]`` over temp views.

    The reference's serving story was literally
    ``spark.sql("SELECT my_udf(image) FROM images")`` after
    ``registerKerasImageUDF`` (SURVEY.md §3.4) — this makes that exact
    string work: expressions run through ``selectExpr`` (registered
    UDFs, nesting, aliases, literals, ``*``), the optional WHERE through
    :meth:`DataFrame.where`. Lazy like every engine transformation.
    """
    from sparkdl_tpu.engine import sql_expr

    parts = sql_expr.split_query(query)
    frame = table(parts["view"])
    if parts["where"]:
        frame = frame.where(parts["where"])
    return frame.selectExpr(*parts["select"])


# ---------------------------------------------------------------------------
# Multi-host gather helpers
# ---------------------------------------------------------------------------

def _serialize_batches(batches: Sequence[pa.RecordBatch],
                       fallback_schema: pa.Schema) -> bytes:
    """Partition batches → one Arrow IPC stream (batch == partition).

    Batches are cast to their permissively-unified schema first: ops
    without an explicit outputType only learn types at materialization,
    so sibling partitions can disagree (e.g. null vs float list).
    """
    import io

    if batches:
        schema = pa.unify_schemas([b.schema for b in batches],
                                  promote_options="permissive")
        batches = [b.cast(schema) for b in batches]
    else:
        schema = fallback_schema
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, schema) as writer:
        for b in batches:
            writer.write_batch(b)
    return sink.getvalue()


def _deserialize_batches(payload: bytes) -> List[pa.RecordBatch]:
    with pa.ipc.open_stream(payload) as reader:
        return list(reader)


def _reinterleave_shards(per_host: List[List[pa.RecordBatch]],
                         fallback_schema: pa.Schema
                         ) -> Tuple[List[pa.RecordBatch], pa.Schema]:
    """Invert round-robin sharding: global partition ``g`` was computed by
    host ``g % n`` at local position ``g // n``. Host schemas are unified
    permissively (hosts infer types independently)."""
    n = len(per_host)
    all_batches = [b for host in per_host for b in host]
    if not all_batches:
        return [], fallback_schema
    schema = pa.unify_schemas([b.schema for b in all_batches],
                              promote_options="permissive")
    parts: List[pa.RecordBatch] = []
    for g in range(n * max(len(h) for h in per_host)):
        host, pos = g % n, g // n
        if pos < len(per_host[host]):
            parts.append(per_host[host][pos].cast(schema))
    return parts, schema


# ---------------------------------------------------------------------------
# Arrow helpers
# ---------------------------------------------------------------------------

def _schema_with(schema: pa.Schema, name: str, dtype: pa.DataType) -> pa.Schema:
    """Declared schema after with-column: replace in place, append if new
    (must mirror _set_column's positional behavior)."""
    if name in schema.names:
        return pa.schema([pa.field(name, dtype) if f.name == name else f
                          for f in schema])
    return pa.schema(list(schema) + [pa.field(name, dtype)])


def _set_column(batch: pa.RecordBatch, name: str, arr) -> pa.RecordBatch:
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    names = batch.schema.names
    if name in names:
        idx = names.index(name)
        cols = list(batch.columns)
        cols[idx] = arr
        fields = [pa.field(n, cols[i].type) for i, n in enumerate(names)]
        return pa.RecordBatch.from_arrays(cols, schema=pa.schema(fields))
    cols = list(batch.columns) + [arr]
    fields = list(batch.schema) + [pa.field(name, arr.type)]
    return pa.RecordBatch.from_arrays(cols, schema=pa.schema(fields))


def to_arrow_array(values: Any) -> pa.Array:
    """Convert list/numpy to Arrow; N-D numpy → FixedSizeList of flattened rows."""
    if isinstance(values, pa.Array):
        return values
    if isinstance(values, np.ndarray) and values.ndim > 1:
        n = values.shape[0]
        flat = np.ascontiguousarray(values).reshape(n, -1)
        return fixed_size_list_array(flat)
    return pa.array(values)


def fixed_size_list_array(flat2d: np.ndarray) -> pa.FixedSizeListArray:
    """(N, K) numpy → Arrow FixedSizeList<item: dtype>[K], zero-copy values."""
    n, k = flat2d.shape
    values = pa.array(np.ascontiguousarray(flat2d).reshape(-1))
    return pa.FixedSizeListArray.from_arrays(values, k)


def column_to_numpy(arr, dtype=None) -> np.ndarray:
    """Arrow column (numeric / [FixedSize]List thereof) → numpy (N, ...) array."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_fixed_size_list(arr.type):
        k = arr.type.list_size
        # flatten() (not .values): respects slice offsets — partition
        # batches are table slices, where .values spans the whole buffer.
        values = arr.flatten().to_numpy(zero_copy_only=False)
        out = values.reshape(len(arr), k)
    elif pa.types.is_list(arr.type) or pa.types.is_large_list(arr.type):
        # sparkdl: allow(columnar-hot-path): generic-list fallback for
        # ragged rows; uniform vector columns take list_column_to_numpy
        rows = arr.to_pylist()
        out = np.asarray(rows)
    else:
        out = arr.to_numpy(zero_copy_only=False)
    if dtype is not None:
        out = np.asarray(out, dtype=dtype)
    return out


def list_column_to_numpy(arr, element_nulls: str = "reject"
                         ) -> Optional[np.ndarray]:
    """Uniform-width list column → (n_valid, K) float64 matrix, no per-row
    Python (docs/PERF.md "Columnar data plane"): null ROWS drop via one
    vectorized filter, the element buffer flattens through numpy once.
    Returns None when the column is not list-typed, rows are ragged, or —
    under ``element_nulls="reject"`` — elements are null; callers fall
    back to their per-row path, so semantics for irregular data are
    unchanged. ``element_nulls="nan"`` maps null elements to NaN instead
    (the Imputer's missing-value convention)."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    fixed = pa.types.is_fixed_size_list(arr.type)
    if not (fixed or pa.types.is_list(arr.type)
            or pa.types.is_large_list(arr.type)):
        return None
    if arr.null_count:
        arr = arr.drop_null()
    n = len(arr)
    if fixed:
        width = arr.type.list_size
    else:
        offsets = arr.offsets.to_numpy()
        widths = np.diff(offsets)
        if widths.size and not (widths == widths[0]).all():
            return None  # ragged vectors — per-row path validates/raises
        width = int(widths[0]) if widths.size else 0
    flat = arr.flatten()  # respects slice offsets and dropped rows
    if flat.null_count and element_nulls != "nan":
        return None
    values = flat.to_numpy(zero_copy_only=False)  # nulls → NaN (float64)
    return np.asarray(values, np.float64).reshape(n, width)
