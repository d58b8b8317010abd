"""Unified telemetry: cross-thread span tracing, metrics, one run report.

The reference library shipped no in-tree observability — operators
hand-instrumented the Spark UI and TF timelines (SURVEY.md §5.1). The
rebuild had fragments: global phase accumulators (`core/profiling.py`),
resilience counters (`core/health.py`), train metrics
(`train/metrics.py`) — none sharing identifiers, none exportable
together. With the data plane spanning four concurrent execution
contexts (driver, supervisor pool threads, the `DevicePrefetcher`
staging thread, the deferred-sync train loop), "where did step 412's
batch spend its time, and which partition task stalled it?" needs
correlated per-span records, not aggregate totals. This module is the
Dapper-style span model plus the Prometheus metric taxonomy for exactly
that, in three integrated parts:

1. **Span tracing** — a :class:`Tracer` producing per-span records
   (name, trace_id, span_id, parent_id, thread, start/end ns,
   attributes) into a bounded ring buffer, with explicit cross-thread
   context handoff (:func:`current_context` on the parent thread,
   ``span(parent=ctx)`` or :func:`attach` on the child) so engine
   partition tasks, prefetcher staging and `Trainer.fit` steps all
   parent correctly under one run trace. Exportable as Chrome-trace
   JSON (``chrome://tracing`` / Perfetto, one track per thread) with no
   ``jax.profiler`` dependency.
2. **Metrics registry** — named :class:`Counter` / :class:`Gauge` /
   :class:`Histogram` instruments (fixed log-scale buckets with
   p50/p95/p99 estimates), with a JSON :meth:`MetricsRegistry.snapshot`
   and a Prometheus text-exposition dump.
3. **Run report** — :class:`RunReport` merges the trace summary, the
   metric snapshot, ``profiling.phase_stats()``/``overlap_stats()`` and
   the active ``HealthMonitor`` report into one JSON artifact written
   at scope exit (opt-in via ``SPARKDL_TELEMETRY_DIR`` or an explicit
   ``Telemetry(out_dir=...)`` scope), plus a structured-logging adapter
   stamping ``run_id``/``trace_id`` onto framework log records.
4. **Live plane** (docs/OBSERVABILITY.md "Live metrics & SLOs") — every
   instrument a scope creates additionally feeds a fixed-size ring of
   time-bucketed sub-snapshots (monotonic-clock rotation, O(1) record
   path), so :meth:`MetricsRegistry.window_snapshot` answers "rate and
   p50/p95/p99 over the last N seconds" alongside the cumulative views
   — a 10-minute-old latency spike no longer pollutes "current" p99.
   A :class:`SnapshotExporter` daemon thread inside the scope writes a
   JSON-lines snapshot (windowed + cumulative + executor queue/breaker
   state) and an atomically-replaced Prometheus text file every
   ``export_interval_s``, evaluates the ``core.slo`` watchdog rules on
   each tick, and flushes one final snapshot at scope exit; the run
   report gains a ``timeline`` summary derived from the snapshots.

Scoping mirrors :class:`~sparkdl_tpu.core.health.HealthMonitor`:
a :class:`Telemetry` scope activates process-wide (engine partition ops
run on pool threads where a ContextVar entered on the driver would be
invisible), nests, and restores the previous scope on exit. With no
active scope every entry point — :func:`span`, :func:`count`,
:func:`gauge_set`, :func:`observe` — is a single global read + ``None``
check returning a shared singleton: the hot paths allocate nothing and
never touch a device (telemetry must never introduce a device sync; the
step-loop AST lint in ``tests/test_taxonomy_lint.py`` stays satisfied).

Dependency-free by design (stdlib only): every layer may import it
without cycles. ``core.profiling`` imports this module; the run report
imports ``profiling``/``health`` lazily to break the cycle.
"""

from __future__ import annotations

import bisect
import itertools
import json
import logging
import math
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

TELEMETRY_DIR_ENV = "SPARKDL_TELEMETRY_DIR"
# Opt-in periodic exporter cadence (seconds) for scopes that don't pass
# export_interval_s explicitly; requires TELEMETRY_DIR for file output.
EXPORT_INTERVAL_ENV = "SPARKDL_TELEMETRY_EXPORT_S"

# The window rings and the exporter read THIS clock (monotonic by
# default) so tests can drive rotation/cadence deterministically with a
# fake clock. The span hot path keeps calling perf_counter_ns directly.
_monotonic = time.monotonic

# ---------------------------------------------------------------------------
# Canonical names (docs/OBSERVABILITY.md is the human-readable catalog).
# The taxonomy lint (tests/test_taxonomy_lint.py) checks every annotate()/
# span() name used in sparkdl_tpu/ against CANONICAL_SPAN_NAMES — a typo'd
# phase name would otherwise silently fork a timer.
# ---------------------------------------------------------------------------

SPAN_RUN = "sparkdl.run"                      # telemetry scope root
SPAN_RUNNER_ATTEMPT = "sparkdl.runner_attempt"  # TPURunner gang attempt
SPAN_FIT = "sparkdl.fit"                      # one Trainer.fit call
SPAN_EPOCH = "sparkdl.epoch"                  # one epoch of the fit loop
SPAN_CHECKPOINT_SAVE = "sparkdl.checkpoint_save"
SPAN_ESTIMATOR_FIT = "sparkdl.estimator_fit"  # KerasImageFileEstimator._fit
SPAN_COLLECT = "sparkdl.collect"              # estimator collected decode
SPAN_MATERIALIZE = "sparkdl.materialize"      # DataFrame._materialize barrier
SPAN_TASK = "sparkdl.task"                    # one pool attempt (or hedge)
SPAN_TASK_ATTEMPT = "sparkdl.task_attempt"    # one retry-loop attempt
SPAN_COMPILE = "sparkdl.compile"              # first launch of a compiled
                                              # program: a new shape of
                                              # ModelFunction.jitted, the
                                              # trainer's step and eval
                                              # programs
                                              # (profiling.compile_span)
SPAN_MODEL_BUILD = "sparkdl.model_build"      # making a model ready to
                                              # launch: weights resolved,
                                              # folded and cast, the apply
                                              # function or the trainer's
                                              # state built
                                              # (profiling.model_build)
SPAN_COALESCED_LAUNCH = "sparkdl.coalesced_launch"  # core/executor.py
SPAN_DECODE_POOL = "sparkdl.decode_pool"      # one pooled decode fan-out
                                              # (core/decode_pool.py)
SPAN_MODEL_LOAD = "sparkdl.model_load"        # serving cold start: loader
                                              # run on a residency miss
                                              # (serving/residency.py)
SPAN_CLUSTER_DISPATCH = "sparkdl.cluster_dispatch"  # one partition's
                                              # round trip to a cluster
                                              # worker (cluster/router.py)
SPAN_CLUSTER_TASK = "sparkdl.cluster_task"    # worker-side execution of
                                              # one dispatched partition
                                              # (cluster/worker.py)
SPAN_DECODE_CHUNK = "sparkdl.decode_chunk"    # one chunk decoded inside
                                              # a pool worker process
                                              # (core/decode_pool.py)
SPAN_SERVING_SHADOW = "sparkdl.serving_shadow"  # shadow-lane replay of
                                              # one serving request
                                              # (serving/server.py)
SPAN_SERVING_PREDICT = "sparkdl.serving_predict"  # worker-side execution
                                              # of one cluster-routed
                                              # predict (serving/cluster.py)
SPAN_SERVING_WARMUP = "sparkdl.serving.warmup_s"  # AOT bucket-ladder
                                              # warmup of one deployment
                                              # (serving/registry.py)
# Where a featurize pass spends its host time (docs/OBSERVABILITY.md):
# all opened through profiling.annotate, so each also feeds a phase timer.
SPAN_ROW_ASSEMBLY = "sparkdl.row_assembly"    # Arrow table → Python rows /
                                              # pandas (engine/dataframe.py)
SPAN_QUEUE_WAIT = "sparkdl.queue_wait"        # a queued request waiting for
                                              # the coalescer's answer
                                              # (core/executor.py _await)
SPAN_LAUNCH = "sparkdl.launch"                # host side of one launch:
                                              # argument hand-over + async
                                              # dispatch (core/batching.py,
                                              # core/executor.py)
SPAN_FETCH = "sparkdl.fetch"                  # device→host copy of ready
                                              # outputs (core/batching.py)

CANONICAL_SPAN_NAMES = frozenset({
    SPAN_RUN, SPAN_RUNNER_ATTEMPT, SPAN_FIT, SPAN_EPOCH,
    SPAN_CHECKPOINT_SAVE, SPAN_ESTIMATOR_FIT, SPAN_COLLECT,
    SPAN_MATERIALIZE, SPAN_TASK, SPAN_TASK_ATTEMPT,
    SPAN_COMPILE, SPAN_MODEL_BUILD, SPAN_COALESCED_LAUNCH, SPAN_DECODE_POOL,
    SPAN_MODEL_LOAD, SPAN_CLUSTER_DISPATCH, SPAN_CLUSTER_TASK,
    SPAN_DECODE_CHUNK, SPAN_SERVING_SHADOW, SPAN_SERVING_PREDICT,
    SPAN_SERVING_WARMUP,
    SPAN_ROW_ASSEMBLY, SPAN_QUEUE_WAIT, SPAN_LAUNCH, SPAN_FETCH,
    # phase names (core/profiling.py constants + literal call sites)
    "sparkdl.decode", "sparkdl.stage", "sparkdl.stage_batch",
    "sparkdl.host_stage", "sparkdl.host_resize", "sparkdl.host_wait",
    "sparkdl.device_apply", "sparkdl.train_step", "sparkdl.device_sync",
})

# Metric catalog. Histograms in seconds use DEFAULT_TIME_BOUNDS; row-count
# histograms use POW2_BOUNDS. Health-event mirrors are dynamic:
# "sparkdl.health.<event>" per core/health.py event name, bumped in
# health.record so telemetry counters equal HealthMonitor counts exactly.
M_TASK_DURATION_S = "sparkdl.task.duration_s"          # histogram
M_STEP_TIME_S = "sparkdl.train.step_time_s"            # histogram (host)
M_STEPS_PER_SEC = "sparkdl.train.steps_per_sec"        # histogram
M_EXAMPLES_PER_SEC = "sparkdl.train.examples_per_sec"  # gauge
M_PREFETCH_DEPTH = "sparkdl.prefetch.queue_depth"      # gauge
M_PREFETCH_STALL_S = "sparkdl.prefetch.stall_s"        # histogram
M_BATCH_ROWS = "sparkdl.batching.rows"                 # counter (valid rows)
M_BATCH_PAD_ROWS = "sparkdl.batching.pad_rows"         # counter (pad rows)
M_BATCH_BUCKET_ROWS = "sparkdl.batching.bucket_rows"   # histogram
M_PADDING_WASTE = "sparkdl.batching.padding_waste"     # gauge (pad fraction)
# Telemetry-tuned bucket ladder (core/batching.BucketPlanner, docs/PERF.md
# "Launch shaping & precision"): one counter bump per adopted ladder, and
# the planner's predicted pad fraction under the ladder it just adopted
# (the per-model padding waste AFTER tuning; the update counter's pace is
# bounded by the planner's hysteresis).
M_BUCKET_LADDER_UPDATE = "sparkdl.batching.bucket_ladder_update"  # counter
M_PLANNER_WASTE = "sparkdl.batching.planner_waste"     # gauge (pad fraction)
M_ENGINE_ROWS_OUT = "sparkdl.engine.rows_out"          # counter
M_ENGINE_BYTES_OUT = "sparkdl.engine.bytes_out"        # counter
# Leaf values collect() turned into Python numbers through numpy instead of
# an Arrow scalar each (engine/dataframe.py _append_rows): rows × width of a
# featurizer's output column; 0 for a frame with no vector column.
M_COLLECT_VECTORIZED_VALUES = "sparkdl.collect.vectorized_values"  # counter
# Device execution service (core/executor.py, docs/PERF.md coalescing):
M_COALESCE_REQUESTS = "sparkdl.executor.coalesce_requests"  # histogram
M_COALESCE_ROWS = "sparkdl.executor.coalesce_rows"     # histogram
M_COALESCE_DEDUP = "sparkdl.executor.dedup_hits"       # counter (hedges)
M_QUEUE_WAIT_S = "sparkdl.executor.queue_wait_s"       # histogram
M_LAUNCH_S = "sparkdl.executor.launch_s"               # histogram (host)
M_EXECUTOR_OCCUPANCY = "sparkdl.executor.occupancy"    # gauge (in-flight)
# Overload protection (ISSUE 6): the shed/deadline/breaker COUNTS arrive
# for free as sparkdl.health.* mirrors of the core/health.py events; the
# gauges below are the executor's own instantaneous state.
M_EXECUTOR_QUEUE_DEPTH = "sparkdl.executor.queue_depth"  # gauge (queued reqs)
M_EXECUTOR_SHED_RATE = "sparkdl.executor.shed_rate"    # gauge (shed fraction)
# Columnar data plane (docs/PERF.md "Columnar data plane"): bytes handed
# to the executor per execute() call, as staged on the host. On the
# columnar path this is raw uint8 pixels — the counter is how bench and
# tests assert "host ships uint8 only" (a f32 regression quadruples it).
M_STAGED_BYTES = "sparkdl.executor.staged_bytes"       # counter
# ...and the way back: bytes every sparkdl.fetch span copied device→host
# (pad rows of a multi-bucket call included — they cross the link too).
M_FETCHED_BYTES = "sparkdl.executor.fetched_bytes"     # counter
# Parallel host decode pool (core/decode_pool.py, docs/PERF.md "Parallel
# host ingest"):
M_DECODE_POOL_DEPTH = "sparkdl.decode_pool.queue_depth"    # gauge (chunks)
M_DECODE_POOL_BUSY = "sparkdl.decode_pool.workers_busy"    # gauge
M_DECODE_POOL_DECODE_S = "sparkdl.decode_pool.decode_s"    # histogram
                                                           # (per blob)
# Online serving plane (sparkdl_tpu/serving/, docs/SERVING.md): row-level
# request path over the executor choke point. Per-model latency
# histograms are declared dynamically at deploy time as
# "sparkdl.serving.request_s.<model>" via declare_metric().
M_SERVING_REQUEST_S = "sparkdl.serving.request_s"      # histogram (e2e)
M_SERVING_QUEUE_DEPTH = "sparkdl.serving.queue_depth"  # gauge (in-flight
                                                       # predict calls)
M_SERVING_SHADOW_DIVERGENCE = "sparkdl.serving.shadow_divergence"
                                                       # histogram (max
                                                       # |active-shadow|)
M_SERVING_EVICTIONS = "sparkdl.serving.evictions"      # counter
# Cluster serving plane (serving/cluster.py, docs/SERVING.md "Cluster
# serving"): replicated deployments across cluster workers. The
# failover counter is the router's own canonical series (the
# serving_failover health mirror carries the same count — the merged
# report cross-checks them); the replicas gauge tracks the live replica
# set of the deployment most recently routed.
M_SERVING_FAILOVER = "sparkdl.serving.failover"        # counter (moved
                                                       # in-flight
                                                       # requests)
M_SERVING_REPLICAS = "sparkdl.serving.replicas"        # gauge (live
                                                       # replicas of the
                                                       # last-routed
                                                       # deployment)
# Cluster inference plane (sparkdl_tpu/cluster/, docs/DISTRIBUTED.md
# "Cluster inference"): the router's load/latency view. Worker-loss and
# re-dispatch COUNTS also arrive as sparkdl.health.* mirrors; the
# redispatch counter below is the router's own canonical series.
M_CLUSTER_OUTSTANDING_ROWS = "sparkdl.cluster.outstanding_rows"  # gauge
                                                       # (rows in flight
                                                       # across workers)
M_CLUSTER_DISPATCH_S = "sparkdl.cluster.dispatch_s"    # histogram (per
                                                       # partition round
                                                       # trip)
M_CLUSTER_REDISPATCH = "sparkdl.cluster.redispatch"    # counter
# Elastic capacity (autoscaler + graceful drain, docs/DISTRIBUTED.md
# "Elastic capacity"): the live worker-set size and how long a drain
# takes from preemption notice / scale-down order to clean exit.
M_CLUSTER_WORKERS = "sparkdl.cluster.workers"          # gauge (live,
                                                       # non-draining)
M_CLUSTER_DRAIN_S = "sparkdl.cluster.drain_s"          # histogram
# Counts that come OUT of a compiled program (the sequence models): the
# program returns them as small row-aligned outputs under the reserved
# output name PROGRAM_COUNTS = {metric name: array, dim 0 = rows}, and the
# executor's choke point records them once the outputs are on the host
# (take_program_counts) — an integer array adds its sum to the counter, a
# float array feeds every value to the histogram. Pad rows are cut off
# before, like every other output's.
PROGRAM_COUNTS = "sparkdl.program_counts"
M_SEQUENCE_TOKENS = "sparkdl.sequence.tokens"          # counter (tokens of
                                                       # the rows scored)
# counter (per row: the layers whose attention was lowered to the fused
# kernel, models/attention.py fused_causal_attention)
M_SEQUENCE_FUSED_ATTENTION_LAYERS = "sparkdl.sequence.fused_attention_layers"
# counter (per row: 1 where the scorer's head was lowered to the fused kernel,
# models/scoring_head.py fused_scoring_head — no logit in HBM)
M_SEQUENCE_FUSED_HEAD_WINDOWS = "sparkdl.sequence.fused_head_windows"
# counter (per row: the layers whose mixer was the gated short convolution)
M_SEQUENCE_CONV_LAYERS = "sparkdl.sequence.conv_layers"
# counter (per row: the layers whose attention was lowered with a span — a
# query reads its last keys only; from a stack that names its layers' kinds)
M_SEQUENCE_WINDOW_ATTENTION_LAYERS = "sparkdl.sequence.window_attention_layers"
# counter (per row, summed over the attention layers and one head's queries:
# the keys whose scores the lowered path computes, masked ones inside a
# visited tile included; beside the former)
M_SEQUENCE_SCORED_KEYS = "sparkdl.sequence.scored_keys"
# counter (per row: the layers whose mixer was the state-space one,
# models/state_space.py; from a stack that has such layers)
M_SEQUENCE_SSM_LAYERS = "sparkdl.sequence.ssm_layers"
# counter (per row: the state-space layers whose recurrence was lowered to
# the kernel, models/state_space.py fused_selective_scan; beside the former)
M_SEQUENCE_FUSED_SCAN_LAYERS = "sparkdl.sequence.fused_scan_layers"
# counter (per row: the attention layers whose heads' outputs a sigmoid gate
# scaled, models/shortconv_moe.py; from a stack whose layers hold one)
M_SEQUENCE_GATED_ATTENTION_LAYERS = "sparkdl.sequence.gated_attention_layers"
# counter (per row: the layers whose mixer was linear attention with the
# gated delta rule, models/linear_attention.py; from a stack that has such
# layers)
M_SEQUENCE_LINEAR_ATTENTION_LAYERS = "sparkdl.sequence.linear_attention_layers"
# counter (per row: the linear-attention layers whose chunked form was
# lowered to the kernel, models/linear_attention.py fused_delta_rule; beside
# the former)
M_SEQUENCE_FUSED_LINEAR_ATTENTION_LAYERS = \
    "sparkdl.sequence.fused_linear_attention_layers"
M_MOE_ROUTED_TOKENS = "sparkdl.moe.routed_tokens"      # counter (tokens
                                                       # routed, once for each
                                                       # expert layer)
M_MOE_LOCAL_PAIRS = "sparkdl.moe.local_pairs"          # counter ((token,
                                                       # expert) pairs routed
                                                       # to experts held here)
M_MOE_OVERFLOW_PAIRS = "sparkdl.moe.overflow_pairs"    # counter (pairs beyond
                                                       # the grouped products'
                                                       # buffer: computed in a
                                                       # further round)
M_MOE_BUFFER_ROWS = "sparkdl.moe.buffer_rows"          # counter (per row its
                                                       # share of the rows the
                                                       # grouped products ran:
                                                       # rounds × the buffer,
                                                       # per expert layer)
# counter (per row: the expert layers whose grouped products were lowered to
# the kernel, models/experts.py grouped_product)
M_MOE_FUSED_PRODUCT_LAYERS = "sparkdl.moe.fused_product_layers"
# counter (per row: the expert layers whose combine was lowered to the
# kernel, models/experts.py fused_combine)
M_MOE_FUSED_COMBINE_LAYERS = "sparkdl.moe.fused_combine_layers"
# counters (per row its share, summed over the expert layers: the grouped
# kernel's visits by its grid's rule — a row tile once for each group it
# holds a row of — and the buffer's row tiles, rounds × the buffer; counted
# on either path, models/experts.py product_visits)
M_MOE_PRODUCT_VISITS = "sparkdl.moe.product_visits"
M_MOE_PRODUCT_ROW_TILES = "sparkdl.moe.product_row_tiles"
# counter (per row: the expert layers with a shared expert beside the routed
# ones, models/shortconv_moe.py; from a stack whose layers hold one)
M_MOE_SHARED_EXPERT_LAYERS = "sparkdl.moe.shared_expert_layers"
# counter (per row: the expert layers whose router chose within groups,
# models/experts.py route; from a stack whose expert layers do)
M_MOE_GROUP_LIMITED_LAYERS = "sparkdl.moe.group_limited_layers"
M_MOE_LOAD_MAX_OVER_MEAN = "sparkdl.moe.load_max_over_mean"  # histogram (per
                                                       # row and expert layer:
                                                       # its launch's fullest
                                                       # held expert's pairs
                                                       # over the mean)
# The start-up record (core/profiling.py ``startup_stats``), mirrored into
# every scope as gauges "sparkdl.startup.<key>" — when the scope opens and
# whenever the record grows — so a scope opened after the model was built
# and compiled still shows what set-up took. Seconds, but for the three
# counts (compile-cache hits and misses, ``sparkdl.compile`` spans closed).
STARTUP_METRIC_PREFIX = "sparkdl.startup."
STARTUP_KEYS = ("import_s", "model_build_s", "trace_lower_s",
                "backend_compile_s", "cache_retrieval_s", "cache_hits",
                "cache_misses", "first_launch_s", "compile_spans")
# Per-tenant fair queueing (core/executor.py, docs/RESILIENCE.md): each
# tenant's queue-wait histogram gets a per-tenant NAME (metrics carry no
# labels), declared dynamically as "sparkdl.executor.queue_wait_s.<tenant>"
# via tenant_queue_wait_metric() + declare_metric().
HEALTH_METRIC_PREFIX = "sparkdl.health."

# Instrument kind per canonical metric — machine-readable so core/slo.py
# can reject a rule whose stat can never be observed on its metric (a
# p99 of a counter would silently watch nothing).
CANONICAL_METRIC_KINDS: Dict[str, str] = {
    M_TASK_DURATION_S: "histogram",
    M_STEP_TIME_S: "histogram",
    M_STEPS_PER_SEC: "histogram",
    M_EXAMPLES_PER_SEC: "gauge",
    M_PREFETCH_DEPTH: "gauge",
    M_PREFETCH_STALL_S: "histogram",
    M_BATCH_ROWS: "counter",
    M_BATCH_PAD_ROWS: "counter",
    M_BATCH_BUCKET_ROWS: "histogram",
    M_PADDING_WASTE: "gauge",
    M_BUCKET_LADDER_UPDATE: "counter",
    M_PLANNER_WASTE: "gauge",
    M_ENGINE_ROWS_OUT: "counter",
    M_ENGINE_BYTES_OUT: "counter",
    M_COLLECT_VECTORIZED_VALUES: "counter",
    M_COALESCE_REQUESTS: "histogram",
    M_COALESCE_ROWS: "histogram",
    M_COALESCE_DEDUP: "counter",
    M_QUEUE_WAIT_S: "histogram",
    M_LAUNCH_S: "histogram",
    M_EXECUTOR_OCCUPANCY: "gauge",
    M_EXECUTOR_QUEUE_DEPTH: "gauge",
    M_EXECUTOR_SHED_RATE: "gauge",
    M_STAGED_BYTES: "counter",
    M_FETCHED_BYTES: "counter",
    M_DECODE_POOL_DEPTH: "gauge",
    M_DECODE_POOL_BUSY: "gauge",
    M_DECODE_POOL_DECODE_S: "histogram",
    M_SERVING_REQUEST_S: "histogram",
    M_SERVING_QUEUE_DEPTH: "gauge",
    M_SERVING_SHADOW_DIVERGENCE: "histogram",
    M_SERVING_EVICTIONS: "counter",
    M_SERVING_FAILOVER: "counter",
    M_SERVING_REPLICAS: "gauge",
    M_CLUSTER_OUTSTANDING_ROWS: "gauge",
    M_CLUSTER_DISPATCH_S: "histogram",
    M_CLUSTER_REDISPATCH: "counter",
    M_CLUSTER_WORKERS: "gauge",
    M_CLUSTER_DRAIN_S: "histogram",
    M_SEQUENCE_TOKENS: "counter",
    M_SEQUENCE_FUSED_ATTENTION_LAYERS: "counter",
    M_SEQUENCE_FUSED_HEAD_WINDOWS: "counter",
    M_SEQUENCE_CONV_LAYERS: "counter",
    M_SEQUENCE_WINDOW_ATTENTION_LAYERS: "counter",
    M_SEQUENCE_SCORED_KEYS: "counter",
    M_SEQUENCE_SSM_LAYERS: "counter",
    M_SEQUENCE_FUSED_SCAN_LAYERS: "counter",
    M_SEQUENCE_GATED_ATTENTION_LAYERS: "counter",
    M_SEQUENCE_LINEAR_ATTENTION_LAYERS: "counter",
    M_SEQUENCE_FUSED_LINEAR_ATTENTION_LAYERS: "counter",
    M_MOE_ROUTED_TOKENS: "counter",
    M_MOE_LOCAL_PAIRS: "counter",
    M_MOE_OVERFLOW_PAIRS: "counter",
    M_MOE_BUFFER_ROWS: "counter",
    M_MOE_FUSED_PRODUCT_LAYERS: "counter",
    M_MOE_FUSED_COMBINE_LAYERS: "counter",
    M_MOE_PRODUCT_VISITS: "counter",
    M_MOE_PRODUCT_ROW_TILES: "counter",
    M_MOE_SHARED_EXPERT_LAYERS: "counter",
    M_MOE_GROUP_LIMITED_LAYERS: "counter",
    M_MOE_LOAD_MAX_OVER_MEAN: "histogram",
    **{STARTUP_METRIC_PREFIX + key: "gauge" for key in STARTUP_KEYS},
}

CANONICAL_METRIC_NAMES = frozenset(CANONICAL_METRIC_KINDS)

_declare_lock = threading.Lock()


def declare_metric(name: str, kind: str) -> str:
    """Declare a DYNAMIC metric name (e.g. the per-model serving latency
    histogram ``sparkdl.serving.request_s.<model>``) into the catalog so
    ``core.slo.SLORule`` construction accepts it. Static call sites must
    use the ``M_*`` constants — this is for names that only exist at
    runtime (model deployments). Idempotent; re-declaring with a
    DIFFERENT kind raises (two writers disagreeing on the instrument
    would corrupt every rule watching it). Returns ``name``."""
    if kind not in ("histogram", "counter", "gauge"):
        raise ValueError(
            f"declare_metric kind must be 'histogram', 'counter' or "
            f"'gauge', got {kind!r}")
    global CANONICAL_METRIC_NAMES
    with _declare_lock:
        have = CANONICAL_METRIC_KINDS.get(name)
        if have is not None and have != kind:
            raise ValueError(
                f"metric {name!r} already declared as {have!r}, cannot "
                f"re-declare as {kind!r}")
        if have is None:
            CANONICAL_METRIC_KINDS[name] = kind
            CANONICAL_METRIC_NAMES = frozenset(CANONICAL_METRIC_KINDS)
    return name


def serving_request_metric(model: str) -> str:
    """The per-model serving latency histogram name. Metrics carry no
    labels, so per-model p99 objectives get per-model NAMES — declared
    at deploy time (``declare_metric``), observed by the ModelServer
    beside the aggregate ``M_SERVING_REQUEST_S``."""
    return M_SERVING_REQUEST_S + "." + model


def tenant_queue_wait_metric(tenant: str) -> str:
    """The per-tenant queue-wait histogram name. Like the per-model
    serving latency, per-tenant fairness objectives get per-tenant NAMES
    — declared on first use (``declare_metric``) by the executor's
    coalescer, observed beside the aggregate ``M_QUEUE_WAIT_S`` so a
    flooding tenant's self-inflicted wait is distinguishable from the
    wait it imposes on everyone else."""
    return M_QUEUE_WAIT_S + "." + tenant

# ---------------------------------------------------------------------------
# Span tracing
# ---------------------------------------------------------------------------


class SpanContext(NamedTuple):
    """The cross-thread handoff token: enough to parent a remote span."""

    trace_id: str
    span_id: int


class _RootSentinel:
    """``Tracer.span(parent=ROOT)``: force a parentless root span (vs
    ``parent=None``, which adopts the ambient context)."""


ROOT = _RootSentinel()


_tls = threading.local()


def _span_stack() -> List["_Span"]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


class _NullSpan:
    """Shared no-op span: the inactive path returns THIS singleton —
    zero allocation, inert context manager."""

    __slots__ = ()
    context: Optional[SpanContext] = None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set_attribute(self, key: str, value: Any) -> None:
        pass


NULL_SPAN = _NullSpan()


class _Span:
    """An open span; records into its tracer's ring buffer on exit."""

    __slots__ = ("_tracer", "name", "trace_id", "span_id", "parent_id",
                 "attributes", "_start_ns", "_pushed")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 span_id: int, parent_id: Optional[int],
                 attributes: Dict[str, Any]) -> None:
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.attributes = attributes
        self._start_ns = 0
        self._pushed = False

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def __enter__(self) -> "_Span":
        _span_stack().append(self)
        self._pushed = True
        self._start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        end_ns = time.perf_counter_ns()
        if self._pushed:
            stack = _span_stack()
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:  # defensive: exited out of order
                stack.remove(self)
            self._pushed = False
        if exc_type is not None:
            self.attributes.setdefault("error", exc_type.__name__)
        self._tracer._record(self, self._start_ns, end_ns)
        return False


class Tracer:
    """Per-run span recorder: bounded ring buffer + Chrome-trace export.

    The ring keeps the most recent ``max_spans`` finished spans (the
    HealthMonitor event log keeps the FIRST n — traces want the tail: the
    end of a run is where failures live) and counts evictions in
    :attr:`dropped`. Thread-safe; spans may finish on any thread.
    Recorded times are nanoseconds since :attr:`epoch_ns`.
    """

    def __init__(self, trace_id: str, max_spans: int = 65536) -> None:
        self.trace_id = trace_id
        self.max_spans = max_spans
        self.dropped = 0
        self.remote_adopted = 0
        self.remote_rejected = 0
        self._lock = threading.Lock()
        self._spans: "deque[Dict[str, Any]]" = deque(maxlen=max_spans)
        # span ids are pid-salted: a cluster/decode worker's spans merge
        # into the coordinator's ring, so ids allocated independently in
        # each process must never collide (Linux pids fit in 22 bits;
        # 40 low bits leave ~10^12 spans per process)
        self._ids = itertools.count((os.getpid() << 40) | 1)
        #: the tracer's epoch, a reading of ``time.perf_counter_ns``:
        #: ``spans()`` times are relative to it, so ``start_ns + epoch_ns``
        #: is the host clock — what lines a telemetry trace up with any
        #: other record of the same run (``profiling.maybe_trace``)
        self.epoch_ns = time.perf_counter_ns()

    # -- producing -----------------------------------------------------------

    def span(self, name: str, parent: Any = None,
             **attributes: Any) -> _Span:
        """An open span context manager. ``parent`` explicitly parents a
        cross-thread span (pass the creating thread's
        :func:`current_context`); otherwise the ambient context — this
        thread's innermost open span, its attached base, or the scope
        root — is the parent. ``parent=ROOT`` makes a parentless root
        span (the scope's own run span)."""
        if parent is ROOT:
            trace_id, parent_id = self.trace_id, None
        else:
            if parent is None:
                parent = current_context()
            if parent is None:
                trace_id, parent_id = self.trace_id, None
            else:
                trace_id, parent_id = parent.trace_id, parent.span_id
        return _Span(self, name, trace_id, next(self._ids), parent_id,
                     attributes)

    def _record(self, span: _Span, start_ns: int, end_ns: int) -> None:
        thread = threading.current_thread()
        rec = {
            "name": span.name,
            "trace_id": span.trace_id,
            "span_id": span.span_id,
            "parent_id": span.parent_id,
            "thread_id": thread.ident,
            "thread_name": thread.name,
            "start_ns": start_ns - self.epoch_ns,
            "end_ns": end_ns - self.epoch_ns,
        }
        if span.attributes:
            rec["attributes"] = span.attributes
        with self._lock:
            if len(self._spans) == self.max_spans:
                self.dropped += 1
            self._spans.append(rec)

    # -- querying / export ---------------------------------------------------

    def spans(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        with self._lock:
            out = list(self._spans)
        if name is not None:
            out = [s for s in out if s["name"] == name]
        return out

    def summary(self) -> Dict[str, Any]:
        """Aggregate per-name stats over ONE snapshot of the ring (the
        count and the aggregates must agree even while other threads
        keep recording)."""
        spans = self.spans()
        by_name: Dict[str, Dict[str, Any]] = {}
        threads = set()
        for s in spans:
            threads.add((s["thread_id"], s["thread_name"]))
            agg = by_name.setdefault(
                s["name"], {"count": 0, "total_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += (s["end_ns"] - s["start_ns"]) / 1e9
        for agg in by_name.values():
            agg["total_s"] = round(agg["total_s"], 6)
            agg["mean_s"] = round(agg["total_s"] / agg["count"], 6)
        return {
            "trace_id": self.trace_id,
            "epoch_ns": self.epoch_ns,
            "spans_recorded": len(spans),
            "spans_dropped": self.dropped,
            "remote_adopted": self.remote_adopted,
            "remote_rejected": self.remote_rejected,
            "threads": sorted(t[1] for t in threads),
            "by_name": {k: by_name[k] for k in sorted(by_name)},
        }

    # -- cross-process merge (docs/OBSERVABILITY.md "Distributed
    # tracing"): a worker ships its ring rebased onto the parent's
    # clock; the parent adopts it into ONE merged trace -------------------

    def export_ring(self, *, clock_offset_ns: int = 0,
                    process: Optional[str] = None,
                    parent_remap: Optional[Dict[int, int]] = None,
                    limit: int = 4096) -> Dict[str, Any]:
        """The shippable view of this ring: every span rebased to the
        PARENT's monotonic clock (``abs_ns = rel + t0 + offset``, offset
        from the worker handshake) and stamped with this process's pid
        and ``process`` track label. ``parent_remap`` rewrites parent
        ids — the worker's still-open ``sparkdl.run`` root never ships,
        so spans under it re-parent onto the coordinator's root instead
        of dangling. Keeps the most recent ``limit`` spans; truncation
        adds to the shipped ``dropped`` count (never silent)."""
        spans = self.spans()
        shipped_dropped = self.dropped
        if len(spans) > limit:
            shipped_dropped += len(spans) - limit
            spans = spans[-limit:]
        pid = os.getpid()
        remap = parent_remap or {}
        out = []
        for s in spans:
            rec = dict(s)
            rec["start_ns"] = s["start_ns"] + self.epoch_ns + clock_offset_ns
            rec["end_ns"] = s["end_ns"] + self.epoch_ns + clock_offset_ns
            rec["pid"] = pid
            if process is not None:
                rec["process"] = process
            parent = rec.get("parent_id")
            if parent in remap:
                rec["parent_id"] = remap[parent]
            out.append(rec)
        return {"spans": out, "dropped": shipped_dropped,
                "clock_offset_ns": clock_offset_ns}

    def adopt_remote_spans(self, records: Sequence[Dict[str, Any]]
                           ) -> Tuple[int, int]:
        """Merge spans shipped by :meth:`export_ring` in another process
        into this ring: absolute parent-clock timestamps rebase onto
        this tracer's epoch so local and remote spans share one
        timeline. A record whose name is not canonical is REJECTED and
        counted (a worker must not invent an unmergeable name — the
        runtime half of the span-names lint); never raises. Returns
        ``(adopted, rejected)``."""
        adopted = rejected = 0
        for s in records:
            if s.get("name") not in CANONICAL_SPAN_NAMES:
                rejected += 1
                continue
            rec = dict(s)
            rec["start_ns"] = s["start_ns"] - self.epoch_ns
            rec["end_ns"] = s["end_ns"] - self.epoch_ns
            with self._lock:
                if len(self._spans) == self.max_spans:
                    self.dropped += 1
                self._spans.append(rec)
            adopted += 1
        with self._lock:
            self.remote_adopted += adopted
            self.remote_rejected += rejected
        return adopted, rejected

    def record_remote(self, name: str, parent: Optional[SpanContext],
                      start_abs_ns: int, end_abs_ns: int, *, pid: int,
                      process: Optional[str] = None,
                      **attributes: Any) -> bool:
        """Adopt ONE remote span measured in another process from a wire
        record (see :func:`remote_span`): the span id is allocated here
        (the remote process — e.g. a decode-pool worker with no tracer —
        never allocated one), timestamps arrive on this process's clock
        base already. Non-canonical names are rejected and counted, not
        raised. Returns True when recorded."""
        if name not in CANONICAL_SPAN_NAMES:
            with self._lock:
                self.remote_rejected += 1
            return False
        rec: Dict[str, Any] = {
            "name": name,
            "trace_id": parent.trace_id if parent else self.trace_id,
            "span_id": next(self._ids),
            "parent_id": parent.span_id if parent else None,
            "thread_id": 0,
            "thread_name": process or f"pid-{pid}",
            "start_ns": start_abs_ns - self.epoch_ns,
            "end_ns": end_abs_ns - self.epoch_ns,
            "pid": pid,
        }
        if process is not None:
            rec["process"] = process
        if attributes:
            rec["attributes"] = attributes
        with self._lock:
            if len(self._spans) == self.max_spans:
                self.dropped += 1
            self._spans.append(rec)
            self.remote_adopted += 1
        return True

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome-trace (Trace Event Format) document: complete ("X")
        events in microseconds on one track per thread, loadable by
        ``chrome://tracing`` and Perfetto. Timestamps are monotonic
        (``perf_counter_ns`` rebased to the tracer epoch), so parent
        spans always enclose their children. Adopted remote spans keep
        their origin pid, giving a merged cluster trace one labeled
        process group per worker beside the coordinator's."""
        events: List[Dict[str, Any]] = []
        own_pid = os.getpid()
        seen_threads: Dict[Tuple[int, int], str] = {}
        seen_procs: Dict[int, Optional[str]] = {}
        for s in self.spans():
            pid = s.get("pid", own_pid)
            seen_threads.setdefault((pid, s["thread_id"]),
                                    s["thread_name"])
            if s.get("process") is not None or pid not in seen_procs:
                seen_procs[pid] = s.get("process") or seen_procs.get(pid)
            event = {
                "name": s["name"], "cat": "sparkdl", "ph": "X",
                "ts": s["start_ns"] / 1e3,
                "dur": (s["end_ns"] - s["start_ns"]) / 1e3,
                "pid": pid, "tid": s["thread_id"],
                "args": {"trace_id": s["trace_id"],
                         "span_id": s["span_id"],
                         "parent_id": s["parent_id"],
                         **s.get("attributes", {})},
            }
            events.append(event)
        for (pid, tid), tname in seen_threads.items():
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": tname}})
        # pid-labeled process groups only once remote spans merged in —
        # a single-process trace keeps its pre-merge shape exactly
        if len(seen_procs) > 1 or any(seen_procs.values()):
            for pid, label in seen_procs.items():
                name = label or ("coordinator" if pid == own_pid
                                 else f"pid-{pid}")
                events.append({"name": "process_name", "ph": "M",
                               "pid": pid, "tid": 0,
                               "args": {"name": name}})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def merged_chrome_trace(self, rings: Sequence[Dict[str, Any]]
                            ) -> Dict[str, Any]:
        """A Chrome-trace document merging this ring's CURRENT spans
        with remote :meth:`export_ring` payloads — WITHOUT mutating this
        ring. The flight recorder (``cluster/router.py``) dumps mid-run
        postmortems from on-demand ring pulls; adopting those pulled
        spans into the live ring would double them when the workers ship
        their final rings at close. A scratch tracer sharing this
        tracer's clock epoch does the merge instead (same canonical-name
        rejection as a real adoption), so the live ring stays
        untouched."""
        scratch = Tracer(self.trace_id, max_spans=self.max_spans)
        scratch.epoch_ns = self.epoch_ns
        with self._lock:
            scratch._spans.extend(dict(s) for s in self._spans)
        for ring in rings:
            scratch.adopt_remote_spans(ring.get("spans") or ())
        return scratch.chrome_trace()


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

# Log-scale (factor-2) bucket upper bounds. Durations: 100 µs .. ~3.7 h.
DEFAULT_TIME_BOUNDS: Tuple[float, ...] = tuple(
    1e-4 * 2 ** i for i in range(27))
# Row counts / sizes: powers of two 1 .. 64Ki.
POW2_BOUNDS: Tuple[float, ...] = tuple(float(2 ** i) for i in range(17))


def _estimate_percentile(q: float, counts: Sequence[int], count: int,
                         bounds: Sequence[float], vmin: Optional[float],
                         vmax: Optional[float]) -> Optional[float]:
    """Estimated q-quantile from ONE consistent copy of log-scale bucket
    counts: the geometric midpoint of the covering bucket, clamped to the
    observed [vmin, vmax]. Returns ``None`` (JSON null) for an empty
    histogram or window — never a bucket-midpoint guess over zero
    samples."""
    if count <= 0:
        return None
    target = max(1, math.ceil(q * count))
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if cum >= target:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = (bounds[i] if i < len(bounds)
                  else (vmax if vmax is not None else lo))
            est = math.sqrt(lo * hi) if lo > 0 and hi > 0 else hi
            if vmin is not None:
                est = max(est, vmin)
            if vmax is not None:
                est = min(est, vmax)
            return est
    return vmax


def _window_floor(span_s: float, slots: int, window_s: float) -> int:
    """Oldest slot epoch inside a trailing ``window_s`` window (clamped
    to the ring capacity). The current partial slot is always included,
    so the effective window is ``window_s`` ± one slot span."""
    k = min(slots, max(1, math.ceil(window_s / span_s)))
    return int(_monotonic() / span_s) - k + 1


class Counter:
    """Monotonic counter. With ``window=(span_s, slots)`` it also keeps a
    fixed ring of time-bucketed sub-counts (lazy monotonic-clock
    rotation, O(1) per inc) so :meth:`window_count` can answer "how many
    in the last N seconds" without a timer thread."""

    __slots__ = ("name", "_lock", "_value", "_w_span", "_w_epochs",
                 "_w_counts")

    def __init__(self, name: str,
                 window: Optional[Tuple[float, int]] = None) -> None:
        self.name = name
        self._lock = threading.Lock()
        self._value = 0
        self._w_span: Optional[float] = None
        if window is not None:
            span_s, slots = window
            self._w_span = float(span_s)
            self._w_epochs = [-1] * slots
            self._w_counts = [0] * slots

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n
            if self._w_span is not None:
                epoch = int(_monotonic() / self._w_span)
                i = epoch % len(self._w_counts)
                if self._w_epochs[i] != epoch:  # lazy rotation
                    self._w_epochs[i] = epoch
                    self._w_counts[i] = 0
                self._w_counts[i] += n

    def window_count(self, window_s: float) -> int:
        """Occurrences within the trailing ``window_s`` (0 without a
        ring; resolution = one ring slot)."""
        if self._w_span is None:
            return 0
        with self._lock:
            floor_epoch = _window_floor(self._w_span, len(self._w_counts),
                                        window_s)
            return sum(c for e, c in zip(self._w_epochs, self._w_counts)
                       if e >= floor_epoch)

    def window_frame(self) -> Dict[int, int]:
        """Per-slot ``{epoch: count}`` export of the live ring (one
        consistent locked copy) — the metrics-federation wire format
        (docs/OBSERVABILITY.md "Cluster metrics federation"). Epochs are
        THIS process's monotonic slot indices; the coordinator rebases
        them onto its own clock with the handshake offset before
        folding. Empty without a ring."""
        if self._w_span is None:
            return {}
        with self._lock:
            floor_epoch = _window_floor(
                self._w_span, len(self._w_counts),
                self._w_span * len(self._w_counts))
            return {e: c for e, c in zip(self._w_epochs, self._w_counts)
                    if e >= floor_epoch and c}

    @property
    def value(self) -> int:
        with self._lock:
            return self._value


class Gauge:
    """Last-write-wins instantaneous value. With ``window=`` it also
    remembers (last, min, max) per ring slot so the windowed view can
    report the envelope of the last N seconds, not just the final
    write."""

    __slots__ = ("name", "_lock", "_value", "_w_span", "_w_epochs",
                 "_w_vals")

    def __init__(self, name: str,
                 window: Optional[Tuple[float, int]] = None) -> None:
        self.name = name
        self._lock = threading.Lock()
        self._value: Optional[float] = None
        self._w_span: Optional[float] = None
        if window is not None:
            span_s, slots = window
            self._w_span = float(span_s)
            self._w_epochs = [-1] * slots
            self._w_vals: List[Optional[Tuple[float, float, float]]] = \
                [None] * slots

    def set(self, value: float) -> None:
        value = float(value)
        with self._lock:
            self._value = value
            if self._w_span is not None:
                epoch = int(_monotonic() / self._w_span)
                i = epoch % len(self._w_vals)
                if self._w_epochs[i] != epoch:
                    self._w_epochs[i] = epoch
                    self._w_vals[i] = (value, value, value)
                else:
                    last, lo, hi = self._w_vals[i]  # type: ignore[misc]
                    self._w_vals[i] = (value, min(lo, value),
                                       max(hi, value))

    def window_values(self, window_s: float) -> Optional[Dict[str, float]]:
        """``{'last', 'min', 'max'}`` over the trailing window; ``None``
        when the window saw no :meth:`set` (or there is no ring)."""
        if self._w_span is None:
            return None
        with self._lock:
            floor_epoch = _window_floor(self._w_span, len(self._w_vals),
                                        window_s)
            seen = sorted((e, v) for e, v in zip(self._w_epochs,
                                                 self._w_vals)
                          if e >= floor_epoch and v is not None)
        if not seen:
            return None
        return {"last": seen[-1][1][0],
                "min": min(v[1] for _, v in seen),
                "max": max(v[2] for _, v in seen)}

    def window_frame(self) -> Dict[int, List[float]]:
        """Per-slot ``{epoch: [last, min, max]}`` envelope export of the
        live ring — the federation wire format for gauges (see
        :meth:`Counter.window_frame`). Empty without a ring."""
        if self._w_span is None:
            return {}
        with self._lock:
            floor_epoch = _window_floor(
                self._w_span, len(self._w_vals),
                self._w_span * len(self._w_vals))
            return {e: list(v) for e, v in zip(self._w_epochs,
                                               self._w_vals)
                    if e >= floor_epoch and v is not None}

    @property
    def value(self) -> Optional[float]:
        with self._lock:
            return self._value


class Histogram:
    """Fixed log-scale-bucket histogram with percentile estimates.

    Buckets are upper bounds (Prometheus ``le`` semantics) growing by a
    constant factor (default 2×), so the relative error of a percentile
    estimate is bounded by the factor. p50/p95/p99 are estimated at the
    geometric midpoint of the covering bucket, clamped to the observed
    [min, max].
    """

    __slots__ = ("name", "_lock", "bounds", "_counts", "count", "sum",
                 "min", "max", "_w_span", "_w_epochs", "_w_slots",
                 "_ex_k", "_w_ex")

    def __init__(self, name: str,
                 bounds: Sequence[float] = DEFAULT_TIME_BOUNDS,
                 window: Optional[Tuple[float, int]] = None,
                 exemplar_k: int = 0) -> None:
        self.name = name
        self._lock = threading.Lock()
        self.bounds = tuple(float(b) for b in bounds)
        self._counts = [0] * (len(self.bounds) + 1)  # last = +Inf overflow
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self._w_span: Optional[float] = None
        # opt-in tail-exemplar reservoir: the top-k observations per
        # window slot, each carrying the span context that produced it —
        # a breached p99 points at concrete traces, not just a number
        self._ex_k = int(exemplar_k) if window is not None else 0
        if window is not None:
            span_s, slots = window
            self._w_span = float(span_s)
            self._w_epochs = [-1] * slots
            # one sub-histogram per ring slot: [counts, count, sum, min,
            # max]; reset lazily when its slot's epoch rotates past
            self._w_slots: List[List[Any]] = [
                [[0] * (len(self.bounds) + 1), 0, 0.0, None, None]
                for _ in range(slots)]
            if self._ex_k:
                # per-slot exemplar list, ascending by value (min first
                # for O(1) eviction checks at tiny fixed k)
                self._w_ex: List[List[Tuple[float, str, int]]] = [
                    [] for _ in range(slots)]

    def observe(self, value: float,
                exemplar: Optional[SpanContext] = None) -> None:
        value = float(value)
        idx = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self._counts[idx] += 1
            self.count += 1
            self.sum += value
            if self.min is None or value < self.min:
                self.min = value
            if self.max is None or value > self.max:
                self.max = value
            if self._w_span is not None:
                epoch = int(_monotonic() / self._w_span)
                i = epoch % len(self._w_slots)
                slot = self._w_slots[i]
                if self._w_epochs[i] != epoch:  # lazy rotation
                    self._w_epochs[i] = epoch
                    slot[0] = [0] * (len(self.bounds) + 1)
                    slot[1], slot[2] = 0, 0.0
                    slot[3] = slot[4] = None
                    if self._ex_k:
                        self._w_ex[i] = []
                slot[0][idx] += 1
                slot[1] += 1
                slot[2] += value
                if slot[3] is None or value < slot[3]:
                    slot[3] = value
                if slot[4] is None or value > slot[4]:
                    slot[4] = value
                if self._ex_k and exemplar is not None:
                    ex = self._w_ex[i]
                    if len(ex) < self._ex_k:
                        bisect.insort(
                            ex, (value, exemplar.trace_id,
                                 exemplar.span_id))
                    elif value > ex[0][0]:  # beats the smallest kept
                        ex.pop(0)
                        bisect.insort(
                            ex, (value, exemplar.trace_id,
                                 exemplar.span_id))

    def percentile(self, q: float) -> Optional[float]:
        """Estimated q-quantile (q in [0, 1]) from the bucket counts
        (``None`` on an empty histogram)."""
        with self._lock:
            return _estimate_percentile(q, self._counts, self.count,
                                        self.bounds, self.min, self.max)

    def _raw(self) -> Tuple[Tuple[float, ...], List[int], int, float]:
        """(bounds, counts, count, sum) as one consistent locked copy —
        the Prometheus exposition source."""
        with self._lock:
            return self.bounds, list(self._counts), self.count, self.sum

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            counts = list(self._counts)
            count, total = self.count, self.sum
            lo, hi = self.min, self.max
        buckets = {("+Inf" if i == len(self.bounds)
                    else repr(self.bounds[i])): c
                   for i, c in enumerate(counts) if c}
        # percentiles from the SAME locked copy as the buckets (a
        # concurrent observe between the copy and the estimate cannot
        # skew them apart), None — not a midpoint guess — when empty
        return {
            "count": count, "sum": round(total, 9), "min": lo, "max": hi,
            "p50": _estimate_percentile(0.50, counts, count, self.bounds,
                                        lo, hi),
            "p95": _estimate_percentile(0.95, counts, count, self.bounds,
                                        lo, hi),
            "p99": _estimate_percentile(0.99, counts, count, self.bounds,
                                        lo, hi),
            "buckets": buckets,
        }

    def window_snapshot(self, window_s: float) -> Dict[str, Any]:
        """Merged ``{count, sum, rate_per_s, min, max, p50, p95, p99}``
        over the trailing ``window_s`` (resolution = one ring slot).
        Percentiles and min/max are ``None`` on an empty window; all
        zeros/None without a ring. With an armed exemplar reservoir the
        snapshot additionally carries ``exemplars``: the top-k in-window
        observations (descending), each
        ``{value, trace_id, span_id}`` — the key is absent entirely when
        exemplars are off, keeping the unarmed shape unchanged."""
        counts = [0] * (len(self.bounds) + 1)
        count, total = 0, 0.0
        vmin: Optional[float] = None
        vmax: Optional[float] = None
        exemplars: List[Tuple[float, str, int]] = []
        if self._w_span is not None:
            with self._lock:
                floor_epoch = _window_floor(self._w_span,
                                            len(self._w_slots), window_s)
                for i, (e, slot) in enumerate(zip(self._w_epochs,
                                                  self._w_slots)):
                    if e < floor_epoch or not slot[1]:
                        continue
                    for j, c in enumerate(slot[0]):
                        counts[j] += c
                    count += slot[1]
                    total += slot[2]
                    vmin = slot[3] if vmin is None else min(vmin, slot[3])
                    vmax = slot[4] if vmax is None else max(vmax, slot[4])
                    if self._ex_k:
                        exemplars.extend(self._w_ex[i])
        out = {
            "count": count, "sum": round(total, 9),
            "rate_per_s": round(count / window_s, 9) if window_s else 0.0,
            "min": vmin, "max": vmax,
            "p50": _estimate_percentile(0.50, counts, count, self.bounds,
                                        vmin, vmax),
            "p95": _estimate_percentile(0.95, counts, count, self.bounds,
                                        vmin, vmax),
            "p99": _estimate_percentile(0.99, counts, count, self.bounds,
                                        vmin, vmax),
        }
        if self._ex_k:
            exemplars.sort(reverse=True)
            out["exemplars"] = [
                {"value": v, "trace_id": t, "span_id": s}
                for v, t, s in exemplars[:self._ex_k]]
        return out

    def window_frame(self) -> Dict[int, List[Any]]:
        """Per-slot sub-histogram export of the live ring, keyed by slot
        epoch: ``{epoch: [bucket_counts, count, sum, min, max]}`` (with
        an armed exemplar reservoir each entry appends its slot's
        ``[(value, trace_id, span_id), ...]`` list). Mergeable by
        construction: the coordinator sums bucket counts across workers
        per rebased epoch, so a cluster percentile is estimated from ONE
        merged bucket array — not a worst-worker guess. Empty without a
        ring."""
        if self._w_span is None:
            return {}
        out: Dict[int, List[Any]] = {}
        with self._lock:
            floor_epoch = _window_floor(
                self._w_span, len(self._w_slots),
                self._w_span * len(self._w_slots))
            for i, (e, slot) in enumerate(zip(self._w_epochs,
                                              self._w_slots)):
                if e < floor_epoch or not slot[1]:
                    continue
                entry: List[Any] = [list(slot[0]), slot[1], slot[2],
                                    slot[3], slot[4]]
                if self._ex_k:
                    entry.append([list(ex) for ex in self._w_ex[i]])
                out[e] = entry
        return out


def escape_label_value(value: Any) -> str:
    """Prometheus text-exposition label-value escaping: backslash,
    double-quote and newline (in that order, per the 0.0.4 format)."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _escape_help(text: str) -> str:
    """HELP-line escaping: backslash and newline only (quotes are legal
    in HELP text)."""
    return text.replace("\\", "\\\\").replace("\n", "\\n")


class MetricsRegistry:
    """Get-or-create registry of named instruments (one per name).

    ``window_s``/``window_buckets`` arm the sliding-window rings on every
    instrument the registry creates: ``window_s`` is the largest
    queryable trailing window, bucketed into ``window_buckets`` ring
    slots (the window resolution). ``window_s=None`` (the bare-registry
    default) creates ring-free instruments — the pre-windowing record
    path, not even a clock read per record.

    ``exemplar_k`` (opt-in, default 0 = off) arms a per-slot tail
    exemplar reservoir on every histogram created here: callers passing
    a span context to :meth:`Histogram.observe` get their top-k
    observations per window surfaced with ``{value, trace_id, span_id}``
    in windowed snapshots."""

    def __init__(self, window_s: Optional[float] = None,
                 window_buckets: int = 12, exemplar_k: int = 0) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._window: Optional[Tuple[float, int]] = None
        if exemplar_k < 0:
            raise ValueError(f"exemplar_k must be >= 0, got {exemplar_k!r}")
        self.exemplar_k = int(exemplar_k)
        if window_s is not None:
            if window_s <= 0 or window_buckets <= 0:
                raise ValueError(
                    "window_s and window_buckets must be > 0, got "
                    f"{window_s!r}/{window_buckets!r}")
            self._window = (float(window_s) / int(window_buckets),
                            int(window_buckets))
        self.window_s = window_s

    def counter(self, name: str) -> Counter:
        with self._lock:
            inst = self._counters.get(name)
            if inst is None:
                inst = self._counters[name] = Counter(
                    name, window=self._window)
            return inst

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            inst = self._gauges.get(name)
            if inst is None:
                inst = self._gauges[name] = Gauge(name,
                                                  window=self._window)
            return inst

    def histogram(self, name: str,
                  bounds: Sequence[float] = DEFAULT_TIME_BOUNDS
                  ) -> Histogram:
        with self._lock:
            inst = self._histograms.get(name)
            if inst is None:
                inst = self._histograms[name] = Histogram(
                    name, bounds, window=self._window,
                    exemplar_k=self.exemplar_k)
            return inst

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able {counters, gauges, histograms} snapshot."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            histograms = dict(self._histograms)
        return {
            "counters": {k: counters[k].value for k in sorted(counters)},
            "gauges": {k: gauges[k].value for k in sorted(gauges)},
            "histograms": {k: histograms[k].snapshot()
                           for k in sorted(histograms)},
        }

    def window_snapshot(self, window_s: Optional[float] = None
                        ) -> Dict[str, Any]:
        """Sliding-window view over every instrument: counter counts and
        rates, gauge last/min/max envelopes, histogram percentiles —
        all over the trailing ``window_s`` seconds (default and cap: the
        ring capacity). Resolution is one ring slot, and the current
        partial slot is included, so the effective window is
        ``window_s`` ± one slot. Empty sections when the registry was
        built without windows."""
        if self._window is None:
            return {"window_s": None, "counters": {}, "gauges": {},
                    "histograms": {}}
        span, slots = self._window
        if window_s is None:
            window_s = span * slots
        window_s = min(float(window_s), span * slots)
        if window_s <= 0:
            raise ValueError(f"window_s must be > 0, got {window_s!r}")
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            histograms = dict(self._histograms)
        out_counters = {}
        for k in sorted(counters):
            c = counters[k].window_count(window_s)
            out_counters[k] = {"count": c,
                               "rate_per_s": round(c / window_s, 9)}
        out_gauges = {}
        for k in sorted(gauges):
            v = gauges[k].window_values(window_s)
            if v is not None:
                out_gauges[k] = v
        return {
            "window_s": window_s,
            "counters": out_counters,
            "gauges": out_gauges,
            "histograms": {k: histograms[k].window_snapshot(window_s)
                           for k in sorted(histograms)},
        }

    def export_frame(self) -> Optional[Dict[str, Any]]:
        """The bounded metrics-federation delta frame: every windowed
        instrument's live ring slots keyed by slot epoch, restricted to
        the canonical catalog plus the ``sparkdl.health.*`` mirrors (the
        restriction ``cluster/aggregate.py``'s counter fold already
        applies — a frame never ships a name the taxonomy lint would
        reject). ``None`` without windows: there is nothing windowed to
        federate. Frame size is bounded by construction — ring slots ×
        bucket counts per instrument, independent of traffic volume —
        and each frame is the full state-of-ring (idempotent
        merge-by-replace coordinator-side), so a dropped frame heals on
        the next cadence instead of leaving a permanent gap."""
        if self._window is None:
            return None
        span, slots = self._window
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            histograms = dict(self._histograms)

        def declared(name: str) -> bool:
            return (name in CANONICAL_METRIC_NAMES
                    or name.startswith(HEALTH_METRIC_PREFIX))

        out_counters: Dict[str, Any] = {}
        for name in sorted(counters):
            if declared(name):
                frame = counters[name].window_frame()
                if frame:
                    out_counters[name] = frame
        out_gauges: Dict[str, Any] = {}
        for name in sorted(gauges):
            if declared(name):
                frame = gauges[name].window_frame()
                if frame:
                    out_gauges[name] = frame
        out_hists: Dict[str, Any] = {}
        for name in sorted(histograms):
            if declared(name):
                frame = histograms[name].window_frame()
                if frame:
                    out_hists[name] = {
                        "bounds": list(histograms[name].bounds),
                        "slots": frame,
                    }
        return {
            "span_s": span,
            "slots": slots,
            "now_epoch": int(_monotonic() / span),
            "counters": out_counters,
            "gauges": out_gauges,
            "histograms": out_hists,
        }

    def prometheus_text(self) -> str:
        """Prometheus text exposition (0.0.4) dump of every instrument:
        one ``# HELP`` + ``# TYPE`` pair per metric family, escaped
        label values, cumulative histogram buckets with a closing
        ``+Inf``."""
        import re as _re

        def sane(name: str) -> str:
            return _re.sub(r"[^a-zA-Z0-9_:]", "_", name)

        lines: List[str] = []

        def family(name: str, kind: str) -> str:
            n = sane(name)
            lines.append(
                f"# HELP {n} {_escape_help(name)} (sparkdl_tpu {kind})")
            lines.append(f"# TYPE {n} {kind}")
            return n

        snap = self.snapshot()
        for name, value in snap["counters"].items():
            n = family(name, "counter")
            lines.append(f"{n} {value}")
        for name, value in snap["gauges"].items():
            if value is None:
                continue
            n = family(name, "gauge")
            lines.append(f"{n} {value}")
        with self._lock:
            hists = dict(self._histograms)
        for name in sorted(hists):
            bounds, counts, count, total = hists[name]._raw()
            n = family(name, "histogram")
            cum = 0
            for i, bound in enumerate(bounds):
                cum += counts[i]
                le = escape_label_value(repr(bound))
                lines.append(f'{n}_bucket{{le="{le}"}} {cum}')
            lines.append(f'{n}_bucket{{le="+Inf"}} {count}')
            lines.append(f"{n}_sum {total}")
            lines.append(f"{n}_count {count}")
        return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# Periodic snapshot exporter (the live half of the run report)
# ---------------------------------------------------------------------------


class SnapshotExporter:
    """Periodic live-snapshot exporter for one telemetry scope.

    Every ``interval_s`` (daemon thread; drop-safe final flush at
    :meth:`close`) a tick:

    - appends one JSON line — sequence number, uptime, windowed +
      cumulative metric snapshots, executor queue/breaker state — to
      ``sparkdl_snapshots_<run_id>.jsonl`` under ``out_dir``;
    - atomically replaces ``sparkdl_metrics_<run_id>.prom`` (temp file +
      ``os.replace``) so a Prometheus textfile collector never reads a
      torn exposition;
    - evaluates the scope's SLO watchdog (``core/slo.py``) so breaches
      surface while the process is alive, not in the post-mortem.

    Without an ``out_dir`` no files are written but ticks still run
    (watchdog + the bounded in-memory timeline that feeds the run
    report). A tick that crashes records one ``telemetry_export_error``
    health event and keeps going — the exporter never takes the run
    down and never dies silently.
    """

    def __init__(self, tel: "Telemetry", interval_s: float,
                 out_dir: Optional[str] = None, watchdog: Any = None,
                 timeline_max: int = 240) -> None:
        if interval_s <= 0:
            raise ValueError(
                f"export_interval_s must be > 0, got {interval_s!r}")
        self.tel = tel
        self.interval_s = float(interval_s)
        self.out_dir = out_dir
        self.watchdog = watchdog
        self.seq = 0
        self.errors = 0
        self.snapshot_path: Optional[str] = None
        self.prom_path: Optional[str] = None
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            # run_id alone is NOT unique across processes: cluster
            # workers pin the coordinator's run_id, so a shared out_dir
            # needs the scope's process suffix to avoid silently
            # clobbering the coordinator's files. The coordinator
            # (process_scope=None) keeps the bare historical names.
            scope = getattr(tel, "process_scope", None)
            suffix = f".{scope}" if scope else ""
            self.snapshot_path = os.path.join(
                out_dir, f"sparkdl_snapshots_{tel.run_id}{suffix}.jsonl")
            self.prom_path = os.path.join(
                out_dir, f"sparkdl_metrics_{tel.run_id}{suffix}.prom")
        self._t0 = _monotonic()
        self._next_due = self._t0 + self.interval_s
        self._tick_lock = threading.Lock()  # thread tick vs close flush
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._timeline: "deque[Dict[str, Any]]" = deque(maxlen=timeline_max)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        # sparkdl: allow(unguarded-shared-write): set once, before the exporter thread exists
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"sparkdl-telemetry-export-{self.tel.run_id}")
        self._thread.start()

    def _loop(self) -> None:
        while True:
            wait_s = max(0.005, min(self._next_due - _monotonic(),
                                    self.interval_s))
            if self._stop.wait(timeout=wait_s):
                return
            self.tick_if_due()

    def close(self) -> None:
        """Stop the thread, then flush one final snapshot — the tail of
        the run (where failures live) is never lost to cadence."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            # sparkdl: allow(unguarded-shared-write): the exporter thread is joined; only close() writes this
            self._thread = None
        self.tick(final=True)

    # -- ticking -------------------------------------------------------------

    def tick_if_due(self) -> bool:
        """Export iff the cadence clock says a snapshot is due."""
        now = _monotonic()
        if now < self._next_due:
            return False
        # sparkdl: allow(unguarded-shared-write): cadence state touched only by the exporter thread (close() only flushes)
        self._next_due = now + self.interval_s
        self.tick()
        return True

    def tick(self, final: bool = False) -> None:
        """One export. Never raises: a crashed tick records ONE
        ``telemetry_export_error`` health event and returns, so the
        exporter thread survives and the next tick gets a fresh try."""
        from sparkdl_tpu.core import health  # lazy: health imports us

        try:
            with self._tick_lock:
                self._export(final=final)
        except Exception as e:  # noqa: BLE001 - recorded, never re-raised
            self.errors += 1
            health.record(health.TELEMETRY_EXPORT_ERROR,
                          error=type(e).__name__, seq=self.seq)
            logging.getLogger(__name__).exception(
                "telemetry snapshot export failed (seq %d): %s",
                self.seq, e)

    def _export(self, final: bool) -> None:
        now = _monotonic()
        tel = self.tel
        self.seq += 1
        slo_state = (self.watchdog.evaluate(tel.metrics, now=now)
                     if self.watchdog is not None else None)
        snap: Dict[str, Any] = {
            "seq": self.seq,
            "run_id": tel.run_id,
            "uptime_s": round(now - self._t0, 6),
            "created_unix_s": round(time.time(), 3),
            "windowed": tel.metrics.window_snapshot(),
            "cumulative": tel.metrics.snapshot(),
            "executor": self._executor_status(),
        }
        serving = self._serving_status()
        if serving is not None:
            snap["serving"] = serving
        cluster = self._cluster_status()
        if cluster is not None:
            snap["cluster"] = cluster
        if slo_state is not None:
            snap["slo"] = slo_state
        if final:
            snap["final"] = True
        self._timeline.append(self._compact(snap))
        if self.snapshot_path is not None:
            # sparkdl: allow(blocking-under-lock): serializing these writes against the close() flush is _tick_lock's whole job
            with open(self.snapshot_path, "a") as f:
                # sparkdl: allow(blocking-under-lock): see the open() above — one writer at a time by design
                f.write(json.dumps(snap, default=str) + "\n")
                f.flush()
        if self.prom_path is not None:
            tmp = self.prom_path + ".tmp"
            # sparkdl: allow(blocking-under-lock): serializing these writes against the close() flush is _tick_lock's whole job
            with open(tmp, "w") as f:
                # sparkdl: allow(blocking-under-lock): see the open() above — one writer at a time by design
                f.write(tel.metrics.prometheus_text())
                # federated cluster series (whole-cluster merged view)
                # append AFTER the local exposition: live scrapes of a
                # cluster coordinator reflect every worker, and the
                # text is empty — file byte-identical — off-path
                # sparkdl: allow(blocking-under-lock): see the open() above — one writer at a time by design
                f.write(self._cluster_prometheus_text())
            os.replace(tmp, self.prom_path)

    @staticmethod
    def _executor_status() -> Optional[Dict[str, Any]]:
        """Queue/breaker state of the device execution service — read
        only when the process already imported it (``sys.modules``, not
        an import: a pure-training job must not pay for the executor
        just because the exporter is on)."""
        import sys

        mod = sys.modules.get("sparkdl_tpu.core.executor")
        if mod is None:
            return None
        return mod.service().status()

    @staticmethod
    def _serving_status() -> Optional[Dict[str, Any]]:
        """Per-deployment replica map of the cluster serving router —
        same ``sys.modules`` stance as :meth:`_executor_status`: a
        process that never imported the cluster serving plane must not
        pay for it (and the key stays absent, keeping single-process
        snapshots byte-identical)."""
        import sys

        mod = sys.modules.get("sparkdl_tpu.serving.cluster")
        if mod is None:
            return None
        return mod.exporter_status()

    @staticmethod
    def _cluster_status() -> Optional[Dict[str, Any]]:
        """The federated cluster-metrics view of the live partition
        router (windowed cluster-wide fold + ``workers_reporting``) —
        same ``sys.modules`` stance as :meth:`_executor_status`: a
        single-process run never imports the cluster plane, and the key
        stays absent (snapshot lines byte-identical) unless a router
        with metrics federation armed is live."""
        import sys

        mod = sys.modules.get("sparkdl_tpu.cluster.router")
        if mod is None:
            return None
        return mod.exporter_status()

    @staticmethod
    def _cluster_prometheus_text() -> str:
        """Federated Prometheus series of the live router, or ``""`` —
        the ``.prom`` analogue of :meth:`_cluster_status` (same absent-
        unless-armed stance, so off-path files stay byte-identical)."""
        import sys

        mod = sys.modules.get("sparkdl_tpu.cluster.router")
        if mod is None:
            return ""
        return mod.exporter_prometheus_text()

    # -- the timeline that feeds RunReport -----------------------------------

    @staticmethod
    def _compact(snap: Dict[str, Any]) -> Dict[str, Any]:
        """One bounded timeline entry per snapshot: windowed activity
        (non-empty instruments only) + the SLO verdicts."""
        windowed = snap["windowed"]
        entry: Dict[str, Any] = {
            "seq": snap["seq"],
            "uptime_s": snap["uptime_s"],
            "windowed_histograms": {
                k: {"count": v["count"], "p50": v["p50"], "p99": v["p99"]}
                for k, v in windowed["histograms"].items() if v["count"]},
            "windowed_counters": {
                k: v for k, v in windowed["counters"].items()
                if v["count"]},
        }
        if snap.get("slo") is not None:
            entry["slo_breached"] = sorted(
                name for name, st in snap["slo"].items() if st["breached"])
            exemplars = {
                name: st["exemplars"]
                for name, st in snap["slo"].items()
                if st["breached"] and st.get("exemplars")}
            if exemplars:
                entry["slo_exemplars"] = exemplars
        if snap.get("final"):
            entry["final"] = True
        return entry

    def timeline_summary(self) -> Dict[str, Any]:
        """The run report's ``timeline`` block: exporter stats + the
        (bounded, tail-keeping) compact snapshot entries."""
        return {
            "export_interval_s": self.interval_s,
            "snapshots": self.seq,
            "errors": self.errors,
            "snapshot_path": self.snapshot_path,
            "prometheus_path": self.prom_path,
            "entries": list(self._timeline),
        }


# ---------------------------------------------------------------------------
# The process-wide scope
# ---------------------------------------------------------------------------

_run_counter = itertools.count(1)


class _RunContextFilter(logging.Filter):
    """Stamps run_id/trace_id onto log records (via the record factory,
    so it reaches records regardless of which handler formats them)."""

    def __init__(self, run_id: str, trace_id: str) -> None:
        super().__init__()
        self.run_id = run_id
        self.trace_id = trace_id

    def filter(self, record: logging.LogRecord) -> bool:
        record.run_id = self.run_id
        record.trace_id = self.trace_id
        return True


class Telemetry:
    """One run's telemetry scope: tracer + metrics + end-of-run report.

    ::

        with Telemetry("nightly-fit", out_dir="/tmp/tel") as tel:
            pipeline.run()
        # exiting wrote sparkdl_run_report_<run_id>.json and
        # sparkdl_trace_<run_id>.json into out_dir

    ``out_dir`` defaults to ``$SPARKDL_TELEMETRY_DIR``; when neither is
    set no files are written and the scope is purely programmatic
    (``tel.tracer`` / ``tel.metrics`` / ``tel.report()``). While the
    scope is active, log records from the ``sparkdl_tpu`` namespace
    carry ``.run_id`` / ``.trace_id`` attributes (structured-logging
    adapter). To fold the active ``HealthMonitor``'s report into the
    run report, enter the monitor BEFORE (outside) the telemetry scope.
    """

    def __init__(self, name: str = "run", out_dir: Optional[str] = None,
                 max_spans: int = 65536,
                 window_s: Optional[float] = 60.0,
                 window_buckets: int = 12,
                 export_interval_s: Optional[float] = None,
                 slo_rules: Optional[Sequence[Any]] = None,
                 run_id: Optional[str] = None,
                 exemplar_k: int = 0,
                 process_scope: Optional[str] = None) -> None:
        self.name = name
        self.out_dir = (out_dir if out_dir is not None
                        else os.environ.get(TELEMETRY_DIR_ENV))
        # run_id pins the identity across process restarts (durable
        # recovery, core/durability.pinned_run_id): the snapshot
        # timeline JSONL appends and the run report path stay THE SAME
        # file before and after a crash. Default: fresh per-scope id.
        self.run_id = run_id or (
            f"{name}-{os.getpid():x}-{next(_run_counter):04x}")
        # process_scope disambiguates output files when several
        # processes share a run_id AND an out_dir (cluster workers pin
        # the coordinator's run_id); None — the coordinator and the
        # durable-resume path — keeps the bare file names.
        self.process_scope = process_scope
        self.tracer = Tracer(trace_id=self.run_id, max_spans=max_spans)
        self.metrics = MetricsRegistry(window_s=window_s,
                                       window_buckets=window_buckets,
                                       exemplar_k=exemplar_k)
        if export_interval_s is None:
            env = os.environ.get(EXPORT_INTERVAL_ENV)
            export_interval_s = float(env) if env else None
        if export_interval_s is not None and export_interval_s <= 0:
            raise ValueError("export_interval_s must be > 0, got "
                             f"{export_interval_s!r}")
        self.export_interval_s = export_interval_s
        if slo_rules is not None and window_s is not None:
            # an EXPLICIT rule window past the ring capacity would
            # silently evaluate over less history than it declares —
            # fail here, where both configs are in hand, not at the
            # first tick. (The shipped defaults adapt instead: a scope
            # with a small ring gets them re-parameterized to fit.)
            for rule in slo_rules:
                if rule.window_s > window_s + 1e-9:
                    raise ValueError(
                        f"SLO rule {rule.name!r} window_s="
                        f"{rule.window_s} exceeds this scope's metric "
                        f"ring capacity (window_s={window_s}); raise "
                        "Telemetry(window_s=...) or shrink the rule "
                        "window")
        self.slo_rules = slo_rules
        self.slo_watchdog: Any = None
        self.exporter: Optional[SnapshotExporter] = None
        self._prev: Optional["Telemetry"] = None
        self._root: Optional[_Span] = None
        self._prev_factory: Any = None
        self._filter = _RunContextFilter(self.run_id, self.run_id)
        self.report_path: Optional[str] = None
        self.trace_path: Optional[str] = None

    # -- context -------------------------------------------------------------

    @property
    def root_context(self) -> Optional[SpanContext]:
        return self._root.context if self._root is not None else None

    def __enter__(self) -> "Telemetry":
        global _active
        with _activation_lock:
            self._prev = _active
            _active = self
            # structured-logging adapter: stamp run/trace ids at record
            # creation so they survive any handler (a Filter on the
            # package logger would miss records emitted via child
            # loggers — logging only runs logger-level filters on the
            # logger actually called)
            prev_factory = logging.getLogRecordFactory()
            self._prev_factory = prev_factory
            flt = self._filter

            def factory(*args: Any, **kwargs: Any) -> logging.LogRecord:
                record = prev_factory(*args, **kwargs)
                if record.name.startswith("sparkdl_tpu"):
                    flt.filter(record)
                return record

            logging.setLogRecordFactory(factory)
        self._root = self.tracer.span(SPAN_RUN, parent=ROOT,
                                      run=self.name)
        self._root.__enter__()
        # lazy: profiling imports this module at module level
        from sparkdl_tpu.core import profiling as _profiling

        _profiling.mirror_startup(self)
        if self.export_interval_s is not None:
            # lazy: core.slo imports this module for the metric catalog
            from sparkdl_tpu.core import slo as _slo

            rules = self.slo_rules
            if rules is None:
                cap = self.metrics.window_s
                if cap is not None and cap < _slo.DEFAULT_WINDOW_S:
                    # the defaults adapt to a smaller metric ring
                    # instead of refusing the scope
                    rules = _slo.default_rules(window_s=cap)
                else:
                    rules = _slo.DEFAULT_RULES
            self.slo_watchdog = _slo.SLOWatchdog(rules) if rules else None
            self.exporter = SnapshotExporter(
                self, self.export_interval_s, out_dir=self.out_dir,
                watchdog=self.slo_watchdog)
            self.exporter.start()
        return self

    def __exit__(self, *exc: Any) -> None:
        global _active
        if self.exporter is not None:
            # stop + final drop-safe flush BEFORE deactivating: SLO
            # events from the last evaluation still mirror into THIS
            # scope's counters and the active HealthMonitor
            self.exporter.close()
        if self._root is not None:
            # pass the unwinding exception through so the run root span
            # carries the error attribute like every interior span
            exc3 = exc if len(exc) == 3 else (None, None, None)
            self._root.__exit__(*exc3)
        with _activation_lock:
            _active = self._prev
            self._prev = None
            logging.setLogRecordFactory(self._prev_factory)
        if self.out_dir:
            try:
                self.write_report(self.out_dir)
            except OSError as e:
                logging.getLogger(__name__).error(
                    "could not write telemetry report to %r: %s",
                    self.out_dir, e)

    # -- reporting -----------------------------------------------------------

    def report(self) -> Dict[str, Any]:
        return RunReport.build(self)

    def write_report(self, out_dir: str) -> str:
        """Write the run report + Chrome trace JSONs; returns the report
        path (also kept in :attr:`report_path` / :attr:`trace_path`)."""
        os.makedirs(out_dir, exist_ok=True)
        suffix = f".{self.process_scope}" if self.process_scope else ""
        trace_path = os.path.join(
            out_dir, f"sparkdl_trace_{self.run_id}{suffix}.json")
        # tmp + os.replace (analyzer rule atomic-write): a crash while
        # exporting must not leave a torn report that a durable-resume
        # reader would trust
        tmp = f"{trace_path}.tmp"
        with open(tmp, "w") as f:
            json.dump(self.tracer.chrome_trace(), f)
        os.replace(tmp, trace_path)
        report = self.report()
        report["chrome_trace"] = trace_path
        report_path = os.path.join(
            out_dir, f"sparkdl_run_report_{self.run_id}{suffix}.json")
        tmp = f"{report_path}.tmp"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=2, default=str)
        os.replace(tmp, report_path)
        self.report_path, self.trace_path = report_path, trace_path
        return report_path


_active: Optional[Telemetry] = None
_activation_lock = threading.Lock()


def active() -> Optional[Telemetry]:
    return _active


def current_context() -> Optional[SpanContext]:
    """The ambient span context on THIS thread: innermost open span,
    else the context attached via :func:`attach`, else the active
    scope's root span. ``None`` without an active scope."""
    tel = _active
    if tel is None:
        return None
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1].context
    base = getattr(_tls, "base", None)
    if base is not None:
        return base
    return tel.root_context


def attach(ctx: Optional[SpanContext]) -> None:
    """Adopt ``ctx`` as this thread's base context: ambient spans opened
    here parent under it. For FRESH worker threads (the prefetcher's
    staging thread); pool threads that outlive a task should pass
    ``parent=`` explicitly instead — an attached base would leak into
    the next task."""
    _tls.base = ctx


def span(name: str, parent: Optional[SpanContext] = None,
         **attributes: Any) -> Any:
    """An open span on the active scope's tracer; the shared
    :data:`NULL_SPAN` singleton (no allocation) when no scope is
    active."""
    tel = _active
    if tel is None:
        return NULL_SPAN
    return tel.tracer.span(name, parent=parent, **attributes)


def count(name: str, n: int = 1) -> None:
    """Bump a counter on the active registry (no-op — one global read —
    without a scope)."""
    tel = _active
    if tel is not None:
        tel.metrics.counter(name).inc(n)


def gauge_set(name: str, value: float) -> None:
    tel = _active
    if tel is not None:
        tel.metrics.gauge(name).set(value)


def observe(name: str, value: float,
            bounds: Sequence[float] = DEFAULT_TIME_BOUNDS,
            exemplar: Optional[SpanContext] = None) -> None:
    """Record one histogram observation, optionally tagged with the span
    context that produced it (kept only by scopes armed with
    ``exemplar_k``; inert — not even stored — otherwise)."""
    tel = _active
    if tel is not None:
        tel.metrics.histogram(name, bounds).observe(value, exemplar)


#: Bounds of the ratio histograms a program reports (1 = even load).
RATIO_BOUNDS = (1.0, 1.05, 1.1, 1.2, 1.35, 1.5, 2.0, 3.0, 4.0, 8.0, 16.0)


def take_program_counts(outputs: Any) -> Any:
    """Strip the ``PROGRAM_COUNTS`` entry off a model's host outputs and
    record it on the active scope (see the catalog above). Outputs without
    the entry — every model but the ones that count — pass through."""
    if not isinstance(outputs, dict) or PROGRAM_COUNTS not in outputs:
        return outputs
    outputs = dict(outputs)
    counts = outputs.pop(PROGRAM_COUNTS)
    if _active is not None:
        for name, values in counts.items():
            if CANONICAL_METRIC_KINDS.get(name) == "histogram":
                for value in values.ravel().tolist():
                    observe(name, value, bounds=RATIO_BOUNDS)
            else:
                count(name, int(values.sum()))
    return outputs


def remote_span(name: str, start_abs_ns: int, end_abs_ns: int, *,
                pid: Optional[int] = None,
                **attributes: Any) -> Dict[str, Any]:
    """Build the WIRE record for a span measured in a process with no
    tracer of its own (a decode-pool worker): timestamps must already be
    on the ADOPTING process's clock base (worker perf_counter_ns + the
    handshake offset). The adopting side turns it into a real span via
    :meth:`Tracer.record_remote`. The name must be canonical — this is
    the process-boundary half of the span-names lint, enforced at
    build time so a worker cannot ship an unmergeable name."""
    if name not in CANONICAL_SPAN_NAMES:
        raise ValueError(
            f"remote span name {name!r} is not in CANONICAL_SPAN_NAMES; "
            "span names crossing a process boundary must be canonical "
            "(docs/OBSERVABILITY.md)")
    rec: Dict[str, Any] = {
        "name": name,
        "start_ns": int(start_abs_ns),
        "end_ns": int(end_abs_ns),
        "pid": pid if pid is not None else os.getpid(),
    }
    if attributes:
        rec["attributes"] = attributes
    return rec


def clock_handshake(conn: Any, timeout_s: float = 5.0) -> int:
    """Worker half of the cross-process clock exchange (NTP-style, one
    round trip over a dedicated pipe): send a ping, read the parent's
    ``perf_counter_ns`` reply, and return the offset that maps THIS
    process's ``perf_counter_ns`` onto the parent's
    (``parent_ns ≈ local_ns + offset``), assuming symmetric transit.
    Falls back to 0 (clocks assumed aligned — on Linux both processes
    read the same CLOCK_MONOTONIC) if the parent never answers."""
    try:
        t0 = time.perf_counter_ns()
        conn.send(("clock", t0))
        if not conn.poll(timeout_s):
            return 0
        t_parent = conn.recv()
        t1 = time.perf_counter_ns()
        return int(t_parent) - (t0 + t1) // 2
    except (EOFError, OSError):
        return 0


# ---------------------------------------------------------------------------
# Run report
# ---------------------------------------------------------------------------


class RunReport:
    """Builder for the single end-of-run JSON artifact: trace summary +
    metric snapshot + phase/overlap stats + the start-up record + health
    report."""

    @staticmethod
    def build(tel: Telemetry,
              health_monitor: Any = None) -> Dict[str, Any]:
        # lazy imports: profiling imports this module at module level
        from sparkdl_tpu.core import health as _health
        from sparkdl_tpu.core import profiling as _profiling

        mon = (health_monitor if health_monitor is not None
               else _health.active_monitor())
        return {
            "run_id": tel.run_id,
            "run": tel.name,
            "created_unix_s": round(time.time(), 3),
            "trace": tel.tracer.summary(),
            "metrics": tel.metrics.snapshot(),
            "phases": _profiling.phase_stats(),
            "overlap": _profiling.overlap_stats(),
            # what set-up took over the process's life, whenever the
            # scope opened (the same numbers as the sparkdl.startup.*
            # gauges)
            "startup": _profiling.startup_stats(),
            "health": mon.report() if mon is not None else None,
            # the live plane's view of the same run: one compact entry
            # per periodic snapshot (None without an exporter)
            "timeline": (tel.exporter.timeline_summary()
                         if tel.exporter is not None else None),
        }
