"""Cluster worker process: one full per-process inference stack.

Each worker is a **spawn-context** process (never fork — the
coordinator owns a live JAX/PJRT runtime; a forked child inheriting
device handles is undefined behavior, the same rule
``core/decode_pool.py`` established) that hosts everything a
single-process run would: its own device runtime, its own
``DeviceExecutor`` + compiled-fn cache (reached through the op chain
exactly as inline execution reaches them), and its own
``Telemetry(run_id=...)`` scope pinned to the COORDINATOR's run id so
every worker's spans and metrics carry the same run identity the
merged report (``cluster/aggregate.py``) is keyed on.

Transport mirrors the decode pool: a PRIVATE task queue in and a
PRIVATE result pipe back per worker — one writer per pipe, so a worker
killed mid-delivery corrupts only its own channel and the router's
collector sees the death as EOF. Op chains ship once per distinct
chain as cloudpickle blobs keyed by the token
``cluster/router.py`` derives from ``core/durability.py``'s op-chain
canonicalization (``durability.ops_token``), then partitions reference
the token — model weights cross the pipe once, not per partition.

Boot order matters: the jax platform is pinned from the coordinator's
configured platform BEFORE any backend initialization (a spawned
interpreter re-runs env resolution from scratch — the coordinator's
choice must win), the backend is then brought up AT BOOT, and the
outcome is the worker's first message: ``("booted", worker_id,
platform)``, or
``("boot_err", ...)`` typed and FATAL-classified, after which the worker
exits. The router waits a bounded time for it, so a worker that cannot
get a device (a TPU chip belongs to ONE process; this plane gives no
worker a chip of its own yet) fails the call that armed the cluster
instead of hanging its first task. Then the coordinator's
``EngineConfig`` snapshot is restored with the cluster/durability/
decode-pool knobs forced off (a worker must never recurse into
another cluster, journal coordinator-owned state, or nest decode
pools under the coordinator's pool).

Protocol (parent -> worker queue):
  ``("ops", token, blob)``                      register an op chain
  ``("srv_*", ...)``                            cluster serving plane
      (``sparkdl_tpu/serving/cluster.py``): deploy/retire/pin fan-out,
      two-phase cutover prepares, and routed predicts. The first
      ``srv_*`` message lazily builds this worker's
      ``WorkerServingPlane`` (own ModelRegistry + residency budget) —
      a batch-only cluster run never imports the serving plane
  ``("task", task_id, index, token, ipc, crash, preempt, tenant,
  ctx)``  run one partition; ``ctx`` is the coordinator's
      dispatch-span ``SpanContext`` (None with tracing off) — the
      worker's ``sparkdl.cluster_task`` span parents under it;
      ``preempt`` (the armed ``cluster_worker_preempt`` marker)
      SIGTERMs this process BEFORE the task runs — the task still
      completes, the drain is zero-recompute; ``tenant`` is the job's
      fair-queueing tag (``EngineConfig.job_tenant``), entered as an
      ``executor.tenant_scope`` around the op chain
  ``("pull_ring",)``                            flight-recorder span
      pull: reply with the CURRENT span ring (rebased, non-draining —
      the worker keeps running) so a mid-run postmortem bundle carries
      a merged partial trace
  ``None``                                      poison pill
(worker -> parent pipe):
  ``("booted", worker_id, platform)`` /
  ``("boot_err", worker_id, type, msg, kind)``
      the boot outcome, always the first message (see above)
  ``("ok", task_id, ipc, meta)`` / ``("err", task_id, type, msg, kind)``
  ``("draining", worker_id)``                   SIGTERM-with-warning
      received (spot-VM preemption): the router stops dispatching here
      and pills this worker once its in-flight tasks finish — the
      worker NEVER self-exits on SIGTERM (a task sitting unread in the
      queue could be stranded otherwise; the drain is pill-driven)
  ``("frame", worker_id, frame)``               metrics-federation frame
      (``EngineConfig.cluster_federation_s`` armed): the bounded
      windowed-metrics export ``cluster/aggregate.build_frame`` makes,
      shipped at the federation cadence between tasks so the
      coordinator's live fold tracks this worker mid-run
  ``("ring", worker_id, ring)``                 ``pull_ring`` reply
  ``("final", worker_id, snapshot)``            last message before EOF
      (with tracing armed the snapshot carries this worker's span ring,
      rebased onto the coordinator's clock via the startup handshake on
      the dedicated clock pipe)
"""

from __future__ import annotations

import os
import signal
import time
from queue import Empty
from typing import Any, Dict

# Idle-worker orphan watch (same rationale as the decode pool): a
# kill -9'd coordinator can never deliver the poison pill, so
# reparenting is the worker's only death signal.
_ORPHAN_POLL_S = 5.0

# True inside a spawned cluster worker (set by _worker_main): a worker
# must never route its own partitions back into a router —
# ``router.maybe_router`` checks this, and the restored EngineConfig
# forces cluster_workers=0 anyway (belt and braces).
_IN_WORKER = False


def _ipc_bytes(batch: Any) -> bytes:
    """One-batch Arrow IPC stream — the partition wire format (the same
    encoding ``core/durability.py`` spills, so cluster transport and
    durable spills agree byte-for-byte on what a partition *is*)."""
    import io

    import pyarrow as pa

    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, batch.schema) as writer:
        writer.write_batch(batch)
    return sink.getvalue()


def _batch_from_ipc(payload: bytes) -> Any:
    import io

    import pyarrow as pa

    with pa.ipc.open_stream(io.BytesIO(payload)) as reader:
        batches = [b for b in reader]
    if len(batches) != 1:
        raise IOError(
            f"cluster task payload holds {len(batches)} batches, "
            "expected 1")
    return batches[0]


def _worker_main(worker_id: int, tasks: Any, conn: Any, owner_pid: int,
                 run_id: str, boot_blob: bytes,
                 clock_conn: Any = None) -> None:
    """Worker process loop: execute partition op chains until the
    ``None`` poison pill, then ship the end-of-run snapshot and EOF.

    Classified retry, hedging, quarantine, deadlines, and fault
    injection all stay COORDINATOR-side (the router routes through
    ``engine/supervisor.py``); this loop only executes one attempt's op
    chain and reports the outcome — an exception ships back typed with
    its ``resilience.classify`` kind so the coordinator's retry loop
    sees exactly what an in-process attempt would have raised. Only the
    armed ``cluster_worker_kill`` marker (evaluated coordinator-side,
    riding on the task message) kills the process — SIGKILL, no
    cleanup, exactly what the chaos leg needs.
    """
    global _IN_WORKER
    _IN_WORKER = True
    import cloudpickle

    boot = cloudpickle.loads(boot_blob)
    from sparkdl_tpu.core import resilience

    try:
        # pin the platform BEFORE anything can initialize the backend:
        # the spawned interpreter re-resolves platform selection from
        # scratch and must land where the coordinator landed (None: the
        # coordinator had not chosen — same environment, same default)
        import jax

        if boot["platform"]:
            jax.config.update("jax_platforms", boot["platform"])
        # bring the backend up NOW, inside the router's bounded boot
        # wait — not lazily under the first task, where a device this
        # process cannot have would surface as a hang or a retry loop
        jax.devices()
        from sparkdl_tpu.cluster import aggregate
        from sparkdl_tpu.core import executor, health, profiling, telemetry
        from sparkdl_tpu.engine.dataframe import EngineConfig

        EngineConfig.restore(boot["config"])
    # sparkdl: allow(broad-retry): not a retry — whatever stops the boot ships typed to the coordinator, which raises it from the call that armed the cluster
    except Exception as e:  # noqa: BLE001 - re-raised parent-side
        conn.send(("boot_err", worker_id, type(e).__name__, str(e),
                   resilience.FATAL))
        conn.close()
        return
    # the platform it LANDED on rides along: with none configured, a JAX
    # that cannot get the TPU falls back to the CPU without a word, and
    # the router refuses a worker set that did not all land in one place
    conn.send(("booted", worker_id, jax.default_backend()))
    name = f"sparkdl-cluster-{worker_id}"
    # SIGTERM-with-warning (spot-VM preemption): the handler ONLY sets a
    # flag — touching the result pipe from a signal frame could tear a
    # message mid-send. The loop notices the flag at its next iteration
    # (PEP 475: the signal interrupts a blocking queue get, which then
    # resumes — worst case one _ORPHAN_POLL_S when idle, instant when
    # busy) and notifies the router, which owns the drain.
    preempted = {"flag": False, "sent": False}

    def _on_sigterm(signum, frame):  # pragma: no cover - signal frame
        preempted["flag"] = True

    signal.signal(signal.SIGTERM, _on_sigterm)
    # the coordinator's root span context (None = tracing off) and the
    # clock offset that maps this process's perf_counter_ns onto the
    # coordinator's — together they let this worker's spans merge onto
    # the coordinator's timeline as ONE trace
    coord_root = boot.get("root_ctx")
    clock_offset = 0
    if clock_conn is not None:
        clock_offset = telemetry.clock_handshake(clock_conn)
        clock_conn.close()
    ops_cache: Dict[str, Any] = {}
    serving_plane = None
    tasks_done = 0
    rows_out = 0
    exec_s_total = 0.0
    snapshot: Dict[str, Any] = {}
    # monitor OUTSIDE the telemetry scope (the documented nesting that
    # folds health into reports); out_dir="" suppresses file export —
    # the snapshot ships over the pipe instead
    monitor = health.HealthMonitor(name)
    # metrics federation (docs/OBSERVABILITY.md "Cluster metrics
    # federation"): NOT forced off in the restored config — the worker
    # reads the coordinator's cadence here and ships bounded frames
    # between tasks; None keeps the loop (and the pipe traffic)
    # byte-identical to the pre-federation protocol
    fed_s = EngineConfig.cluster_federation_s
    frame_seq = 0
    # armed by the first message: a worker idling through the router's
    # boot wait has nothing to federate, and frames that started before
    # its first (slow: the op chain unpickles) message would only make it
    # look stale
    next_frame = None
    with monitor, telemetry.Telemetry(
            name=name, out_dir="", run_id=run_id,
            process_scope=f"w{worker_id}",
            exemplar_k=int(boot.get("exemplar_k") or 0)) as tel:
        # ambient worker spans (compiles, executor launches) parent
        # under the coordinator's root rather than this worker's private
        # root — a no-op when tracing is off (coord_root is None)
        telemetry.attach(coord_root)

        def _ring():
            remap = ({tel.root_context.span_id: coord_root.span_id}
                     if coord_root is not None else None)
            return tel.tracer.export_ring(
                clock_offset_ns=clock_offset, process=name,
                parent_remap=remap)

        while True:
            if next_frame is not None and time.monotonic() >= next_frame:
                frame_seq += 1
                frame = aggregate.build_frame(
                    name, worker_id, frame_seq, tel,
                    clock_offset_ns=clock_offset)
                if frame is not None:
                    conn.send(("frame", worker_id, frame))
                next_frame = time.monotonic() + fed_s
            if preempted["flag"] and not preempted["sent"]:
                # tell the router we are draining, then KEEP processing:
                # in-flight and already-queued tasks run to completion
                # (zero re-execution); the router pills us once our
                # in-flight set empties
                preempted["sent"] = True
                health.record(health.CLUSTER_PREEMPTION_NOTICE,
                              worker=name)
                conn.send(("draining", worker_id))
            try:
                timeout = _ORPHAN_POLL_S
                if next_frame is not None:
                    # wake for the next frame even while idle (the
                    # cadence must not stall just because no task came)
                    timeout = min(timeout,
                                  max(0.01,
                                      next_frame - time.monotonic()))
                msg = tasks.get(timeout=timeout)
            except Empty:
                if os.getppid() != owner_pid:  # orphaned: owner died hard
                    conn.close()
                    return
                continue
            if msg is None:
                break
            if fed_s and next_frame is None:
                next_frame = time.monotonic() + fed_s
            if msg[0] == "ops":
                _, token, blob = msg
                ops_cache[token] = cloudpickle.loads(blob)
                continue
            if msg[0] == "pull_ring":
                # flight-recorder pull: ship the CURRENT ring (rebased,
                # re-parented like the final one) and keep running —
                # the postmortem must not disturb the stream
                conn.send(("ring", worker_id, _ring()))
                continue
            if isinstance(msg[0], str) and msg[0].startswith("srv_"):
                if serving_plane is None:
                    from sparkdl_tpu.serving.cluster import \
                        WorkerServingPlane

                    serving_plane = WorkerServingPlane(worker_id, name,
                                                       conn)
                serving_plane.handle(msg)
                continue
            _, task_id, index, token, payload, crash, preempt, tenant, \
                ctx = msg
            if crash:
                # injected worker death (chaos leg): die as hard as a
                # machine loss — no cleanup, no final snapshot
                os.kill(os.getpid(), signal.SIGKILL)
            if preempt:
                # injected SIGTERM-with-warning: the flag is set before
                # the task runs, so the drain notice goes out on the
                # NEXT loop iteration — this task still completes
                os.kill(os.getpid(), signal.SIGTERM)
            t0 = time.perf_counter()
            try:
                ops = ops_cache[token]
                out = _batch_from_ipc(payload)
                # parent = the coordinator's sparkdl.cluster_dispatch
                # span that shipped this task (ambient fallback when
                # tracing is off), so the cross-process parent link is
                # explicit, not inferred; the job's tenant tag scopes
                # the op chain so worker-side executor metrics stay
                # tenant-attributed
                with executor.tenant_scope(tenant), \
                        telemetry.span(telemetry.SPAN_CLUSTER_TASK,
                                       parent=ctx, partition=index,
                                       cluster_worker=worker_id):
                    for op in ops:
                        out = op(out)
                result = _ipc_bytes(out)
            # sparkdl: allow(broad-retry): not a retry — the error ships typed (with its classify kind) to the coordinator, whose supervisor owns the retry decision
            except Exception as e:  # noqa: BLE001 - re-raised parent-side
                conn.send(("err", task_id, type(e).__name__, str(e),
                           resilience.classify(e)))
                continue
            dt = time.perf_counter() - t0
            tasks_done += 1
            rows_out += out.num_rows
            exec_s_total += dt
            conn.send(("ok", task_id, result,
                       {"exec_s": dt, "rows": out.num_rows}))
        # end-of-run snapshot, built while the scopes are still active;
        # with tracing armed it carries this worker's span ring, rebased
        # onto the coordinator's clock, with spans still hanging off the
        # worker's (never-shipped, still-open) root re-parented onto the
        # coordinator's root
        span_ring = _ring() if coord_root is not None else None
        snapshot = aggregate.build_snapshot(
            name, os.getpid(), tel, monitor, tasks=tasks_done,
            rows=rows_out, exec_s=exec_s_total,
            phases=profiling.phase_stats(), span_ring=span_ring,
            serving=(serving_plane.stats()
                     if serving_plane is not None else None))
    conn.send(("final", worker_id, snapshot))
    conn.close()
