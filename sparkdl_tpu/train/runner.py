"""TPURunner — HorovodRunner-parity distributed training entry point.

Parity (SURVEY.md §3.5): ``HorovodRunner(np=N).run(train_fn)`` launched a
Spark barrier-mode gang, MPI ranks, and a NCCL ring. On TPU the whole
apparatus collapses: ``jax.distributed.initialize`` joins the per-host
processes (multi-host), the device mesh spans all chips, and the train
step's shardings make XLA emit the all-reduce over ICI/DCN. What survives
is the *runner* contract:

- ``TPURunner(np=N).run(train_fn, **kwargs)`` builds an N-chip ``data``
  mesh and calls ``train_fn(mesh=mesh, **kwargs)``;
- gang failure semantics (§5.3): if ``train_fn`` raises, the runner
  restarts it up to ``max_restarts`` times — train fns that checkpoint
  via Trainer.fit resume from the last saved step, reproducing barrier
  mode's "fail the gang, rerun" with far less lost work.
"""

from __future__ import annotations

import inspect
import logging
import os
import time
from typing import Any, Callable, Optional

import jax

from sparkdl_tpu.core import health, resilience, telemetry
from sparkdl_tpu.core.mesh import MeshConfig, make_mesh

logger = logging.getLogger(__name__)


def maybe_initialize_distributed() -> bool:
    """Join the multi-host process group when coordinator env vars are set.

    Single-host (this environment) is a no-op. Multi-host: set
    ``SPARKDL_COORDINATOR``, ``SPARKDL_NUM_PROCESSES``,
    ``SPARKDL_PROCESS_ID`` (the jax.distributed triple) on every host.
    """
    coordinator = os.environ.get("SPARKDL_COORDINATOR")
    if not coordinator:
        return False
    # nothing here may touch jax.devices()/default_backend(): that would
    # initialize the backend, which initialize() forbids (CPU gangs need
    # no help — gloo is the installed JAX's default CPU collective)
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(os.environ["SPARKDL_NUM_PROCESSES"]),
        process_id=int(os.environ["SPARKDL_PROCESS_ID"]))
    return True


class TPURunner:
    """Run a training function over an ``np``-device data-parallel mesh.

    Restart semantics (core.resilience): a failed ``main`` is classified —
    only RETRYABLE errors (preemption, transient runtime errors — the
    gang-failure class) restart, up to ``max_restarts`` times with
    exponential backoff and deterministic jitter instead of a fixed
    delay. FATAL errors (shape/dtype/``ValueError``: deterministic, a
    restart replays them) and OOM (a same-shape replay reproduces it;
    the batch-shrink response lives in core.batching, not here) raise
    immediately with zero restart attempts. Train fns that
    checkpoint via ``Trainer.fit(checkpoint=...)`` resume from
    ``CheckpointManager.latest_step()``, not step 0.

    ``retry_policy`` overrides the backoff schedule; when omitted, one is
    built from ``restart_delay_s`` (kept as the base delay for
    compatibility with the original fixed-delay API).
    """

    def __init__(self, np: int = -1, max_restarts: int = 0,
                 restart_delay_s: float = 0.0,
                 mesh_config: Optional[MeshConfig] = None,
                 retry_policy: Optional[resilience.RetryPolicy] = None
                 ) -> None:
        self.np = np
        self.max_restarts = max_restarts
        self.restart_delay_s = restart_delay_s
        self.mesh_config = mesh_config
        self.retry_policy = retry_policy or resilience.RetryPolicy(
            max_retries=max_restarts, base_delay_s=restart_delay_s,
            max_delay_s=max(restart_delay_s * 8, 60.0))

    def _build_mesh(self):
        maybe_initialize_distributed()
        if self.mesh_config is not None:
            return make_mesh(self.mesh_config)
        n = self.np if self.np != -1 else len(jax.devices())
        if n > len(jax.devices()):
            raise ValueError(
                f"np={n} but only {len(jax.devices())} devices visible")
        return make_mesh(MeshConfig(data=n), devices=jax.devices()[:n])

    def run(self, main: Callable, **kwargs) -> Any:
        """Call ``main`` with the mesh; restart on failure up to the cap.

        ``main`` receives ``mesh=`` iff its signature accepts it (keyword
        or **kwargs), matching HorovodRunner's convention of passing
        through user kwargs untouched.
        """
        mesh = self._build_mesh()
        sig = inspect.signature(main)
        accepts_mesh = ("mesh" in sig.parameters or any(
            p.kind is inspect.Parameter.VAR_KEYWORD
            for p in sig.parameters.values()))
        call_kwargs = dict(kwargs)
        if accepts_mesh:
            call_kwargs["mesh"] = mesh

        attempts = self.max_restarts + 1
        last_err: Optional[BaseException] = None
        for attempt in range(attempts):
            try:
                # telemetry: one span per gang attempt — the fit span
                # (and everything under it) nests here, so a restarted
                # run's trace shows attempt 1 vs attempt 2 side by side
                with telemetry.span(telemetry.SPAN_RUNNER_ATTEMPT,
                                    attempt=attempt):
                    return main(**call_kwargs)
            except Exception as e:  # noqa: BLE001 - gang boundary
                kind = resilience.classify(e)
                if kind != resilience.RETRYABLE:
                    # FATAL: deterministic — a restart replays it from the
                    # checkpoint and fails again. OOM: a same-shape replay
                    # reproduces it too, and the runner has no batch-shrink
                    # response (that lives in core.batching) — surface
                    # both unretried.
                    health.record(health.GANG_FATAL, kind=kind,
                                  error=type(e).__name__)
                    logger.error(
                        "TPURunner: attempt %d failed with a %s error "
                        "(%s: %s); not restarting", attempt + 1, kind,
                        type(e).__name__, e)
                    raise
                last_err = e
                if attempt + 1 < attempts:
                    delay = self.retry_policy.delay(attempt + 1)
                    health.record(health.GANG_RESTART, attempt=attempt + 1,
                                  error=type(e).__name__)
                    logger.warning(
                        "TPURunner: attempt %d/%d failed (%s: %s); "
                        "restarting in %.2fs", attempt + 1, attempts,
                        type(e).__name__, e, delay)
                    if delay > 0:
                        time.sleep(delay)
        health.record(health.GANG_FAILED, attempts=attempts,
                      error=type(last_err).__name__
                      if last_err is not None else None)
        raise RuntimeError(
            f"TPURunner: train fn failed after {attempts} attempts"
        ) from last_err
