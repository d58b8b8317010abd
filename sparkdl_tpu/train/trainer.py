"""Trainer — the pjit training engine.

The reference's training path (SURVEY.md §3.3) collected data to the
driver and called keras ``model.fit`` locally; distributed training meant
Horovod's NCCL ring (§3.5). Here one jitted train step does forward,
backward, all-reduce and update in a single XLA program:

- with a mesh: batch arrays are sharded over the ``data`` axis, state is
  replicated — XLA emits the gradient all-reduce over ICI/DCN from those
  shardings (the HorovodRunner-parity layout, no NCCL);
- state buffers are donated, so params/opt_state update in place in HBM;
- models with mutable normalization state (Flax ``batch_stats``) update it
  in the same program; stateless models (ingested Keras DAGs) skip it.
"""

from __future__ import annotations

import math
import sys
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from sparkdl_tpu.core import (
    batching,
    health,
    pipeline,
    profiling,
    resilience,
    telemetry,
)
from sparkdl_tpu.core.mesh import batch_sharding, replicated
from sparkdl_tpu.train.checkpoint import CheckpointManager
from sparkdl_tpu.train.metrics import MetricsLogger
from sparkdl_tpu.train.optimizers import (
    accuracy_metric,
    make_loss,
    make_optimizer,
)


def _first_launch_spanned(jitted: Callable, name: str) -> Callable:
    """``jitted(state, *batch)`` with ``sparkdl.compile`` around the first
    launch of each batch shape — where JAX traces, lowers and compiles (or
    retrieves) synchronously inside the call. The shapes seen ride on the
    jitted function itself, so a step shared through a ``step_cache`` is
    spanned once over all its fits; a warm step pays one set lookup on
    its batch's shapes."""
    seen = jitted.__dict__.setdefault("_sparkdl_seen_shapes", set())

    def step(state, *batch):
        key = tuple((b.shape, b.dtype) for b in batch)
        if key in seen:
            return jitted(state, *batch)
        with profiling.compile_span(model=name, shapes=repr(key)):
            out = jitted(state, *batch)
        seen.add(key)
        return out

    return step


class TrainState(struct.PyTreeNode):
    """Full training state — everything checkpoint/resume needs (§5.4)."""

    step: jax.Array
    params: Any
    opt_state: Any
    model_state: Any  # e.g. {'batch_stats': ...}; {} when stateless
    rng: jax.Array


@dataclass
class Trainer:
    """Builds and runs the jitted train step for one model.

    ``apply_fn(variables, x, train, rngs) -> out | (out, new_model_state)``
    where ``variables = {'params': ..., **model_state}``. Use the
    constructors ``from_flax`` / ``from_model_function`` instead of filling
    this in by hand.
    """

    apply_fn: Callable
    loss: Callable
    optimizer: optax.GradientTransformation
    mesh: Any = None
    has_model_state: bool = False
    compute_accuracy: bool = True
    accuracy_from_logits: bool = False
    # Mixed precision (keras mixed_precision parity, TPU-native form):
    # forward/backward run in this dtype (bf16 keeps f32's exponent range,
    # so no loss scaling is needed on TPU) while master params, optimizer
    # state and the update stay float32. None = full precision.
    #
    # NOTE on gradient checkpointing: a Trainer-level whole-model
    # jax.checkpoint was tried and REMOVED — one monolithic checkpoint
    # does not reduce peak HBM (the backward's recompute materializes the
    # same residual set before transposing; it only adds ~1 forward of
    # FLOPs). Memory-bound models should use flax ``nn.remat`` on block
    # boundaries inside the module definition, which the Trainer runs
    # unchanged.
    compute_dtype: Any = None
    # Optional shared compiled-step cache (from_model_function wires it to
    # the ModelFunction): repeated fits of the same model — HPO maps,
    # repeated estimator.fit — reuse ONE jitted step instead of paying the
    # compile each time. Safe because the step closes over no
    # fit-specific values: params/opt_state arrive via TrainState and the
    # learning rate is an opt_state hyperparam (make_optimizer injects it).
    step_cache: Any = None
    step_cache_key: Any = None

    # -- constructors --------------------------------------------------------

    @classmethod
    def from_flax(cls, module, variables: Dict[str, Any],
                  loss="categorical_crossentropy", optimizer="adam",
                  learning_rate: Optional[float] = None, mesh=None,
                  from_logits: bool = False, **kwargs) -> Tuple["Trainer", TrainState]:
        """Flax module + variables → (trainer, initial state).

        Mutable collections (``batch_stats``) train properly: they update
        inside the same XLA program as the gradient step.
        """
        variables = dict(variables)
        params = variables.pop("params")
        model_state = variables  # batch_stats etc (may be empty)
        mutable_keys = sorted(model_state)

        def apply_fn(vs, x, train, rngs):
            if train and mutable_keys:
                out, updates = module.apply(vs, x, train=True,
                                            mutable=mutable_keys, rngs=rngs)
                return out, updates
            return module.apply(vs, x, train=train, rngs=rngs)

        with profiling.model_build(type(module).__name__) as span:
            trainer = cls(apply_fn=apply_fn,
                          loss=make_loss(loss, from_logits=from_logits),
                          optimizer=make_optimizer(optimizer, learning_rate),
                          mesh=mesh, has_model_state=bool(mutable_keys),
                          accuracy_from_logits=from_logits, **kwargs)
            state = trainer.init_state(params, model_state)
            span.set_attribute("bytes", batching.tree_nbytes(state))
        return trainer, state

    @classmethod
    def from_model_function(cls, mf, loss="categorical_crossentropy",
                            optimizer="adam",
                            learning_rate: Optional[float] = None, mesh=None,
                            from_logits: bool = False,
                            **kwargs) -> Tuple["Trainer", TrainState]:
        """ModelFunction (e.g. an ingested Keras DAG) → (trainer, state).

        The model runs in inference form during training (normalization
        uses stored moving stats — fine-tune semantics). Weights the
        ingestion marked non-trainable (``mf.trainable_mask``, e.g. Keras
        BatchNorm moving stats) are frozen so their gradients through the
        inference-mode forward are never applied.
        """
        if isinstance(mf.input_spec, dict):
            raise ValueError(
                f"Model {mf.name!r} has multiple named inputs; the Trainer "
                "trains single-input models — serve multi-IO models via "
                "TPUTransformer inputMapping/outputMapping instead")

        def apply_fn(vs, x, train, rngs):
            out = mf.apply_fn(vs["params"], x)
            if isinstance(out, dict):
                raise ValueError(
                    f"Model {mf.name!r} returns multiple named outputs; "
                    "the Trainer's loss needs a single output head")
            return out

        tx = make_optimizer(optimizer, learning_rate)
        mask = getattr(mf, "trainable_mask", None)
        if mask is not None and not all(jax.tree.leaves(mask)):
            labels = jax.tree.map(lambda t: "train" if t else "freeze", mask)
            tx = optax.multi_transform(
                {"train": tx, "freeze": optax.set_to_zero()}, labels)
        cache = cache_key = None
        if isinstance(loss, str) and isinstance(optimizer, str):
            # lr is NOT part of the key: it's an injected opt_state
            # hyperparam, so one compiled step serves every lr. EVERY
            # other Trainer option (compute_accuracy, compute_dtype, ...)
            # changes the compiled program, so all kwargs key the cache —
            # any unhashable option value disables caching rather than
            # risking a stale step.
            try:
                cache_key = (loss, optimizer, from_logits, mesh,
                             tuple(sorted(
                                 (k, str(v)) for k, v in kwargs.items())))
                hash(cache_key)
            except TypeError:
                cache_key = None
            if cache_key is not None:
                cache = mf.__dict__.setdefault("_train_step_cache", {})
        with profiling.model_build(mf.name) as span:
            trainer = cls(apply_fn=apply_fn,
                          loss=make_loss(loss, from_logits=from_logits),
                          optimizer=tx, mesh=mesh, has_model_state=False,
                          accuracy_from_logits=from_logits,
                          step_cache=cache, step_cache_key=cache_key,
                          **kwargs)
            state = trainer.init_state(mf.variables, {})
            span.set_attribute("bytes", batching.tree_nbytes(state))
        return trainer, state

    # -- state ---------------------------------------------------------------

    def init_state(self, params, model_state=None, seed: int = 0) -> TrainState:
        # Own fresh copies: the train step donates state buffers (in-place
        # HBM update), which deletes them — caller-supplied arrays must
        # survive (e.g. two trainers initialized from the same variables).
        params = jax.tree.map(jnp.array, params)
        model_state = jax.tree.map(jnp.array, model_state or {})
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=self.optimizer.init(params),
            model_state=model_state,
            rng=jax.random.PRNGKey(seed))

    # -- the step ------------------------------------------------------------

    def make_train_step(self, donate: bool = True) -> Callable:
        """Compiled ``(state, x, y) -> (state, metrics)``.

        With a shared ``step_cache`` (from_model_function), the jitted
        step is built once per (loss, optimizer, mesh, dtype, donate) and
        reused by every subsequent fit of the same ModelFunction.

        One XLA program: forward, loss, backward, (implicit all-reduce),
        optimizer update, model-state update. With a mesh, x/y shard over
        ``data`` and state is replicated; XLA inserts the collectives.
        """
        if self.step_cache is not None:
            cached = self.step_cache.get((self.step_cache_key, donate))
            if cached is not None:
                return cached
        loss_fn = self.loss
        apply_fn = self.apply_fn
        optimizer = self.optimizer
        has_state = self.has_model_state
        want_acc = self.compute_accuracy
        acc_from_logits = self.accuracy_from_logits
        compute_dtype = (jnp.dtype(self.compute_dtype)
                         if self.compute_dtype is not None else None)

        def to_compute(tree):
            return jax.tree.map(
                lambda a: a.astype(compute_dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

        def to_master(tree, like):
            return jax.tree.map(
                lambda a, m: a.astype(m.dtype), tree, like)

        def step_fn(state: TrainState, x, y):
            rng, step_rng = jax.random.split(state.rng)
            rngs = {"dropout": step_rng}

            def compute_loss(params):
                # model_state (e.g. BatchNorm running stats) deliberately
                # stays f32 under mixed precision: the moving-average
                # update old*m + batch*(1-m) underflows bf16's 8-bit
                # mantissa for small increments and the stats would stall
                # (keras mixed_precision keeps BN state f32 for the same
                # reason)
                model_state = state.model_state
                if compute_dtype is not None:
                    params = to_compute(params)
                    xc = to_compute(x)
                else:
                    xc = x
                vs = {"params": params, **model_state}
                res = apply_fn(vs, xc, True, rngs)
                if has_state:
                    out, new_model_state = res
                else:
                    out, new_model_state = res, state.model_state
                # loss in f32 regardless: reductions over many bf16 terms
                # lose precision
                return loss_fn(out.astype(jnp.float32), y), (out, new_model_state)

            grad_fn = jax.value_and_grad(compute_loss, has_aux=True)
            (loss, (out, new_model_state)), grads = grad_fn(state.params)
            if compute_dtype is not None:
                # value_and_grad already returns f32 grads (the cast is in
                # the graph); this is a defensive no-op. Model-state leaves
                # a model computes in low precision get restored to master
                # dtype.
                grads = to_master(grads, state.params)
                new_model_state = to_master(new_model_state,
                                            state.model_state)
            updates, new_opt_state = optimizer.update(grads, state.opt_state,
                                                      state.params)
            new_params = optax.apply_updates(state.params, updates)
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   opt_state=new_opt_state,
                                   model_state=new_model_state, rng=rng)
            metrics = {"loss": loss}
            if want_acc and out.ndim >= 2:
                metrics["accuracy"] = accuracy_metric(
                    out, y, from_logits=acc_from_logits)
            return new_state, metrics

        kwargs: Dict[str, Any] = {"donate_argnums": (0,)} if donate else {}
        if self.mesh is None:
            jitted = jax.jit(step_fn, **kwargs)
        else:
            data_sh = batch_sharding(self.mesh)
            # state sharding None = keep as placed (replicated by
            # fit/device_put); batch sharded over data → XLA all-reduces
            # grads across the axis.
            jitted = jax.jit(step_fn, in_shardings=(None, data_sh, data_sh),
                             **kwargs)
        if self.step_cache is not None:
            self.step_cache[(self.step_cache_key, donate)] = jitted
        return jitted

    def make_eval_step(self) -> Callable:
        apply_fn = self.apply_fn

        def eval_fn(state: TrainState, x):
            vs = {"params": state.params, **state.model_state}
            return apply_fn(vs, x, False, None)

        if self.mesh is None:
            return jax.jit(eval_fn)
        data_sh = batch_sharding(self.mesh)
        return jax.jit(eval_fn, in_shardings=(None, data_sh),
                       out_shardings=data_sh)

    def make_eval_metrics_step(self) -> Callable:
        """Compiled ``(state, x, y) -> {loss, accuracy}`` (no grads).

        Deliberately jitted WITHOUT batch in_shardings even under a mesh:
        validation sets are small and arbitrarily sized, and a
        data-sharded eval step would reject any batch not divisible by
        the data axis. GSPMD propagates shardings from the (replicated)
        state; exact metrics beat parallel evaluation here.
        """
        if self.step_cache is not None:
            cached = self.step_cache.get((self.step_cache_key, "eval"))
            if cached is not None:
                return cached
        own = self.__dict__.get("_eval_step")
        if own is not None:
            return own
        apply_fn = self.apply_fn
        loss_fn = self.loss
        want_acc = self.compute_accuracy
        acc_from_logits = self.accuracy_from_logits

        def eval_fn(state: TrainState, x, y):
            vs = {"params": state.params, **state.model_state}
            out = apply_fn(vs, x, False, None)
            metrics = {"loss": loss_fn(out, y)}
            if want_acc:
                metrics["accuracy"] = accuracy_metric(
                    out, y, from_logits=acc_from_logits)
            return metrics

        jitted = jax.jit(eval_fn)
        if self.step_cache is not None:
            self.step_cache[(self.step_cache_key, "eval")] = jitted
        else:
            # no shared cache (custom loss/optimizer objects): memoize on
            # this Trainer so per-epoch evaluate() doesn't recompile
            self.__dict__["_eval_step"] = jitted
        return jitted

    def evaluate(self, state: TrainState,
                 batches: Iterable[Tuple[np.ndarray, np.ndarray]]
                 ) -> Dict[str, float]:
        """Mean loss/accuracy over a batch stream (keras ``evaluate``).

        Multi-host (VERDICT r4 #7): training state is replicated, so every
        host holds a full copy — pull it host-local and evaluate the
        (host-identical) validation batches as a purely LOCAL computation.
        Every process reports metrics EXACTLY equal to a single-process
        evaluation; no collectives, no divisibility constraints on the
        validation batch size.
        """
        if jax.process_count() > 1:
            try:
                state = jax.tree.map(
                    lambda a: np.asarray(jax.device_get(a)), state)
            except RuntimeError as e:
                raise NotImplementedError(
                    "multi-host evaluate requires fully-replicated train "
                    f"state (every host must hold a full copy): {e}") from e
        eval_step = _first_launch_spanned(self.make_eval_metrics_step(),
                                          "eval_metrics_step")
        totals: Dict[str, float] = {}
        n = 0
        for x, y in batches:
            xd = jnp.asarray(np.asarray(x))
            if xd.dtype == jnp.uint8:  # same contract as stage_batch
                xd = xd.astype(jnp.float32)
            out = eval_step(state, xd, jnp.asarray(np.asarray(y)))
            first_launch = profiling.first_launch_wait()
            m = jax.device_get(out)
            profiling.first_launch_done(first_launch)
            k = len(x)
            n += k
            for key, value in m.items():
                totals[key] = totals.get(key, 0.0) + float(value) * k
        if n == 0:
            return {}
        return {f"val_{k}": v / n for k, v in totals.items()}

    # -- the loop ------------------------------------------------------------

    def fit(self, state: TrainState,
            batches: Iterable[Tuple[np.ndarray, np.ndarray]],
            epochs: int = 1,
            metrics_logger: Optional[MetricsLogger] = None,
            checkpoint: Optional[CheckpointManager] = None,
            checkpoint_every: int = 0,
            resume: bool = True,
            on_step: Optional[Callable[[int], None]] = None,
            on_epoch: Optional[Callable[[int, TrainState], None]] = None,
            sync_every: int = 8,
            prefetch: int = 2) -> TrainState:
        """Run the pipelined train loop; resume from the latest checkpoint.

        ``batches``: a reiterable of ``(x, y)`` numpy pairs (all the same
        shape — pad or drop the remainder upstream; static shapes keep one
        compiled program). ``on_step(step)`` is the fault-injection hook
        (SURVEY.md §5.3): raising from it aborts the loop exactly as a
        worker loss would, and TPURunner restarts from the checkpoint.
        ``on_epoch(epoch_index, state)`` fires after each epoch (the
        estimator's validation-evaluation hook).

        Async input pipeline (ISSUE 3, docs/PERF.md): host pull + decode +
        staging for batch ``k+1`` runs on a background thread
        (``core.pipeline.DevicePrefetcher``, ``prefetch`` staged batches
        deep; 0 = inline serial staging) while the device trains batch
        ``k``, and the loop never blocks on the device per step — the
        step counter is tracked on the HOST (the device chain is
        deterministic, so they agree) and the device is only awaited at
        the designated sync points: every ``sync_every`` steps, at
        checkpoint writes, before each ``on_step`` call (so the hook's
        contract — "the step has completed" — survives), and at epoch
        boundaries. Per-step metrics defer on device and materialize at
        sync points (``MetricsLogger.flush``). Batch values, order, RNG
        chain and donation semantics are untouched, so a pipelined fit is
        bit-identical to the serial loop, and exact resume still replays
        to the precise next batch (skipped positions are never staged).
        ``sync_every`` also bounds in-flight device work (each unsynced
        step holds its staged batch alive): raise it to hide slow hosts
        deeper, lower it to cap device memory and tighten failure
        detection latency.
        """
        if checkpoint is not None and resume:
            latest = checkpoint.latest_step()
            if latest is not None:
                state = checkpoint.restore(state)
                state = jax.tree.map(jnp.asarray, state)
                health.record(health.FIT_RESUMED, step=int(state.step))
        train_step = _first_launch_spanned(self.make_train_step(),
                                           "train_step")
        multihost = self.mesh is not None and jax.process_count() > 1
        if jax.process_count() > 1:
            # Multi-process: force inline staging. The batch source may run
            # per-batch collectives (the streaming estimator's lockstep
            # allgather) and stage_batch assembles global arrays — enqueued
            # from a staging thread they would interleave with the main
            # thread's train-step collectives in a scheduler-dependent
            # order that can DIVERGE across processes and hang the gang.
            # One thread per process keeps every host's collective order
            # identical to the serial loop's; deferred step sync (the
            # host-side win) still applies.
            prefetch = 0
        if self.mesh is not None:
            state = jax.device_put(state, replicated(self.mesh))

        def stage_batch(arr):
            """Host batch → device array sharded over ``data``.

            uint8 batches (decoded images) transfer raw and cast to f32
            ON DEVICE — 4x less host→device traffic than casting on the
            host (the cast is exact for 0-255 integers). Multi-host
            (SURVEY.md §5.8, HorovodRunner parity): every process passes
            its LOCAL rows; the global array is assembled from the
            process-local shards — the per-host input feeding the
            reference achieved with one Spark partition per worker.
            """
            arr = np.asarray(arr)
            if multihost:
                sharding = batch_sharding(self.mesh, arr.ndim)
                out = jax.make_array_from_process_local_data(sharding, arr)
            else:
                out = jnp.asarray(arr)
            if out.dtype == jnp.uint8:
                out = out.astype(jnp.float32)
            return out

        def stage_pair(pair):
            """Staging-thread stage: host (x, y) → (n_examples, xd, yd)."""
            x, y = pair
            with profiling.annotate(profiling.STAGE_BATCH):
                return len(x), stage_batch(x), stage_batch(y)

        # Exact resume: the loop replays the (deterministic) batch stream and
        # skips the first `state.step` positions — mid-epoch restarts land on
        # the precise next batch.
        done = int(state.step)
        host_step = done
        global_idx = 0
        sync_every = max(1, int(sync_every))
        last_sync_t: Optional[float] = None
        last_sync_step = done

        def sync(st: TrainState) -> None:
            """Designated sync point — the ONLY place the step loop blocks
            on the device (enforced by the AST lint in
            tests/test_taxonomy_lint.py). Drains deferred metrics (one
            batched fetch), then barriers on the device step counter — a
            scalar fetch, the barrier bench.py uses too (core/profiling.py).
            The sync window also feeds the telemetry steps/sec histogram:
            steps COMPLETED (barriered) per wall second, the honest
            throughput number the deferred pipeline obscures per step.
            """
            nonlocal last_sync_t, last_sync_step
            # where the step that compiled is awaited, the wait is set-up's
            first_launch = profiling.first_launch_wait()
            if metrics_logger is not None:
                metrics_logger.flush()
            with profiling.annotate(profiling.DEVICE_SYNC):
                device_step = int(st.step)
            profiling.first_launch_done(first_launch)
            if device_step != host_step:
                raise RuntimeError(
                    f"pipelined fit desynchronized: device step "
                    f"{device_step} != host-tracked step {host_step} — "
                    "the batch stream or state chain was tampered with "
                    "mid-fit")
            now = time.perf_counter()
            if last_sync_t is not None and host_step > last_sync_step:
                dt = now - last_sync_t
                if dt > 0:
                    telemetry.observe(telemetry.M_STEPS_PER_SEC,
                                      (host_step - last_sync_step) / dt)
            last_sync_t, last_sync_step = now, host_step

        def save_checkpoint(st: TrainState) -> None:
            with telemetry.span(telemetry.SPAN_CHECKPOINT_SAVE,
                                step=host_step):
                checkpoint.save(host_step, jax.device_get(st))

        def epoch_source():
            # runs on the staging thread: resume-skipped positions are
            # counted but never staged (no wasted device_put on replay)
            nonlocal global_idx
            for pair in batches:
                if global_idx < done:
                    global_idx += 1
                    continue
                global_idx += 1
                yield pair

        # Telemetry (docs/OBSERVABILITY.md): the fit span is the parent
        # of every epoch/step span on this thread AND — via the
        # prefetcher's context handoff — of the staging thread's
        # stage_batch/decode spans, so one run trace covers both sides
        # of the pipeline. Step timing below is HOST dispatch interval
        # (perf_counter only — telemetry must never sync the device; the
        # step-loop AST lint enforces it).
        fit_span = telemetry.span(telemetry.SPAN_FIT, epochs=epochs,
                                  resume_step=done, prefetch=prefetch,
                                  sync_every=sync_every)
        last_dispatch = None
        try:
            fit_span.__enter__()
            for _epoch in range(epochs):
                with telemetry.span(telemetry.SPAN_EPOCH, epoch=_epoch), \
                        pipeline.DevicePrefetcher(
                        epoch_source(), stage_fn=stage_pair,
                        depth=prefetch, name="trainer.fit",
                        report_health=True) as staged:
                    for n_examples, xd, yd in staged:
                        # dispatch only — execution is awaited at sync
                        # points (DEVICE_SYNC carries the blocking time)
                        with profiling.annotate("sparkdl.train_step",
                                                step=host_step + 1):
                            state, metrics = train_step(state, xd, yd)
                        host_step += 1
                        now = time.perf_counter()
                        if last_dispatch is not None:
                            telemetry.observe(telemetry.M_STEP_TIME_S,
                                              now - last_dispatch)
                        last_dispatch = now
                        if metrics_logger is not None:
                            metrics_logger.log_step(host_step, metrics,
                                                    examples=n_examples,
                                                    defer=True)
                        due_ckpt = (checkpoint is not None and
                                    checkpoint_every and
                                    host_step % checkpoint_every == 0)
                        if (due_ckpt or on_step is not None
                                or host_step % sync_every == 0):
                            sync(state)
                        if due_ckpt:
                            save_checkpoint(state)
                        if on_step is not None:
                            on_step(host_step)
                        # Injection point AFTER the checkpoint write: a
                        # preemption here models losing the gang between
                        # steps — TPURunner classifies it retryable,
                        # restarts, and this loop's resume path replays
                        # from the step just saved (SURVEY.md §5.3).
                        resilience.inject("preemption", step=host_step)
                # epoch boundary is a designated sync point: on_epoch
                # observes a fully-materialized state and complete metrics
                sync(state)
                if on_epoch is not None:
                    on_epoch(_epoch, state)
        except BaseException:
            # The gang is dying with async checkpoint writes possibly in
            # flight. Flush them before unwinding so (a) the restarted
            # attempt's latest_step() sees every step this attempt
            # completed (no redone work) and (b) an abandoned async write
            # can't race the restart's save of the same step. Deferred
            # metrics flush best-effort (their steps may be the ones that
            # failed); the staging thread is already closed by the
            # prefetcher's context manager.
            if metrics_logger is not None:
                try:
                    metrics_logger.flush()
                except Exception:  # noqa: BLE001 - already unwinding
                    pass
            if checkpoint is not None:
                try:
                    checkpoint.wait_until_finished()
                except Exception:  # noqa: BLE001 - already unwinding
                    pass
            fit_span.__exit__(*sys.exc_info())
            raise
        try:
            if checkpoint is not None:
                checkpoint.save(host_step, jax.device_get(state),
                                synchronous=True)
            health.record(health.FIT_COMPLETED, steps=host_step)
            fit_span.set_attribute("steps", host_step)
        except BaseException:
            # the final synchronous save can fail too (disk full, bad
            # path) — the span must still close, or it leaks on the
            # thread-local stack and adopts every later span
            fit_span.__exit__(*sys.exc_info())
            raise
        fit_span.__exit__(None, None, None)
        return state

    def variables_of(self, state: TrainState) -> Dict[str, Any]:
        """Variables dict for inference from a trained state."""
        return {"params": state.params, **state.model_state}
