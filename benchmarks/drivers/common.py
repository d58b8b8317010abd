"""What the drivers share: the seed's PRNG key, the compile meter, the
program's telemetry over a traced window, and the plain references' entry
points (forward in blocks, loss and gradient)."""

import time

import numpy as np


def prng_key(seed):
    """A JAX key from any whole number up to 2**63 (seeds pass 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                              int(seed) >> 31)


class CompileMeter:
    """Backend compiles JAX made, and the seconds they took, from JAX's own
    monitoring events. A persistent-cache hit still counts as one (the
    program was not in memory): inside a measured window there must be
    none of either."""

    def __init__(self):
        import jax.monitoring

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += seconds


class ProgramTelemetry:
    """The program's telemetry scope (``core/telemetry.py``) over the window
    of a traced run; a no-op otherwise. After the ``with``: ``snapshot``
    (counters and histograms) and ``spans`` — the program's ``sparkdl.*``
    spans as ``[name, start_ns, dur_ns]`` on ``time.perf_counter_ns``. The
    program records spans against an epoch of its own; a span of the
    benchmark's, opened at a known time, gives the offset."""

    ALIGN = "bench.align"

    def __init__(self, enabled):
        self.enabled = enabled
        self.snapshot, self.spans = None, []

    def __enter__(self):
        if self.enabled:
            from sparkdl_tpu.core import telemetry

            self.scope = telemetry.Telemetry(name="bench", out_dir="")
            self.scope.__enter__()
            self._opened_ns = time.perf_counter_ns()
            with telemetry.span(self.ALIGN):
                pass
        return self

    def __exit__(self, *exc):
        if self.enabled:
            self.snapshot = self.scope.metrics.snapshot()
            recorded = self.scope.tracer.spans()
            self.scope.__exit__(*exc)
            offset = self._opened_ns - next(
                s["start_ns"] for s in recorded if s["name"] == self.ALIGN)
            self.spans = [[s["name"], s["start_ns"] + offset,
                           s["end_ns"] - s["start_ns"]] for s in recorded
                          if s["name"].startswith("sparkdl.")
                          and s["name"] != "sparkdl.run"]
        return False


def program_readings(program, meter, compiles_before):
    """What both drivers read of the program over the window: its phase
    timers, its telemetry and spans, and the compiles JAX made."""
    from sparkdl_tpu.core import profiling

    phases = profiling.phase_stats()
    return {"phases": phases,
            "phase_s": {k: v["total_s"] for k, v in phases.items()},
            "telemetry": program.snapshot, "spans": program.spans,
            "compiles_in_window": meter.count - compiles_before}


def reference_features(reference, variables, pixels, block, quant=None):
    """The plain reference over uint8 ``pixels``: float32, full matrix
    precision, one jitted program over blocks of ``block`` rows."""
    import jax
    import jax.numpy as jnp

    from references import plain

    def forward(vs, x):
        x = reference.preprocess(x.astype(jnp.float32))
        return reference.forward(plain.Scope.apply(vs, quant=quant), x,
                                 include_top=False)

    outs = []
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(forward)
        for lo in range(0, len(pixels), block):
            chunk = pixels[lo:lo + block]
            pad = block - len(chunk)
            if pad:
                chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad, 0)])
            outs.append(np.asarray(fn(variables, chunk))[:block - pad])
    return np.concatenate(outs)


def reference_training(reference, variables, batches, steps, learning_rate,
                       quant=None):
    """The plain reference of ``steps`` SGD steps from ``variables``
    (``{"params", "batch_stats"}``) over ``batches[:steps]``: a float32
    ``jax.value_and_grad`` of the plain forward in training mode (batch
    statistics), Keras's categorical cross-entropy on the softmax, and
    ``p ← p − lr·g``. Returns host ``(losses, first gradient, params after
    the last step)``."""
    import jax
    import jax.numpy as jnp

    from references import plain

    def loss_fn(params, stats, x, y):
        scope = plain.Scope.apply({"params": params, "batch_stats": stats},
                                  train=True, quant=quant)
        logits = reference.forward(scope, x, include_top=True)
        probs = jnp.clip(jax.nn.softmax(logits), 1e-7, 1 - 1e-7)
        return -jnp.mean(jnp.sum(y * jnp.log(probs), axis=-1)), \
            scope.new_stats

    def step(params, stats, x, y):
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, stats, x, y)
        new = jax.tree.map(lambda p, g: p - learning_rate * g, params, grads)
        return loss, grads, new, stats

    params, stats = variables["params"], variables.get("batch_stats", {})
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(step)
        for x, y in batches[:steps]:
            loss, grads, params, stats = fn(params, stats, x, y)
            losses.append(float(loss))
            if first_grad is None:
                first_grad = jax.device_get(grads)
            del grads
    return losses, first_grad, jax.device_get(params)
