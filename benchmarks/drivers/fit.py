"""Driver ``fit``: one ``Trainer.fit(state, batches, epochs=1)`` over a
reiterable that cycles the cell's seeded batches until ``--seconds``.

Set-up builds ONE trainer with its compiled step and state, drives it through
its first ``check_steps`` steps with ``fit`` itself (the window's own call and
feed), keeps the parameters before, after one and after all of them on the
host, and hands the same trainer and state to the window. The trainer is
given a ``step_cache`` (an option of the program's own) so that every ``fit``
call reuses the one compiled step instead of tracing it anew.
"""

import gc
import time

import numpy as np

import check
import flops
import harness
import traffic
from drivers import common


class Feed:
    """Reiterable over ``batches``, cycling; an iteration ends after
    ``limit`` positions or once ``deadline`` (perf_counter) has passed."""

    def __init__(self, batches, limit=None, deadline=None):
        self.batches, self.limit, self.deadline = batches, limit, deadline

    def __iter__(self):
        i = 0
        while (self.limit is None or i < self.limit) and (
                self.deadline is None or time.perf_counter() < self.deadline):
            yield self.batches[i % len(self.batches)]
            i += 1


class Driver:
    def __init__(self, cell, seed, env):
        self.cell, self.seed, self.env = cell, seed, env
        self.config, self.traffic = cell.config, cell.traffic
        self.reference = harness.by_name("references",
                                         self.config["reference"],
                                         env["root"])

    def setup(self):
        import jax

        import sparkdl_tpu  # noqa: F401  (places the compile cache)
        from sparkdl_tpu.models import registry
        from sparkdl_tpu.train import Trainer
        from sparkdl_tpu.train.metrics import MetricsLogger

        self.meter = common.CompileMeter()
        self.batches = traffic.train_batches(self.traffic, self.seed)
        self.variables = jax.jit(
            lambda key: flops.init_variables(self.reference, key, True))(
                common.prng_key(self.seed))
        spec = registry.get_model_spec(self.config["model"])
        module = spec.builder(include_top=True, classes=spec.classes)
        self.trainer, state = Trainer.from_flax(
            module, {k: v for k, v in self.variables.items() if v}, loss=self.config["loss"],
            optimizer=self.config["optimizer"],
            learning_rate=self.config["learning_rate"],
            compute_dtype=self.config["compute_dtype"],
            step_cache={}, step_cache_key="bench")
        self.steps = self.traffic["check_steps"]
        log = MetricsLogger(sinks=[lambda record: None])   # history only
        self.params = [jax.device_get(state.params)]
        for upto in (1, self.steps):
            state = self.trainer.fit(state, Feed(self.batches, limit=upto),
                                     epochs=1, metrics_logger=log)
            self.params.append(jax.device_get(state.params))
        self.losses = [float(r["loss"]) for r in log.history]
        self.state = state

    def measure(self, seconds, tracer):
        import jax

        from sparkdl_tpu.core import profiling

        batch = self.traffic["batch"]
        profiling.reset_phase_stats()
        compiles = self.meter.count
        first = int(self.state.step)
        tracer.after(self.traffic.get("trace_delay", 3.0),
                     self.traffic.get("trace_seconds", 3.0))
        t0 = time.perf_counter()
        with common.ProgramTelemetry(tracer.enabled) as program:
            state = self.trainer.fit(
                self.state, Feed(self.batches, deadline=t0 + seconds),
                epochs=1)
            jax.block_until_ready(state)
            elapsed = time.perf_counter() - t0
        self.state = state
        steps = int(state.step) - first
        moved = not all(np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(jax.device_get(state.params)),
            jax.tree.leaves(self.params[-1])))
        return {
            "seconds": elapsed, "images": steps * batch, "steps": steps,
            "attempted": steps, "failed": 0 if moved and steps else steps,
            "rows_per_run": batch,
            # forward + backward = 3 × forward, nothing recomputed
            "flops_per_image": 3 * flops.forward_flops_per_image(
                self.reference, True),
            "end_to_end": {"fit_images_per_s": steps * batch / elapsed},
            **common.program_readings(program, self.meter, compiles),
        }

    def release(self):
        self.state = self.trainer = None
        gc.collect()

    def readings(self, losses, first_grad, last_params):
        """The numbers compared, from one side's losses, first gradient and
        parameters after the steps."""
        return {"losses": losses,
                "grad": check.leaf_norms(first_grad),
                "update": check.leaf_norms(check.tree_sub(last_params,
                                                          self.params[0]))}

    def program_readings(self):
        lr = self.config["learning_rate"]
        grad = {k: v / lr for k, v in check.leaf_norms(check.tree_sub(
            self.params[0], self.params[1])).items()}
        return {"losses": self.losses, "grad": grad,
                "update": check.leaf_norms(check.tree_sub(self.params[-1],
                                                          self.params[0]))}

    def reference_readings(self, quant=None):
        return self.readings(*common.reference_training(
            self.reference, self.variables, self.batches, self.steps,
            self.config["learning_rate"], quant=quant))

    @staticmethod
    def numbers(got, want):
        """The numbers compared, and where the worst leaves are. The worst
        leaf's gap catches a leaf that did not move or moved double (it
        reads 1); the median leaf's gap is steady from seed to seed and is
        what a coarser precision moves (PERF.md §2). The losses' gap has no
        upper reading and is not compared; it is given with the leaves."""
        keep = check.moving_leaves(want["grad"])
        grad_worst, grad_leaf, grad_median = check.worst_and_median(
            check.leaf_gaps(got["grad"], want["grad"], keep))
        update_worst, update_leaf, update_median = check.worst_and_median(
            check.leaf_gaps(got["update"], want["update"], keep))
        loss_gap = max(abs(a - b) / abs(b)
                       for a, b in zip(got["losses"], want["losses"])) \
            if len(got["losses"]) == len(want["losses"]) else float("inf")
        return ({"grad_median_gap": grad_median,
                 "update_median_gap": update_median,
                 "grad_norm_gap": grad_worst,
                 "update_norm_gap": update_worst},
                {"grad_leaf": grad_leaf, "update_leaf": update_leaf,
                 "left_out": len(want["grad"]) - len(keep),
                 "loss_gap": loss_gap})

    def check(self):
        numbers, _ = self.numbers(self.program_readings(),
                                  self.reference_readings())
        return check.decide(numbers, self.cell.workload["limits"])
