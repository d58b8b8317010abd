"""Driver ``windows_ssm``: the ``windows`` driver for a model of the ``jamba``
family without expert layers — a pre-norm stack of state-space mixers with an
attention layer among them and a gated MLP after each. The passes, the
comparison's three numbers and the result line are ``drivers.windows``'s;
what differs is what names the other model — the count of a window's
operations (``flops_ssm``: the timed loop is ``windows``' with that count at
its end, as in ``windows_span``), the weights (a layer drawn with its mixer
given, the head tied), a warm-up and a check that ask for no expert counts
(the stack has no expert layer), and the reference's pass, which can leave
one thing of the model out (``control_ssm.py``)."""

import time

import numpy as np

import check
import flops_ssm
import token_traffic
from drivers import common, windows


class Driver(windows.Driver):
    def setup(self):
        from sparkdl_tpu.ml import DeepSequenceScorer
        from sparkdl_tpu.models import registry

        # a program older than the model: say so before 6 GB of weights
        if self.config["model"] not in registry.SEQUENCE_MODELS:
            raise SystemExit("windows_ssm driver: the program has no "
                             f"sequence model {self.config['model']!r}")
        if self.traffic["kind"] != "token_windows":
            raise SystemExit("windows_ssm driver: no traffic kind "
                             f"{self.traffic['kind']!r}")
        self.meter = common.CompileMeter()
        self.key = common.prng_key(self.seed)
        self.scorer = DeepSequenceScorer(
            inputCol="tokens", modelName=self.config["model"],
            weights=self.make_variables(), window=self.traffic["window"],
            batchSize=self.traffic["batch_size"])
        n, partitions = self.traffic["n"], self.traffic["partitions"]
        self.tokens = token_traffic.token_windows(self.traffic, self.seed)
        self.frame = self._frame(np.arange(n), partitions)
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 79])
        self.sample = np.sort(rng.choice(n, size=min(n, self.traffic[
            "check_rows"]), replace=False))
        # warm-up: one partition's rows launch the one shape every launch
        # has (batch_size windows)
        self.scorer.transform(self._frame(np.arange(-(-n // partitions)),
                                          1)).collect()

    def make_variables(self):
        """The program's weights: the reference's, part by part, bfloat16;
        the head is the embedding, one array under both names."""
        import jax
        import jax.numpy as jnp

        ref, s = self.reference, self.sizes

        def half(tree):
            return jax.tree.map(lambda a: a.astype(jnp.bfloat16), tree)

        layer = jax.jit(lambda k, i, attention: half(ref.init_layer(
            k, s, i, attention)), static_argnums=2)
        embed = jax.jit(lambda k: half(ref.init_embed(k, s)["embed"]))(
            self.key)
        return {"embed": embed, "head": embed,              # tied: no copy
                "final_norm": jax.jit(lambda k: half(ref.init_head(k, s)[
                    "final_norm"]))(self.key),
                "layers": [layer(self.key, i, ref.is_attention(s, i))
                           for i in range(s.layers)]}

    def measure(self, seconds, tracer):
        from sparkdl_tpu.core import profiling

        n = self.traffic["n"]
        # as windows.Driver.measure: whole passes, the cell's ``trace_passes``
        # traced once the first pass has settled
        first = 1
        last = first + self.traffic["trace_passes"]
        profiling.reset_phase_stats()
        compiles = self.meter.count
        attempted = returned = traced_images = passes = 0
        t0 = time.perf_counter()
        with common.ProgramTelemetry(tracer.enabled) as program:
            while True:
                if passes == first:
                    tracer.start()
                good = self._pass()
                passes += 1
                attempted += n
                returned += good
                if tracer.enabled and first < passes <= last:
                    traced_images += good
                    if passes == last:
                        tracer.stop()
                elapsed = time.perf_counter() - t0 - tracer.overhead_s
                if elapsed >= seconds and not (tracer.enabled
                                               and passes < last):
                    break
        return {
            "seconds": elapsed, "images": returned,
            "attempted": attempted, "failed": attempted - returned,
            "traced_images": traced_images,
            "flops_per_image": flops_ssm.window_flops(
                self.config, self.traffic["window"]),
            "end_to_end": {"featurize_images_per_s": returned / elapsed},
            **common.program_readings(program, self.meter, compiles),
        }

    def reference_outputs(self, quant=None, without=None, stream=None):
        """(pooled, logprobs) of the sampled rows by the plain reference;
        ``without`` names a thing of the model the reference leaves out
        (``"carry"``, ``"inner_norms"``); ``stream``, a list, gains the
        residual stream's root mean square layer by layer."""
        import jax

        sizes = self.sizes if without is None else self.reference.without(
            self.sizes, without)
        with jax.default_matmul_precision("highest"):
            return self.reference.forward(
                self.key, sizes, self.tokens[self.sample], quant, stream)

    def check(self):
        return check.decide(
            self.numbers(self.samples, self.reference_outputs()),
            self.cell.workload["limits"])
