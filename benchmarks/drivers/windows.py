"""Driver ``windows``: ``DeepSequenceScorer(modelName, weights, batchSize)
.transform(df).collect()`` over an in-memory DataFrame of fixed-length
token-id windows, pass after pass.

Weights are the benchmark's own: made on the device from ``--seed`` by the
plain reference's initialisers, one part at a time, cast to bfloat16 inside
the program that draws them (the float32 values never exist as a whole), and
handed to the scorer as a variables dict. After the window the reference
makes them again, in float32, one layer at a time, for the sampled rows.
"""

import gc
import sys
import time

import numpy as np

import check
import flops_lm
import harness
import token_traffic
from drivers import common


class Driver:
    def __init__(self, cell, seed, env):
        self.cell, self.seed, self.env = cell, seed, env
        self.config, self.traffic = cell.config, cell.traffic
        self.reference = harness.by_name("references",
                                         self.config["reference"],
                                         env["root"])
        self.sizes = self.reference.sizes(self.config)
        self.samples = []       # per pass: (pooled, logprobs) of the sample

    # -- set-up ---------------------------------------------------------------

    def make_variables(self):
        """The program's weights: the reference's, part by part, bfloat16."""
        import jax
        import jax.numpy as jnp

        ref, s = self.reference, self.sizes

        def half(tree):
            return jax.tree.map(lambda a: a.astype(jnp.bfloat16), tree)

        layer = jax.jit(lambda k, i, dense: half(ref.init_layer(k, s, i,
                                                                dense)),
                        static_argnums=2)
        return {**jax.jit(lambda k: half(ref.init_embed(k, s)))(self.key),
                **jax.jit(lambda k: half(ref.init_head(k, s)))(self.key),
                "layers": [layer(self.key, i, i < s.dense_layers)
                           for i in range(s.layers)]}

    def setup(self):
        from sparkdl_tpu.ml import DeepSequenceScorer

        if self.traffic["kind"] != "token_windows":
            raise SystemExit("windows driver: no traffic kind "
                             f"{self.traffic['kind']!r}")
        self.meter = common.CompileMeter()
        self.key = common.prng_key(self.seed)
        self.scorer = DeepSequenceScorer(
            inputCol="tokens", modelName=self.config["model"],
            weights=self.make_variables(),
            expertsHeld=self.config["experts_held"],
            window=self.traffic["window"],
            batchSize=self.traffic["batch_size"])
        n, partitions = self.traffic["n"], self.traffic["partitions"]
        self.tokens = token_traffic.token_windows(self.traffic, self.seed)
        self.frame = self._frame(np.arange(n), partitions)
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 79])
        self.sample = np.sort(rng.choice(n, size=min(n, self.traffic[
            "check_rows"]), replace=False))
        # warm-up: one partition's rows launch the one shape every launch
        # has (batch_size windows); then the sampled rows once more, with the
        # experts' token counts as a third column (the same program: a
        # launch padded to batch_size), for the routing's agreement
        self.scorer.transform(self._frame(np.arange(-(-n // partitions)),
                                          1)).collect()
        rows = self.scorer.transform(
            self._frame(self.sample, 1),
            {self.scorer.expertCountsCol: "expert_counts"}).collect()
        self.expert_counts = np.asarray(
            [r["expert_counts"] for r in rows], np.float64).reshape(
                len(rows), self.sizes.layers - self.sizes.dense_layers, -1)

    def _frame(self, indices, partitions):
        import pyarrow as pa

        from sparkdl_tpu.engine.dataframe import DataFrame

        return DataFrame.fromArrow(pa.table({
            "id": pa.array(np.asarray(indices, np.int64)),
            "tokens": pa.array(list(self.tokens[indices]),
                               type=pa.list_(pa.int32()))}),
            numPartitions=partitions)

    # -- the timed path -------------------------------------------------------

    def _pass(self):
        rows = self.scorer.transform(self.frame).collect()
        # a window with an id outside the slice comes back as not-a-number
        by_id = {r["id"]: r for r in rows
                 if r["pooled"] is not None and r["logprobs"] is not None
                 and r["pooled"][0] == r["pooled"][0]}
        blank = {"pooled": [np.nan] * self.sizes.hidden,
                 "logprobs": [np.nan] * self.traffic["window"]}
        picked = [by_id.get(int(i), blank) for i in self.sample]
        self.samples.append(tuple(
            np.asarray([r[name] for r in picked], np.float32)
            for name in ("pooled", "logprobs")))
        return len(by_id)

    def measure(self, seconds, tracer):
        from sparkdl_tpu.core import profiling

        n = self.traffic["n"]
        # a traced run traces whole passes, the same number in every run:
        # the cell's ``trace_passes`` once the first pass has settled
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
                # passes are synchronous, so the seconds the profiler's own
                # start and stop took come out of the window exactly
                elapsed = time.perf_counter() - t0 - tracer.overhead_s
                if elapsed >= seconds and not (tracer.enabled
                                               and passes < last):
                    break
        return {
            "seconds": elapsed, "images": returned,
            "attempted": attempted, "failed": attempted - returned,
            "traced_images": traced_images,
            "flops_per_image": flops_lm.window_flops(
                self.config, self.traffic["window"]),
            "end_to_end": {"featurize_images_per_s": returned / elapsed},
            **common.program_readings(program, self.meter, compiles),
        }

    # -- after the window -----------------------------------------------------

    def release(self):
        from sparkdl_tpu.core import executor

        self.scorer = self.frame = None
        # the executor's coalescing states hold the model, and so its weights:
        # 9.84 GB that the reference's float32 layers need
        executor.reset()
        gc.collect()

    def reference_outputs(self, quant=None):
        """(pooled, logprobs, per expert layer the tokens per expert) of the
        sampled rows by the plain reference."""
        import jax

        with jax.default_matmul_precision("highest"):
            pooled, logprobs, chosen = self.reference.forward(
                self.key, self.sizes, self.tokens[self.sample], quant)
        counts = np.stack([
            np.stack([np.bincount(row.ravel(), minlength=self.sizes.experts)
                      for row in layer]) for layer in chosen], 1)
        return pooled, logprobs, counts

    def check(self):
        pooled, logprobs, counts = self.reference_outputs()
        print("routing agreement by expert layer: "
              f"{self.routing_agreement(self.expert_counts, counts)}",
              file=sys.stderr)
        return check.decide(self.numbers(self.samples, (pooled, logprobs)),
                            self.cell.workload["limits"])

    @staticmethod
    def routing_agreement(got, want):
        """Per expert layer, the share of (token, expert) choices on which
        the two sides agree, read from each window's tokens per expert: a
        pair of flips that cancel within a window is not seen."""
        moved = np.abs(np.asarray(got, np.float64) - want).sum((0, 2)) / 2
        return (1.0 - moved / np.asarray(want).sum((0, 2))).round(5).tolist()

    @staticmethod
    def numbers(samples, want):
        """Over every timed pass's sampled rows: the worst row's angle
        between ``pooled`` and the reference's, and of |Δ log p| over a
        row's positions the median and the 90th percentile, worst row. A
        token whose eighth and ninth router scores lie within bfloat16's
        noise may choose another expert than the reference's and then moves
        by a whole expert; quantiles over positions are not decided by those
        few (PERF.md §2)."""
        want_pooled, want_logp = want
        angle = p50 = p90 = 0.0
        for pooled, logp in samples:
            angle = max(angle, check.feature_angle_gap(pooled, want_pooled))
            if logp.shape != want_logp.shape or not np.isfinite(logp).all():
                p50 = p90 = float("inf")
                continue
            gap = np.abs(logp.astype(np.float64) - want_logp)[:, :-1]
            p50 = max(p50, float(np.quantile(gap, 0.5, axis=1).max()))
            p90 = max(p90, float(np.quantile(gap, 0.9, axis=1).max()))
        if not samples:
            angle = p50 = p90 = float("inf")
        return {"pooled_angle_gap": angle, "logprob_gap_p50": p50,
                "logprob_gap_p90": p90}
