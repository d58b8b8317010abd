"""Driver ``featurize``: ``DeepImageFeaturizer(modelName, batchSize)
.transform(df).collect()`` over the cell's traffic, pass after pass.

The traffic is ``image_arrays``: an in-memory image DataFrame at the model's
input size, built once in set-up, so decode and resize are not in the pass.

Weights are the benchmark's own: drawn on the device from ``--seed`` by the
plain reference's ``init`` in one jitted call, handed to the featurizer as a
Flax variables dict, and used again by the reference after the window.
"""

import gc
import time

import numpy as np

import check
import flops
import harness
import traffic
from drivers import common


class Driver:
    def __init__(self, cell, seed, env):
        self.cell, self.seed, self.env = cell, seed, env
        self.config, self.traffic = cell.config, cell.traffic
        self.reference = harness.by_name("references",
                                         self.config["reference"],
                                         env["root"])
        self.samples = []       # per pass: (sampled indices' features)

    # -- set-up ---------------------------------------------------------------

    def setup(self):
        import jax

        from sparkdl_tpu.ml import DeepImageFeaturizer

        if self.traffic["kind"] != "image_arrays":
            raise SystemExit("featurize driver: no traffic kind "
                             f"{self.traffic['kind']!r}")
        self.meter = common.CompileMeter()
        self.variables = jax.jit(
            lambda key: flops.init_variables(self.reference, key, False))(
                common.prng_key(self.seed))
        self.featurizer = DeepImageFeaturizer(
            inputCol="image", outputCol="features",
            modelName=self.config["model"], weights=self.variables,
            batchSize=self.traffic["batch_size"])
        n, partitions = self.traffic["n"], self.traffic["partitions"]
        self.arrays = traffic.image_arrays(self.traffic, self.seed)
        self.frame = self._array_frame(self.arrays, partitions)
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 77])
        self.sample = np.sort(rng.choice(n, size=min(n, self.traffic[
            "check_rows"]), replace=False))
        # warm-up: one partition's rows launch the shape every partition
        # launches (batch_size rows, uint8); a compile inside the window
        # would show in compiles_in_window
        self.featurizer.transform(self._array_frame(
            self.arrays[:-(-n // partitions)], 1)).collect()

    def _array_frame(self, arrays, partitions):
        import pyarrow as pa

        from sparkdl_tpu.engine.dataframe import DataFrame
        from sparkdl_tpu.image import imageIO

        origins = [f"array:{i:05d}" for i in range(len(arrays))]
        column = imageIO.imageArraysToStructColumn(list(arrays), origins)
        return DataFrame.fromArrow(
            pa.Table.from_arrays([column], names=["image"]),
            numPartitions=partitions)

    # -- the timed path -------------------------------------------------------

    def _pass(self):
        rows = self.featurizer.transform(self.frame).collect()
        good = sum(1 for r in rows if r["features"] is not None)
        # origins are ``array:<index>``
        by_index = {int(r["image"]["origin"][6:]): r["features"]
                    for r in rows if r["features"] is not None}
        self.samples.append(np.asarray(
            [by_index.get(int(i), [np.nan] * self.reference.FEATURE_DIM)
             for i in self.sample], np.float32))
        return good

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
            "flops_per_image": flops.forward_flops_per_image(self.reference,
                                                             False),
            "end_to_end": {"featurize_images_per_s": returned / elapsed},
            **common.program_readings(program, self.meter, compiles),
        }

    # -- after the window -----------------------------------------------------

    def release(self):
        self.featurizer = self.frame = None
        gc.collect()

    def check(self):
        want = common.reference_features(
            self.reference, self.variables, self.arrays[self.sample],
            block=self.traffic.get("check_block", 64))
        return check.decide(self.numbers(self.samples, want),
                            self.cell.workload["limits"])

    @staticmethod
    def numbers(samples, want):
        """Over every timed pass's sampled rows, the worst row's angle to
        the reference: the gap left once the best common factor is taken out
        of the row. It is steady from seed to seed, where the plain relative
        gap moves with the seed's weights by as much as the control adds
        (PERF.md §2)."""
        return {"feature_angle_gap": max(
            (check.feature_angle_gap(got, want) for got in samples),
            default=float("inf"))}
