"""Driver ``windows_laguna``: the ``windows_span`` driver for a model of the
``laguna`` family — a pre-norm stack whose attention layers are sliding or
full by their position, each kind with its own query heads, and whose leading
layer's ffn is a dense MLP. Set-up (the driver's message, before a weight is
drawn, on a program without the model), the passes, the check and the
control's readings are ``windows_span``'s; what differs is the count of a
window's operations (``flops_laguna``: the timed loop is ``windows``' with
that count at its end, as in ``windows_span``) and the weights (a layer is
drawn with its kind and whether it is dense given, so that each shape of
layer is one program)."""

import time

import flops_laguna
from drivers import common, windows_span


class Driver(windows_span.Driver):
    def make_variables(self):
        """The program's weights: the reference's, part by part, bfloat16."""
        import jax
        import jax.numpy as jnp

        ref, s = self.reference, self.sizes

        def half(tree):
            return jax.tree.map(lambda a: a.astype(jnp.bfloat16), tree)

        layer = jax.jit(lambda k, i, kind, dense: half(ref.init_layer(
            k, s, i, kind, dense)), static_argnums=(2, 3))
        return {**jax.jit(lambda k: half(ref.init_embed(k, s)))(self.key),
                **jax.jit(lambda k: half(ref.init_head(k, s)))(self.key),
                "layers": [layer(self.key, i, s.layer_types[i],
                                 i < s.dense_layers)
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
            "flops_per_image": flops_laguna.window_flops(
                self.config, self.traffic["window"]),
            "end_to_end": {"featurize_images_per_s": returned / elapsed},
            **common.program_readings(program, self.meter, compiles),
        }
