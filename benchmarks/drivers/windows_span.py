"""Driver ``windows_span``: the ``windows`` driver for a model of the
``mellum`` family — a pre-norm stack whose attention layers are sliding or
full by their position. Set-up, the passes, the check and the control's
readings are ``drivers.windows``'s; what differs is what names the other
model — the count of a window's operations (``flops_window``: the timed loop
is ``windows``' with that count at its end, as in ``windows_shortconv``), the
weights (a layer is drawn whatever its kind; the head is untied), and the
reference's pass, which can leave one of the model's mechanisms out
(``control_span.py``)."""

import time

import flops_window
from drivers import common, windows


class Driver(windows.Driver):
    def setup(self):
        from sparkdl_tpu.models import registry

        # a program older than the model: say so before 7.6 GB of weights
        if self.config["model"] not in registry.SEQUENCE_MODELS:
            raise SystemExit("windows_span driver: the program has no "
                             f"sequence model {self.config['model']!r}")
        super().setup()

    def make_variables(self):
        """The program's weights: the reference's, part by part, bfloat16."""
        import jax
        import jax.numpy as jnp

        ref, s = self.reference, self.sizes

        def half(tree):
            return jax.tree.map(lambda a: a.astype(jnp.bfloat16), tree)

        layer = jax.jit(lambda k, i: half(ref.init_layer(k, s, i)))
        return {**jax.jit(lambda k: half(ref.init_embed(k, s)))(self.key),
                **jax.jit(lambda k: half(ref.init_head(k, s)))(self.key),
                "layers": [layer(self.key, i) for i in range(s.layers)]}

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
            "flops_per_image": flops_window.window_flops(
                self.config, self.traffic["window"]),
            "end_to_end": {"featurize_images_per_s": returned / elapsed},
            **common.program_readings(program, self.meter, compiles),
        }

    def reference_outputs(self, quant=None, without=None):
        """``windows.Driver.reference_outputs``; ``without`` names a
        mechanism the reference leaves out (``"span"``, ``"yarn"``)."""
        sound = self.sizes
        if without is not None:
            self.sizes = self.reference.without(sound, without)
        try:
            return super().reference_outputs(quant)
        finally:
            self.sizes = sound
