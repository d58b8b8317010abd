"""Driver ``windows_ling``: the ``windows_laguna`` driver for a model of the
``bailing_hybrid`` family — a pre-norm stack whose mixer is linear attention
with the gated delta rule or latent attention by its published position, and
whose leading layer's ffn is a dense MLP. Set-up (the driver's message,
before a weight is drawn, on a program without the model), the weights (a
layer drawn with its kind and whether it is dense given), the passes, the
check and the control's readings are ``windows_laguna``'s; what differs is
the count of a window's operations (``flops_ling``: the timed loop is
``windows``' with that count at its end, as in ``windows_laguna``)."""

import time

import flops_ling
from drivers import common, windows_laguna


class Driver(windows_laguna.Driver):
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
            "flops_per_image": flops_ling.window_flops(
                self.config, self.traffic["window"]),
            "end_to_end": {"featurize_images_per_s": returned / elapsed},
            **common.program_readings(program, self.meter, compiles),
        }
