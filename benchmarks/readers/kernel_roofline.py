"""Reader ``kernel_roofline``: a hand-written kernel's share of its roofline
over the traced part of the window, from the trace reduction's device ops
(``trace_reduce.reduce`` lists the ten that took most time).

Of the listed ops whose name starts with ``op_prefix`` — one name a call site
of the kernel in the launch's program — the least seconds the chip could take
for their calls ÷ the device seconds they took, in percent. A call's least
seconds are ``max(operations ÷ peak FLOP/s, bytes ÷ peak bytes/s)``, the
operations and bytes from the named functions of the named module of the
benchmark (``module``, ``ops``, ``bytes``: each ``f(config, window)`` of one
call); a call site runs once for each image (window) traced. Call sites
outside the ten are left out of both sides, so the share is of the calls that
were found. None where none was found: a program without the kernel, or one
whose every call is outweighed by ten other ops."""

import importlib


def read(spec, run):
    trace, window, cell = run["trace"], run["window"], run["cell"]
    images = window.get("traced_images")
    if not trace or not images:
        return None
    found = [seconds for name, seconds in trace["device_ops"]
             if name.startswith(spec["op_prefix"])]
    if not found or sum(found) <= 0:
        return None
    counts = importlib.import_module(spec["module"])
    tokens = cell.traffic["window"]
    least = max(
        getattr(counts, spec["ops"])(cell.config, tokens)
        / run["peaks"]["bf16_flops_per_s"],
        getattr(counts, spec["bytes"])(cell.config, tokens)
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * len(found) * images / sum(found)
