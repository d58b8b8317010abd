"""Reader ``trace``: keys of the trace reduction (``trace_reduce.reduce``)
over the traced part of the window.

``idle_share``  100 × (1 − busy ÷ traced window)
``busy_mfu``    the benchmark's FLOPs of the images the device worked on
                in the traced part ÷ (busy seconds × peak), in percent: the
                same work whatever implements it. The images are the
                driver's count where passes are synchronous
                (``traced_images``), else the runs of the device program that
                took most time × ``rows_per_run``.
``program_ms``  mean device milliseconds of one run of the device program
                that took most time (the featurize launch, the train step):
                steady where the idle share is not."""


def read(spec, run):
    trace, window = run["trace"], run["window"]
    if not trace or trace["busy_s"] <= 0:
        return None
    if spec["key"] == "idle_share":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if spec["key"] == "program_ms":
        if not trace["programs"]:
            return None
        runs, seconds = max(trace["programs"].values(), key=lambda p: p[1])
        return 1000.0 * seconds / runs
    if spec["key"] == "busy_mfu":
        images = window.get("traced_images")
        if not images:
            if not trace["programs"] or "rows_per_run" not in window:
                return None
            runs, _ = max(trace["programs"].values(), key=lambda p: p[1])
            images = runs * window["rows_per_run"]
        return 100.0 * window["flops_per_image"] * images / (
            trace["busy_s"] * run["peaks"]["bf16_flops_per_s"])
    raise SystemExit(f"trace reader: no key {spec['key']!r}")
