"""Reader ``window``: arithmetic on the driver's own counts of the window.

``mfu``  the benchmark's FLOPs per image × images ÷ (window seconds × the
         chip's peak bf16 FLOP/s), in percent — all the work over all the
         time, idle included."""


def read(spec, run):
    window, peak = run["window"], run["peaks"]["bf16_flops_per_s"]
    if spec["key"] == "mfu":
        if not window.get("images"):
            return None
        return 100.0 * window["flops_per_image"] * window["images"] / (
            window["seconds"] * peak)
    raise SystemExit(f"window reader: no key {spec['key']!r}")
