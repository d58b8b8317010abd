"""Reader ``phase``: one of the program's phase timers
(``profiling.phase_stats``), summed over threads — so a total can exceed the
window. ``per``: ``images`` or ``seconds`` of the window; ``scale``
multiplies (1000 for ms, 100 for %)."""


def read(spec, run):
    window = run["window"]
    stats = window["phases"].get(spec["phase"])
    if not stats or not window.get(spec["per"]):
        return None
    return stats[spec.get("stat", "total_s")] / window[spec["per"]] \
        * spec.get("scale", 1)
