"""Reader ``telemetry``: a counter or a histogram of the program's telemetry
scope, open over the whole window of a traced run. Counters are divided by
``per`` (a count of the window); histograms give ``mean`` (sum ÷ count) or a
snapshot key (``p50``, ``p95``, ``max``…)."""


def read(spec, run):
    snapshot = run["window"].get("telemetry")
    if not snapshot:
        return None
    entry = snapshot[spec["group"]].get(spec["name"])
    if entry is None:
        return None
    if spec["group"] == "histograms":
        if not entry.get("count"):
            return None
        stat = spec.get("stat", "mean")
        value = entry["sum"] / entry["count"] if stat == "mean" \
            else entry.get(stat)
    else:
        value = entry
    if value is None:
        return None
    per = run["window"].get(spec["per"]) if "per" in spec else 1
    if not per:
        return None
    return value / per * spec.get("scale", 1)
