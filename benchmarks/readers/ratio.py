"""Reader ``ratio``: one reading of the program's telemetry scope over
another — a counter's value, or a histogram's ``count`` / ``sum`` — times
``scale``. ``{"numerator": {group, name[, stat]}, "denominator": {…}}``.
None where the scope lacks either (a program older than the counter) or the
denominator is 0."""


def _value(snapshot, spec):
    entry = snapshot[spec["group"]].get(spec["name"])
    if entry is None:
        return None
    return entry[spec.get("stat", "count")] \
        if spec["group"] == "histograms" else entry


def read(spec, run):
    snapshot = run["window"].get("telemetry")
    if not snapshot:
        return None
    top = _value(snapshot, spec["numerator"])
    bottom = _value(snapshot, spec["denominator"])
    if top is None or not bottom:
        return None
    return top / bottom * spec.get("scale", 1)
