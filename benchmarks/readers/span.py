"""Reader ``span``: the program's spans of one name over the window of a
traced run (``window["spans"]``: ``[name, start_ns, dur_ns]``).

``total_s``  the durations summed — over threads too, like the ``phase``
             reader, so it can exceed the window.
``union_s``  the length of the union of the intervals: the wall time during
             which at least one thread was inside such a span.

``per``: ``images`` or ``seconds`` of the window; ``scale`` multiplies (1000
for ms, 100 for %). Where spans were collected and none has the name the
reading is 0.0 — a quiet run in which, say, no request queued still reports
the metric; ``None`` only where the run collected no spans at all."""

import trace_reduce


def read(spec, run):
    window = run["window"]
    spans = window.get("spans")
    if not spans or not window.get(spec["per"]):
        return None
    mine = [(start, start + dur) for name, start, dur in spans
            if name == spec["span"]]
    stat = spec.get("stat", "total_s")
    if stat == "union_s":
        mine = trace_reduce.union(mine)
    elif stat != "total_s":
        raise SystemExit(f"span reader: no stat {stat!r}")
    seconds = sum(stop - start for start, stop in mine) / 1e9
    return seconds / window[spec["per"]] * spec.get("scale", 1)
