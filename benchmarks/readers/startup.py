"""Reader ``startup``: sums over the program's start-up record
(``sparkdl_tpu/core/profiling.py`` ``startup_stats``), which every telemetry
scope mirrors into gauges ``sparkdl.startup.<key>`` as it opens — so the
scope a traced run opens over its window still holds what set-up took
before it. ``keys`` names the record's keys to add.

``sum``    the named keys added (seconds)
``share``  100 × that sum ÷ the run's ``setup_s``: how much of set-up the
           program accounts for (the rest is the interpreter, JAX and the
           backend coming up, and the benchmark's own weights and traffic)

None where the scope lacks one of the gauges: a program older than the
record."""

PREFIX = "sparkdl.startup."


def read(spec, run):
    snapshot = run["window"].get("telemetry")
    if not snapshot:
        return None
    gauges = snapshot.get("gauges", {})
    values = [gauges.get(PREFIX + key) for key in spec["keys"]]
    if any(value is None for value in values):
        return None
    if spec["key"] == "sum":
        return sum(values)
    if spec["key"] == "share":
        return 100.0 * sum(values) / run["setup_s"] \
            if run.get("setup_s") else None
    raise SystemExit(f"startup reader: no key {spec['key']!r}")
