"""From a profiler trace to numbers. Two stages, so that the arithmetic can
be checked on a small recorded trace without the profiler:

``extract(xplane_path)``  → ``{"device": {plane: {line: [[name, start_ns,
                            dur_ns], …]}}, "host": [[name, start_ns,
                            dur_ns], …]}`` — device planes' lines, and what
                            ``sparkdl.*`` / benchmark annotations the
                            profiler's own host tracer recorded (none when
                            it is off, as in the harness: see ``Tracer``);
``reduce(events, …)``     → busy seconds (union of device-op intervals,
                            averaged over the chips), window seconds, the
                            device ops that took most time, the idle gaps shared
                            among the host spans open during them, and how
                            often and how long each device program ran.

The traced window is marked on the device's own line by two tiny marker
programs (``marked_window``); the program's host spans, which the harness
reads on the host's clock, are brought onto the device's clock by the same
two markers (``on_device_clock``).
"""

import collections
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("sparkdl.", "bench.")
TOP = 10
NAME_CHARS = 160        # of a device op's HLO text in the result line


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(xplane_path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    device, host, seen = {}, [], {}
    for plane in data.planes:
        seen[plane.name] = [line.name for line in plane.lines]
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = device.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"device": device, "host": host, "seen": seen}


def marker_runs(events, names):
    """``{name: (start_ns, end_ns)}``: the first run of each marker program
    (``jit_<name>``) on a device's ``XLA Modules`` line."""
    found = {}
    for lines in events["device"].values():
        for module, start, dur in lines.get(MODULES_LINE, ()):
            name = _program_name(module).removeprefix("jit_")
            if name in names and name not in found:
                found[name] = (start, start + dur)
    return found


def marked_window(events, start_name, stop_name):
    """``(start_ns, end_ns)`` on the device's clock, from the end of the
    start marker's run to the start of the stop marker's, or ``None``
    (first to last device event) where they are missing."""
    runs = marker_runs(events, (start_name, stop_name))
    if len(runs) < 2 or runs[stop_name][0] <= runs[start_name][1]:
        return None
    return runs[start_name][1], runs[stop_name][0]


def on_device_clock(spans, ready_ns, runs):
    """Host ``spans`` (``[name, start_ns, dur_ns]`` on the host's clock)
    moved onto the device's: ``ready_ns[name]`` is the host's clock when a
    marker's result came back, ``runs[name]`` its run on the device, and
    the mean difference over the markers is the offset (the result comes
    back some tenths of a millisecond after the run ends; gaps worth
    attributing are far longer)."""
    both = [n for n in ready_ns if n in runs]
    if not both:
        return []
    offset = sum(runs[n][1] - ready_ns[n] for n in both) // len(both)
    return [[name, start + offset, dur] for name, start, dur in spans]


def union(intervals):
    """Merged, sorted ``[start, end)`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(events, lo, hi):
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def _program_name(name):
    """``jit_step_fn(1234…)`` → ``jit_step_fn``: a module's name without the
    fingerprint XLA appends."""
    return name.split("(")[0]


def reduce(events, window=None):
    """``window`` is ``(start_ns, end_ns)`` on the profiler's clock; default:
    from the first to the last device event. Returns ``None`` when no
    operation ran on a device."""
    planes = {name: lines for name, lines in events["device"].items()
              if lines.get(OPS_LINE) or lines.get(MODULES_LINE)}
    if not planes:
        return None
    if window is None:
        spans = [(s, s + d) for lines in planes.values()
                 for evs in lines.values() for _, s, d in evs]
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    lo, hi = window
    busy_ns, op_ns = 0, collections.Counter()
    runs, run_ns = collections.Counter(), collections.Counter()
    gaps = collections.Counter()
    for lines in planes.values():
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        clipped = list(_clip(ops, lo, hi))
        busy = union([s, e] for _, s, e in clipped)
        busy_ns += sum(e - s for s, e in busy)
        for name, s, e in clipped:
            op_ns[name] += e - s
        for name, start, dur in lines.get(MODULES_LINE, ()):
            # a run cut by the window's edge counts by the part inside
            inside = min(start + dur, hi) - max(start, lo)
            if inside > 0:
                runs[_program_name(name)] += inside / max(dur, 1)
                run_ns[_program_name(name)] += inside
        edges = [lo] + [t for span in busy for t in span] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                _attribute(events["host"], gs, ge, gaps)
    n = len(planes)
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[name[:NAME_CHARS], ns / n / 1e9]
                       for name, ns in op_ns.most_common(TOP)],
        "idle_gaps": [[name, ns / n / 1e9]
                      for name, ns in gaps.most_common(TOP)],
        # device programs: [runs, seconds], a run cut by the edge in part
        "programs": {name: [runs[name] / n, run_ns[name] / n / 1e9]
                     for name in runs},
    }


def _attribute(host, gs, ge, into):
    """Share the gap ``[gs, ge)`` among the host annotations open during it:
    cut it at their edges, and give each piece to the annotation that
    started last of those covering it (the innermost on one thread, the most
    recent across threads). ``(none)`` where no annotation was open: idle
    with nothing the program names going on."""
    inside = [(s, s + d, n) for n, s, d in host if s < ge and s + d > gs]
    cuts = sorted({gs, ge, *(t for s, e, _ in inside for t in (s, e)
                             if gs < t < ge)})
    for lo, hi in zip(cuts, cuts[1:]):
        covering = [(s, n) for s, e, n in inside if s <= lo and e >= hi]
        into[max(covering)[1] if covering else "(none)"] += hi - lo
