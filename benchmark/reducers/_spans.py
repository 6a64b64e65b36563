"""Shared by the span reducers: the traced run's `.xplane.pb`, read once
more for what `ctx` does not carry — the program's own spans on the host
plane (`wormhole_tpu/obs/trace.py` enters a `TraceAnnotation` for every
`span()` while the tap's profiler session runs, so they lie on the clock
of the device's operations) and the device's operations as intervals.

`ctx` has no handle on the trace file, so it is found: the newest
`whbench_*/trace` under `tempfile.gettempdir()`, the directory `run.py`
makes for the run and removes at its end. Found is not yet the run's
own: the file's count of `bench.step` marks and its device busy time
have to equal what `ctx["trace"]` says of the run's trace. Otherwise
`of_run` returns None, and so does every reducer built on it: the metric
is left out of the line, nothing is guessed. A program without the spans
(the parent of the PR that added them) gives a trace in which a span's
name never occurs: the reducers then return None as well.
"""

from __future__ import annotations

import glob
import math
import os
import tempfile

from benchmark import xplane

# path of the trace last read -> what `parse` made of it: a process
# traces once, and a dozen reducers read it
_KEPT: dict = {}


def parse(pd) -> dict:
    """`pd` is a `ProfileData` (or anything shaped like one: planes,
    lines, events with name / start_ns / duration_ns and, on the host,
    stats).

      host      name -> [(start_ns, end_ns, line, event)] over every
                host line, sorted by start; `line` numbers the thread
      busy      device plane -> merged [(start_ns, end_ns)] in which
                some operation ran on that chip
      steps_marked, busy_s   as `xplane.summarize` gives them
    """
    host: dict[str, list] = {}
    lines = 0
    for plane in pd.planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            lines += 1
            for e in line.events:
                host.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns, lines, e))
    for evs in host.values():
        evs.sort(key=lambda t: t[:2])
    busy = {name: xplane.union((s, e) for _, s, e in ops)
            for name, ops in xplane.device_ops(pd).items()}
    secs = [sum(b - a for a, b in iv) * 1e-9 for iv in busy.values()]
    return {"pd": pd, "host": host, "busy": busy,
            "steps_marked": len(host.get(xplane.STEP_MARK, ())),
            "busy_s": sum(secs) / len(secs) if secs else 0.0}


def newest_trace() -> str | None:
    dirs = glob.glob(os.path.join(tempfile.gettempdir(), "whbench_*",
                                  "trace"))
    if not dirs:
        return None
    try:
        return xplane.find(max(dirs, key=os.path.getmtime))
    except FileNotFoundError:
        return None


def of_run(ctx: dict) -> dict | None:
    """The parsed trace of the run `ctx` describes, or None."""
    path = newest_trace()
    if path is None:
        return None
    if path not in _KEPT:
        _KEPT.clear()
        _KEPT[path] = parse(xplane.load(path))
    t, said = _KEPT[path], ctx["trace"]
    own = (t["steps_marked"] == said["steps_marked"]
           and math.isclose(t["busy_s"], said["busy_s"], rel_tol=1e-9))
    return t if own else None


def spans(t: dict, name: str) -> list:
    """[(start_ns, end_ns, line, {argument: value})] of one span name."""
    return [(a, b, line, dict(getattr(e, "stats", ()) or ()))
            for a, b, line, e in t["host"].get(name, ())]


def ms(span: tuple, value: str):
    """One span's reading in ms: its duration on the trace's clock, or
    (any other `value`, e.g. "cpu_us") that argument of the span, taken
    as microseconds; None where the span carries no such argument."""
    a, b, _, args = span
    if value == "duration":
        return (b - a) * 1e-6
    return args[value] * 1e-3 if value in args else None


def idle(t: dict) -> dict:
    """device plane -> [(start_ns, end_ns)] of the gaps between its
    operations, from its first to its last: the idle time
    `breakdown.idle_gaps` lists the longest of."""
    return {name: [(a, b) for (_, a), (b, _) in zip(iv, iv[1:])]
            for name, iv in t["busy"].items()}
