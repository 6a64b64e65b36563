"""Reduction from a profiler trace (`.xplane.pb`) to numbers.

Read with `jax.profiler.ProfileData`, nothing else. A trace holds planes;
the device planes are named `/device:TPU:<n>` and carry a line `XLA Ops`
whose events are the operations that ran on that chip, each with a start
and a duration in nanoseconds; the host plane carries one line per thread
with the `TraceAnnotation`s the tap wrote (`bench.step` round every
`train_batch`). From those:

  busy     union of the device-op intervals, averaged over the chips
  idle     1 - busy / traced span
  ops      time and count per operation name
  gaps     the idle gaps between device ops, each labelled by what the
           train thread was doing for most of it: inside `bench.step`
           (`step_host`: dispatch, the blocking scalar fetch) or outside it
           (`queue_wait+merge`: waiting on the loader queue, merging
           progress)
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
OPS_LINE = "XLA Ops"
STEP_MARK = "bench.step"


def short_name(op: str) -> str:
    """A device op is named by its whole HLO instruction,
    `%name = type opcode(operands), attributes`: keep `%name opcode`,
    and say so where the custom call is a Pallas (Mosaic) kernel."""
    lhs, sep, rhs = op.partition(" = ")
    if not sep:
        return op[:120]
    m = _OPCODE.search(" " + rhs)
    code = m.group(1) if m else "?"
    if "tpu_custom_call" in rhs:
        code += "[tpu_custom_call]"
    return f"{lhs} {code}"


def find(logdir: str) -> str:
    hits = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return hits[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_ops(pd) -> dict:
    """plane name -> [(op name, start_ns, end_ns)] sorted by start."""
    out = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                evs.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        out[plane.name] = sorted(evs, key=lambda e: e[1])
    return out


def host_marks(pd, name: str = STEP_MARK) -> list:
    """[(start_ns, end_ns)] of the tap's annotations, any host thread."""
    out = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    out.append((e.start_ns, e.start_ns + e.duration_ns))
    return sorted(out)


def union(intervals) -> list:
    """Merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def busy_ns(ops) -> float:
    return float(sum(b - a for a, b in union((s, e) for _, s, e in ops)))


def op_totals(ops) -> dict:
    """op name -> [count, seconds]."""
    out: dict[str, list] = {}
    for name, s, e in ops:
        c = out.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) * 1e-9
    return out


def overlap(a, b, marks) -> float:
    return float(sum(max(0, min(b, m1) - max(a, m0)) for m0, m1 in marks))


def idle_gaps(ops, marks, top: int = 10) -> list:
    """The longest idle gaps between device ops as [label, seconds],
    labelled by where the train thread spent most of the gap."""
    gaps = []
    busy = union((s, e) for _, s, e in ops)
    for (_, a), (b, _) in zip(busy, busy[1:]):
        inside = overlap(a, b, marks)
        label = ("step_host" if 2 * inside >= (b - a)
                 else "queue_wait+merge")
        gaps.append([label, (b - a) * 1e-9])
    return sorted(gaps, key=lambda g: -g[1])[:top]


def summarize(pd, window_s: float | None = None) -> dict:
    """Everything the trace reducers read. `window_s` is the traced span
    on the host's clock; without it the span of the device events stands
    in (tests)."""
    planes = device_ops(pd)
    if not planes or not any(planes.values()):
        raise ValueError("the trace holds no device operation")
    marks = host_marks(pd)
    busy = [busy_ns(ops) * 1e-9 for ops in planes.values()]
    first = next(iter(planes.values()))
    span = (max(e for _, _, e in first) - min(s for _, s, _ in first)) * 1e-9
    totals: dict[str, list] = {}
    for ops in planes.values():
        for name, (n, s) in op_totals(ops).items():
            c = totals.setdefault(name, [0, 0.0])
            c[0] += n
            c[1] += s
    nplanes = len(planes)
    return {
        "chips": nplanes,
        "busy_s": sum(busy) / nplanes,
        "window_s": float(window_s) if window_s else span,
        "device_span_s": span,
        "ops": {k: [n, s / nplanes] for k, (n, s) in totals.items()},
        "gaps": idle_gaps(first, marks),
        "steps_marked": len(marks),
    }


def breakdown(summary: dict, top: int = 10) -> dict:
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1][1])[:top]
    return {"device_ops": [[short_name(k), v[1]] for k, v in ops],
            "idle_gaps": summary["gaps"][:top]}
