"""Share (%) of the device's idle time — the gaps between its
operations, first to last, in the traced window — during which one of
the program's spans was open on some host thread: what the host was
doing while the chip waited. Spans of one thread do not overlap, so the
shares of spans that follow one another on the train thread add up to at
most 100."""

from benchmark import xplane
from benchmark.reducers import _spans


def read(ctx: dict, span: str):
    t = _spans.of_run(ctx)
    if t is None:
        return None
    marks = xplane.union((a, b) for a, b, _, _ in _spans.spans(t, span))
    if not marks:
        return None
    idle = under = 0.0
    for gaps in _spans.idle(t).values():
        for a, b in gaps:
            idle += b - a
            under += xplane.overlap(a, b, marks)
    return 100.0 * under / idle if idle > 0 else None
