"""Mean over the traced window of one of the program's spans, in ms:
its duration on the trace's clock, or (`value` = "cpu_us") the thread
CPU time the span itself measured, which leaves out what the thread
spent waiting (the interpreter lock, I/O)."""

from benchmark.reducers import _spans


def read(ctx: dict, span: str, value: str = "duration"):
    t = _spans.of_run(ctx)
    if t is None:
        return None
    ms = [_spans.ms(s, value) for s in _spans.spans(t, span)]
    ms = [v for v in ms if v is not None]
    return sum(ms) / len(ms) if ms else None
