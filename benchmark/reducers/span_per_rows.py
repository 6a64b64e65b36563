"""Thread time a batch's rows cost in a set of the program's spans, in
ms: the spans' summed duration (or, `value` = "cpu_us", their summed
thread CPU time) over the rows they produced (the spans' `rows`
argument), times the rows of one batch. For spans whose unit of work is
not a batch: a text chunk, a crb record."""

from benchmark.reducers import _spans


def read(ctx: dict, spans: list, value: str = "duration"):
    t = _spans.of_run(ctx)
    if t is None:
        return None
    ms, rows = 0.0, 0
    for name in spans:
        for s in _spans.spans(t, name):
            ms += _spans.ms(s, value) or 0.0
            rows += s[3].get("rows", 0)
    if rows <= 0:
        return None
    return ms / rows * ctx["batch"]["rows"]
