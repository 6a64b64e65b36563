"""One of the run's own end-to-end numbers, as the driver's
`end_to_end` gives them, read beside the layers: for a cell in which the
number is too unsteady to be held to a bound (PERF.md §2) and is a layer
metric under another name. In a traced run it is that run's own number,
the profiler's seconds in it."""


def read(ctx: dict, metric: str):
    v = ctx.get("end_to_end", {}).get(metric)
    return v if v else None
