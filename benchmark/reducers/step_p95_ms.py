"""95th percentile of the tap's own per-step times (the program's
histogram keeps only a reservoir), in ms."""

from benchmark.window import p95


def read(ctx: dict):
    if len(ctx["step_s"]) < 20:
        return None
    return 1e3 * p95(ctx["step_s"])
