"""Device time of the step's Pallas custom calls per train step, from
the trace, in ms."""

from benchmark.reducers import _kernels as _k


def read(ctx: dict, pattern: str):
    total, hit = _k.kernel_seconds(ctx, pattern)
    if total is None:
        return None
    return 1e3 * total / _k.steps(hit)
