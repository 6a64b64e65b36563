"""Device time one chip spends a train step in the operations whose
name matches `pattern`, from the trace, in ms.

`xplane.summarize` gives an operation's seconds averaged over the
device planes and its count summed over them, so on a trace of several
chips the median count (`_kernels.steps`) is steps x chips:
`kernel_ms_per_step` divides by it and reads a chip's time over the
number of chips. Here the count is divided by `trace.chips` first. On
one chip the two agree."""

from benchmark.reducers import _kernels as _k


def chips(ctx: dict) -> int:
    """Device planes the trace holds."""
    return max(int(ctx["trace"].get("chips", 1)), 1)


def chip_steps(ctx: dict, hit: dict) -> float:
    """Steps each chip made under the trace."""
    return _k.steps(hit) / chips(ctx)


def read(ctx: dict, pattern: str):
    total, hit = _k.kernel_seconds(ctx, pattern)
    if total is None:
        return None
    return 1e3 * total / chip_steps(ctx, hit)
