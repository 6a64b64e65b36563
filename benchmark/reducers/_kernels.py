"""Shared by the kernel reducers: which device ops are the step's Pallas
kernels, and how many steps the trace holds."""

import re


def kernel_seconds(ctx: dict, pattern: str):
    rx = re.compile(pattern)
    hit = {k: v for k, v in ctx["trace"]["ops"].items() if rx.search(k)}
    if not hit or ctx["trace_steps"] <= 0:
        return None, hit
    return sum(s for _, s in hit.values()), hit


def steps(hit: dict) -> float:
    """Steps whose kernels the trace holds: every kernel runs once a
    step, so the commonest count among the kernel names is the number of
    steps; the tap's own count of steps completed under the profiler
    differs from it by the steps cut at the trace's edges."""
    counts = sorted(n for n, _ in hit.values())
    return float(counts[len(counts) // 2])
