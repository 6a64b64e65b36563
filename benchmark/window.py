"""Window arithmetic: what the end-to-end numbers are made of.

A window is the times at which train steps completed. It opens at the
first step completed after warm-up (that step is the clock's zero, not
work of the window) and holds every later step up to and including the
first one completed `seconds` or more after it: steps are counted whole,
and all the time up to the last one's end is the window's, a stall in the
closing step included.
"""

from __future__ import annotations

import math


def p95(values) -> float:
    """Nearest-rank 95th percentile (the value with at most 5 % of the
    sample above it)."""
    s = sorted(values)
    if not s:
        raise ValueError("p95 of no samples")
    return s[max(math.ceil(0.95 * len(s)) - 1, 0)]


def rate(t_open: float, ends, rows) -> float:
    """Rows of the steps completed in the window over the time from the
    window's first completed step (its opening) to its last."""
    if not ends:
        raise ValueError("no step completed inside the window")
    return float(sum(rows)) / (ends[-1] - t_open)


def gaps_ms(t_open: float, ends) -> list[float]:
    """Times between consecutive completed steps, the opening step
    included as the first boundary."""
    ts = [t_open, *ends]
    return [1e3 * (b - a) for a, b in zip(ts, ts[1:])]
