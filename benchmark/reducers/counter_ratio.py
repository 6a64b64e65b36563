"""Ratio of two of the program's counters (`wormhole_tpu/obs/metrics.py`
REGISTRY), as they stand when the run's metrics are taken; times the
number of chips where `times_chips` is set (the fullest of a batch's
mesh cells over the mean cell = max x cells / sum). None where the
program has no such counter, or the second is still zero."""

from benchmark.reducers.op_ms_per_step_per_chip import chips


def read(ctx: dict, num: str, den: str, times_chips: bool = False):
    from wormhole_tpu.obs.metrics import REGISTRY

    counters = REGISTRY.snapshot()["counters"]
    if num not in counters or not counters.get(den):
        return None
    ratio = counters[num] / counters[den]
    return float(ratio * chips(ctx) if times_chips else ratio)
