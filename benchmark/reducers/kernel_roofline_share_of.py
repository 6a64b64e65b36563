"""Share (%) of the roofline that some of a step's device operations
reach: as `kernel_roofline_share`, over the operations `pattern` matches
and the counts of the `kernels` the layer metric names (modules under
benchmark/kernels/), where the accepted reducer takes every kernel the
configuration lists. For a part of a step whose operations are not all
Pallas custom calls: the vector-row side of the FM step is one Pallas
kernel between XLA's row gather and scatter."""

import importlib

from benchmark.reducers import _kernels as _k
from benchmark.reducers import kernel_roofline_share as _all


def read(ctx: dict, pattern: str, kernels: list):
    total, hit = _k.kernel_seconds(ctx, pattern)
    if total is None or total <= 0 or ctx["batch"]["uniq"] <= 0:
        return None
    mods = [importlib.import_module(f"benchmark.kernels.{k}")
            for k in kernels]
    least, bound = _all.least_seconds(dict(ctx, kernels=mods))
    print(f"[bench] roofline of {kernels}: least {1e6 * least:.1f} us a "
          f"step, bound by {bound}", flush=True)
    return 100.0 * least / (total / _k.steps(hit))
