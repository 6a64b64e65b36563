"""Share (%) of the roofline the step's Pallas kernels reach on a mesh:
as `kernel_roofline_share`, per chip. The kernels' counts
(benchmark/kernels/*.py) are of the whole batch, which the chips share:
the least time a chip could take is a kernel's whole-batch least time
over the number of chips, and the measured time is a chip's own a step
(`op_ms_per_step_per_chip`). Each kernel's own share is printed, under
the name its `pallas_call` carries; the metric is the sum of the least
times over the sum of the measured ones."""

from benchmark.reducers import _kernels as _k
from benchmark.reducers.op_ms_per_step_per_chip import chip_steps, chips


def read(ctx: dict, pattern: str):
    total, hit = _k.kernel_seconds(ctx, pattern)
    if total is None or total <= 0 or ctx["batch"]["uniq"] <= 0:
        return None
    n, steps = chips(ctx), chip_steps(ctx, hit)
    peaks, least = ctx["peaks"], 0.0
    for mod in ctx["kernels"]:
        c = mod.cost(ctx["batch"])
        tb = c["bytes"] / peaks["bytes_per_s"] / n
        tf = c["flops"] / peaks["flops_per_s"] / n
        least += max(tb, tf)
        name = mod.__name__.rsplit(".", 1)[-1]
        own, _ = _k.kernel_seconds(ctx, rf"^(ROOT )?%{name}[.\w]* = ")
        if own:
            print(f"[bench] kernel roofline: {name} least "
                  f"{1e6 * max(tb, tf):.1f} us a chip a step, bound by "
                  f"{'bytes' if tb >= tf else 'flops'}, measured "
                  f"{1e3 * own / steps:.3f} ms: "
                  f"{100.0 * max(tb, tf) / (own / steps):.4f} %", flush=True)
    return 100.0 * least / (total / steps)
