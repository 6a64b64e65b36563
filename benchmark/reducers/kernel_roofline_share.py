"""Share (%) of the roofline the step's Pallas kernels reach: the least
time the chip could take for what the algorithm needs (per kernel the
larger of bytes / peak bytes/s and operations / peak FLOP/s, bytes and
operations from the batch's shapes by benchmark/kernels/*.py) over their
measured device time per step."""

from benchmark.reducers import _kernels as _k


def least_seconds(ctx: dict) -> tuple[float, str]:
    peaks, least, by = ctx["peaks"], 0.0, {"bytes": 0.0, "flops": 0.0}
    for mod in ctx["kernels"]:
        c = mod.cost(ctx["batch"])
        tb = c["bytes"] / peaks["bytes_per_s"]
        tf = c["flops"] / peaks["flops_per_s"]
        least += max(tb, tf)
        by["bytes" if tb >= tf else "flops"] += max(tb, tf)
    return least, max(by, key=by.get)


def read(ctx: dict, pattern: str):
    total, hit = _k.kernel_seconds(ctx, pattern)
    if total is None or total <= 0 or ctx["batch"]["uniq"] <= 0:
        return None
    least, bound = least_seconds(ctx)
    print(f"[bench] kernel roofline: least {1e6 * least:.1f} us a step, "
          f"bound by {bound}", flush=True)
    return 100.0 * least / (total / _k.steps(hit))
