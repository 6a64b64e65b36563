"""Share (%) of its roofline that the device work under one of the
program's spans reaches: the least time the chip could take for what the
algorithm needs (the larger of bytes / peak bytes/s and operations / peak
FLOP/s, from the kernel count `kernel` of the configuration) over the
device time measured under the span, an occurrence.

Device time is taken by the span and not by an operation's name: the
time in which some operation ran on the chip between the span's start and
its end on the trace's clock. That fits a span that ends in a blocking
read (the device has then finished what the span launched) and holds
whatever programs do the work, so a change of kernels is read by the
same metric. None where the trace holds no such span, or the
configuration names no such count."""

from benchmark.reducers import _spans


def busy_ns(busy: list, a: int, b: int) -> int:
    """Nanoseconds of the merged busy intervals that lie in [a, b]."""
    return sum(max(0, min(b, e) - max(a, s)) for s, e in busy)


def read(ctx: dict, span: str, kernel: str):
    t = _spans.of_run(ctx)
    mods = [m for m in ctx["kernels"]
            if m.__name__.rsplit(".", 1)[-1] == kernel]
    if t is None or not mods or not t["busy"]:
        return None
    found = _spans.spans(t, span)
    if not found:
        return None
    busy = [iv for ivs in t["busy"].values() for iv in ivs]
    device_s = 1e-9 * sum(busy_ns(busy, a, b) for a, b, _, _ in found) / len(
        t["busy"])
    if device_s <= 0:
        return None
    c, peaks = mods[0].cost(ctx["batch"]), ctx["peaks"]
    tb = c["bytes"] / peaks["bytes_per_s"]
    tf = c["flops"] / peaks["flops_per_s"]
    print(f"[bench] roofline of {span}: least {1e3 * max(tb, tf):.3f} ms "
          f"an occurrence, bound by {'bytes' if tb >= tf else 'flops'}; "
          f"device busy {1e3 * device_s / len(found):.3f} ms an occurrence "
          f"over {len(found)}", flush=True)
    return 100.0 * max(tb, tf) * len(found) / device_s
