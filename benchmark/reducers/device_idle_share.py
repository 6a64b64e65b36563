"""Share (%) of the traced span in which no operation ran on the device:
1 - union of the device-op intervals / traced span."""


def read(ctx: dict):
    t = ctx["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
