"""Ratio of what the window added to two of the program's counters: the
driver's `hist_open` and `hist_close` hold them as they stood when the
window opened and where its host-side numbers end (`drivers/batch.py`),
and `ctx["hist"]` is their difference. Set-up's work and the served
step's are in neither. None where the driver kept no such counter, or the
window added nothing to the second."""


def read(ctx: dict, num: str, den: str):
    hist = ctx["hist"]
    if num not in hist or den not in hist or hist[den]["count"] <= 0:
        return None
    return hist[num]["count"] / hist[den]["count"]
