"""Sum of one of the program's histograms over the window, as a share
(%) of the window's length on the same clock."""


def read(ctx: dict, hist: str):
    h = ctx["hist"].get(hist)
    if not h or h["count"] <= 0 or ctx["host_window_s"] <= 0:
        return None
    return 100.0 * h["sum"] / ctx["host_window_s"]
