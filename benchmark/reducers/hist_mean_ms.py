"""Mean of one of the program's histograms over the window, in ms."""


def read(ctx: dict, hist: str):
    h = ctx["hist"].get(hist)
    if not h or h["count"] <= 0:
        return None
    return 1e3 * h["sum"] / h["count"]
