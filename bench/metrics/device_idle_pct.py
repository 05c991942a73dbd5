"""Device idle share of the traced window (%): 1 - (union of the
intervals in which a device op ran) / window, averaged over chips."""


def read(win):
    t = win.trace
    if not t or not t.get("busy_s") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
