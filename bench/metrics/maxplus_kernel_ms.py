"""Device time of the (max,+) Pallas kernel per round (ms): the summed
durations of its trace events, found by the kernel's function name."""


def read(win):
    t = win.trace
    if not t or not t.get("kernel_s"):
        return None
    return 1e3 * t["kernel_s"] / win.rounds
