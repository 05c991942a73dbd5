"""Share of the window's rounds served by the fused device path (%):
the rise of ``fused_stats().rounds`` over the window, over rounds."""


def read(win):
    if not win.rounds:
        return None
    return 100.0 * win.fused_rounds / win.rounds
