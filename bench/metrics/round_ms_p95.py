"""95th percentile of the window's round times (ms), host clock around
each whole round (events, ``run_round``, device sync)."""

import statistics


def read(win):
    if len(win.wall_s) < 20:
        return None
    return 1e3 * statistics.quantiles(win.wall_s, n=20)[-1]
