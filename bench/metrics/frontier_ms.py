"""Device time of the frontier aggregation per round (ms): the device
self time of the fused pipeline's ops in the ``frontier_wave<i>`` scopes
(each depth wave's (max,+) combine and cap mask), summed over waves,
from the trace (``bench.spantrace``)."""

from bench import spantrace


def read(win):
    red = spantrace.for_window(win)
    if not red:
        return None
    waves = [t for k, t in red["scope_s"].items() if k.startswith("frontier_wave")]
    if not waves:
        return None
    return 1e3 * sum(waves) / win.rounds
