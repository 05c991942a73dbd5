"""Device idle time inside the pipeline call per round (ms): idle device
time while the host was in the fused round's ``fused.launch`` or
``fused.wait`` span (launch, argument upload, gaps between the
program's stages), from the trace (``bench.spantrace``)."""

from bench import spantrace

SPANS = ("fused.launch", "fused.wait")


def read(win):
    red = spantrace.for_window(win)
    if not red or not any(s in red["idle_by_span"] for s in SPANS):
        return None
    return 1e3 * sum(red["idle_by_span"].get(s, 0.0) for s in SPANS) / win.rounds
