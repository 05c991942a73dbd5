"""Device time of the sparse-to-dense option scatter per round (ms): the
device self time of the fused pipeline's ops in the ``option_scatter``
scope (``maxplus_stage_pallas_batched``'s scatter of each stage's
options onto a dense curve, every leaf stage), from the trace
(``bench.spantrace``)."""

from bench import spantrace


def read(win):
    red = spantrace.for_window(win)
    if not red or "option_scatter" not in red["scope_s"]:
        return None
    return 1e3 * red["scope_s"]["option_scatter"] / win.rounds
