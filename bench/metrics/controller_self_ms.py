"""Controller host self time per round (ms): the time of the program's
``controller.*`` spans less the spans opened inside them, read from the
trace's host plane over the traced window (``bench.spantrace``), on the
clock of the device metrics."""

from bench import spantrace


def read(win):
    red = spantrace.for_window(win)
    if not red:
        return None
    own = [t for k, t in red["self_s"].items() if k.startswith("controller.")]
    if not own:
        return None
    return 1e3 * sum(own) / win.rounds
