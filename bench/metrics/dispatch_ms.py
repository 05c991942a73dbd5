"""Pipeline call per round (ms): the fused round's ``dispatch_s``, the
host clock around the jitted pipeline through ``block_until_ready``
(launch, device time and wait together)."""


def read(win):
    if not win.fused_rounds:
        return None
    return 1e3 * sum(float(seg.get("dispatch_s", 0.0)) for seg in win.segments) / win.rounds
