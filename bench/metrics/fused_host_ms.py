"""Fused round host time per round (ms): ``prep_s + patch_s +
compact_s + backtrack_s + assembly_s`` of ``FusedState.last_segments``
(everything of ``_fused_run`` but the pipeline call)."""

HOST_SEGMENTS = ("prep_s", "patch_s", "compact_s", "backtrack_s", "assembly_s")


def read(win):
    if not win.fused_rounds:
        return None
    tot = sum(float(seg.get(k, 0.0)) for seg in win.segments for k in HOST_SEGMENTS)
    return 1e3 * tot / win.rounds
