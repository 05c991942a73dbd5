"""The fused DP's share of its memory roofline (%): the least time the
round's DP needs (``bench.roofline.dp_sizes`` bytes over the chip's HBM
peak) over the measured device time of every op of the fused pipeline
program, summed over the window's fused rounds."""


def read(win):
    t = win.trace
    if not t or not t.get("pipeline_s") or not win.peaks:
        return None
    need = sum(s["bytes"] for s in win.dp_sizes if s) / win.peaks["hbm_bytes_per_s"]
    if need <= 0:
        return None
    return 100.0 * need / t["pipeline_s"]
