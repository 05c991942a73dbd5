"""Controller host time per round (ms) outside the fused round:
``allocate_s`` minus the fused segments (grouping sync, option tables,
caches, and the whole solve in rounds the fused path did not serve)."""


def read(win):
    if not win.profiles:
        return None
    tot = 0.0
    for p, seg in zip(win.profiles, win.segments):
        tot += float(p.get("allocate_s", 0.0)) - sum(seg.values())
    return 1e3 * tot / win.rounds
