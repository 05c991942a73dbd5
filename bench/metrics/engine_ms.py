"""Engine host time per round (ms): the benchmark's ``ingest`` span
(``apply_events`` + ``invalidate``) plus the engine's own phases
``partition_s + batch_s + conserve_s + actuate_s`` of
``ClusterSim.last_round_profile``."""

ENGINE_PHASES = ("partition_s", "batch_s", "conserve_s", "actuate_s")


def read(win):
    if not win.rounds or not win.profiles:
        return None
    tot = sum(sp["ingest"] for sp in win.spans)
    tot += sum(float(p.get(k, 0.0)) for p in win.profiles for k in ENGINE_PHASES)
    return 1e3 * tot / win.rounds
