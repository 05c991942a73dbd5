"""Emulated telemetry per round (ms): ``measure_s`` of
``ClusterSim.last_round_profile``."""


def read(win):
    if not win.profiles:
        return None
    return 1e3 * sum(float(p.get("measure_s", 0.0)) for p in win.profiles) / win.rounds
