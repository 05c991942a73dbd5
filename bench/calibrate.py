"""Readings that set the limits of the comparison, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... --seconds <s>

For each seed, one run of the cell through the harness (a short window,
the same sampled rounds a benchmark run checks) gives the program's
readings of every compared number; the control (the reference put in the
program's place, its DP in bfloat16, one precision below the float32 the
configurations state, its caps valued in float64 as the program values
its own) gives its readings on the same traffic's rounds.
Prints one JSON line per seed and side.  The benchmark's own runs never
run this.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, reference  # noqa: E402
from bench.cluster import Deployment, initial_state  # noqa: E402
from bench.traffic import STREAM_POPULATION, STREAM_SAMPLE, Traffic, rng_for  # noqa: E402

#: the control reads rounds drawn from the seed among this many of the
#: cell's traffic, this many of them
CONTROL_ROUNDS, CONTROL_CHECKED = 200, 6


def control_readings(config: dict, mix: dict, seed: int) -> dict:
    """Worst and smallest reading of each compared number over the
    control's checked rounds."""
    dep = Deployment(config)
    state = initial_state(dep, rng_for(seed, STREAM_POPULATION))
    dep.set_domain_caps(state)
    tr = Traffic(dep, mix, seed, state)
    pick = set(
        rng_for(seed, STREAM_SAMPLE).choice(CONTROL_ROUNDS, CONTROL_CHECKED, replace=False).tolist()
    )
    curves = reference.option_curves(dep)
    worst = {k: 0.0 for k in harness.CHECKS}
    smallest = {k: float("inf") for k in harness.CHECKS}
    for r in range(CONTROL_ROUNDS):
        _r, budget, _ev = tr.next_round()
        if r not in pick:
            continue
        rnd = reference.Round(dep, state, budget)
        got = reference.check_round(rnd, curves, *reference.control_answer(rnd, curves))
        for k in harness.CHECKS:
            worst[k] = max(worst[k], got[k])
            smallest[k] = min(smallest[k], got[k])
    return {"worst": worst, "smallest_round": smallest, "rounds_checked": len(pick)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()

    bench = harness.load_benchmark()
    cell, config, mix, limits = harness.cell_inputs(bench, args.workload)
    harness.setup_process(int(cell["chips"]))
    for seed in args.seeds:
        t0 = time.perf_counter()
        out, _ = harness.run_cell(
            config, mix, limits, seed=seed, seconds=args.seconds, trace=False, t_start=t0,
        )
        print(json.dumps({
            "side": "program", "workload": args.workload, "seed": seed,
            "correct": out["correct"], "attempted": out["attempted"],
            "device": out["device"], "round_ms": out["metrics"]["round_ms"]["value"],
            "readings": {k: v["value"] for k, v in out["checks"].items()},
        }), flush=True)
        ctl = control_readings(config, mix, seed)
        print(json.dumps({
            "side": "control", "workload": args.workload, "seed": seed, **ctl,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
