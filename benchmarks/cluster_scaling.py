"""Cluster-scaling benchmark: grouped columnar engine vs legacy path.

Times one redistribution round at n ∈ {100, 1k, 10k} nodes for

 * **grouped**: the columnar engine — array partition, batched events,
   group-collapsed sparse DP (one super-stage per behaviour class),
   vectorized measurement;
 * **legacy**:  the per-node path — NodeState view materialization,
   per-instance option tables, one DP stage per receiver, per-node loop
   measurement —

plus allocator-only wall-clock (cold and warm caches) and a 20-round
grouped scenario at the top tier with failures/stragglers/arrivals.
Grouped-vs-legacy cap parity is asserted at every tier before timing.

Run as a module to emit ``BENCH_cluster_scaling.json``:

    PYTHONPATH=src python -m benchmarks.cluster_scaling [--fast]

``--check BENCH_cluster_scaling.json`` additionally guards against
regressions: fresh warm-round times must stay within a generous factor of
the committed reference (the reference is loaded before ``--out``
overwrites it, so both flags may point at the same file — CI does).
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmarks.common import csv_line, get_suite
from repro.cluster import ClusterSim, Scenario
from repro.cluster.controller import make_controller
from repro.kernels.ops import use_compile_cache

#: wall-clock guard for the top-tier 20-round grouped scenario (matches the
#: CI smoke budget; the acceptance bar for DESIGN.md §11)
SCENARIO_BUDGET_S = 60.0


def _sim(system, apps, surfs, n: int) -> ClusterSim:
    # grid-aligned uniform initial caps: the realistic fleet-provisioning
    # case, and it keeps the sparse DP state lattice at watt-step pitch
    return ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=0, initial_caps=(150.0, 150.0)
    )


def _budget(n: int) -> float:
    return float(min(2.0 * n, 8000.0))


def _legacy_round(sim: ClusterSim, ctrl, budget: float) -> float:
    """One legacy round: view materialization + per-instance DP + loop
    measurement (the pre-columnar engine's shape)."""
    t0 = time.perf_counter()
    _, recv, _ = sim.partition()
    sim.run_round(
        ctrl, budget=budget, receivers=recv, use_loop_measurement=True
    )
    return time.perf_counter() - t0


def _grouped_round(sim: ClusterSim, ctrl, budget: float) -> float:
    t0 = time.perf_counter()
    sim.run_round(ctrl, budget=budget)
    return time.perf_counter() - t0


def _alloc_times(sim: ClusterSim, budget: float) -> dict:
    """Allocator-only wall-clock: grouped vs legacy, cold and warm."""
    _, rows, _ = sim.partition_rows()
    batch = sim._receiver_batch(rows, None, False)
    out = {}
    ctrl = make_controller("ecoshift", sim.system)
    t0 = time.perf_counter()
    alloc_g = ctrl.allocate_grouped(batch, budget)
    out["grouped_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    ctrl.allocate_grouped(batch, budget)
    out["grouped_warm_s"] = time.perf_counter() - t0

    recv = sim.table.views(rows)
    apps = [n.app for n in recv]
    baselines = {n.app.name: n.caps for n in recv}
    seen = {n.app.name: sim._surface(n) for n in recv}
    ctrl_u = make_controller("ecoshift", sim.system, grouped=False)
    t0 = time.perf_counter()
    alloc_u = ctrl_u.allocate(apps, baselines, budget, seen)
    out["legacy_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    ctrl_u.allocate(apps, baselines, budget, seen)
    out["legacy_warm_s"] = time.perf_counter() - t0
    assert dict(alloc_g.caps) == dict(alloc_u.caps), "grouped/legacy divergence"
    return out


def _scenario(n_rounds: int, n: int, budget: float) -> Scenario:
    scen = Scenario.constant(n_rounds, budget=budget)
    scen = scen.with_failure(1, *range(0, max(1, n // 100)))
    scen = scen.with_straggler(min(2, n_rounds - 1), n // 2, 1.7)
    return scen


def run(lines: list[str], *, fast: bool = False, results: list | None = None):
    system, apps, surfs = get_suite("system1-a100")
    tiers = [100, 1000] if fast else [100, 1000, 10000]
    for n in tiers:
        budget = _budget(n)
        alloc = _alloc_times(_sim(system, apps, surfs, n), budget)

        sim_g = _sim(system, apps, surfs, n)
        ctrl_g = make_controller("ecoshift", system)
        t_round_cold = _grouped_round(sim_g, ctrl_g, budget)
        t_round_warm = _grouped_round(sim_g, ctrl_g, budget)

        sim_l = _sim(system, apps, surfs, n)
        ctrl_l = make_controller("ecoshift", system, grouped=False)
        t_legacy_cold = _legacy_round(sim_l, ctrl_l, budget)
        t_legacy_warm = _legacy_round(sim_l, ctrl_l, budget)

        speedup = t_legacy_warm / t_round_warm
        if n >= 10000:
            # acceptance bar (DESIGN.md §11.4); measured ~370x, so a 10x
            # floor is robust to shared-runner noise
            assert speedup >= 10.0, (
                f"grouped speedup at n={n} regressed to {speedup:.1f}x"
            )
        tier = {
            "n_nodes": n,
            "budget_w": budget,
            "alloc": alloc,
            "grouped_round_s": {"cold": t_round_cold, "warm": t_round_warm},
            "legacy_round_s": {"cold": t_legacy_cold, "warm": t_legacy_warm},
            "round_speedup_warm": speedup,
        }

        # top tier: a 20-round scenario with events, inside the CI guard
        if n == tiers[-1]:
            n_rounds = 20
            sim_s = _sim(system, apps, surfs, n)
            scen = _scenario(n_rounds, n, budget)
            t0 = time.perf_counter()
            trace = sim_s.run(scen, make_controller("ecoshift", system))
            elapsed = time.perf_counter() - t0
            assert trace.n_rounds == n_rounds
            assert np.isfinite(trace.improvement_trace).all()
            assert elapsed < SCENARIO_BUDGET_S, (
                f"{n}-node {n_rounds}-round scenario took {elapsed:.1f}s "
                f"(guard {SCENARIO_BUDGET_S}s)"
            )
            tier["scenario"] = {
                "n_rounds": n_rounds,
                "total_s": elapsed,
                "rounds_per_s": n_rounds / elapsed,
            }

        if results is not None:
            results.append(tier)
        lines.append(
            csv_line(
                f"cluster_scaling.n{n}",
                t_round_warm * 1e6,
                f"grouped_round_s={t_round_warm:.4f};"
                f"legacy_round_s={t_legacy_warm:.4f};"
                f"speedup={speedup:.1f}x;"
                f"alloc_grouped_warm_s={alloc['grouped_warm_s']:.4f};"
                f"alloc_legacy_warm_s={alloc['legacy_warm_s']:.4f}",
            )
        )


#: regression-guard tolerance vs a committed reference: generous, because
#: the reference was measured on a different (possibly idle) machine
CHECK_FACTOR = 5.0
CHECK_SLACK_S = 0.25


def check_against(reference: dict, results: list) -> list[str]:
    """Compare fresh warm-round times against a committed reference run.

    A tier regresses when its fresh grouped warm round exceeds
    ``CHECK_FACTOR x ref + CHECK_SLACK_S`` — loose enough for shared-runner
    noise, tight enough to catch an accidental return to per-node scaling
    (the legacy path is 60-370x slower at the upper tiers).  Only tiers
    present in both runs are compared.  Returns regression messages.
    """
    ref_by_n = {t["n_nodes"]: t for t in reference.get("tiers", [])}
    problems = []
    for tier in results:
        ref = ref_by_n.get(tier["n_nodes"])
        if ref is None:
            continue
        fresh = tier["grouped_round_s"]["warm"]
        budget = CHECK_FACTOR * ref["grouped_round_s"]["warm"] + CHECK_SLACK_S
        if fresh > budget:
            problems.append(
                f"n={tier['n_nodes']}: warm grouped round {fresh:.3f}s "
                f"exceeds {budget:.3f}s "
                f"({CHECK_FACTOR}x ref {ref['grouped_round_s']['warm']:.3f}s "
                f"+ {CHECK_SLACK_S}s)"
            )
    return problems


def main() -> None:
    use_compile_cache()
    import argparse
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="skip the 10k tier")
    ap.add_argument(
        "--out", default="BENCH_cluster_scaling.json", help="JSON output path"
    )
    ap.add_argument(
        "--check",
        default=None,
        metavar="REF_JSON",
        help="compare fresh warm-round times against a committed reference "
        "(loaded before --out overwrites it); exit 1 on regression",
    )
    args = ap.parse_args()

    reference = None
    if args.check:
        with open(args.check) as f:
            reference = json.load(f)

    lines: list[str] = ["name,us_per_call,derived"]
    results: list = []
    t0 = time.time()
    run(lines, fast=args.fast, results=results)
    payload = {
        "benchmark": "cluster_scaling",
        "fast": args.fast,
        "elapsed_s": time.time() - t0,
        "tiers": results,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print("\n".join(lines))
    print(f"# wrote {args.out} in {payload['elapsed_s']:.1f}s")

    if reference is not None:
        problems = check_against(reference, results)
        for p in problems:
            print(f"# REGRESSION: {p}", file=sys.stderr)
        if problems:
            sys.exit(1)
        print(f"# regression guard OK vs {args.check}")


if __name__ == "__main__":
    main()
