"""Dynamic-budget receding-horizon benchmark (DESIGN.md §15).

Day-scale scenarios riding the shipped grid-signal fixtures (96 points =
15-minute resolution): a CO2-intensity day on a flat cluster and a
solar-following budget on a racked cluster.  Three policies run through
identical sims per tier:

 * **myopic** — the default controller riding the instantaneous cap
   (H=1, today's behaviour, the baseline);
 * **reactive** — the signal-blind eco mode: the same controller under a
   uniformly derated budget (``ScaledProvider(base, ECO)``), i.e. the
   same average power reduction with no knowledge of *when* power is
   dirty;
 * **mpc** — the receding-horizon planner (``horizon=H``,
   ``eco_factor=ECO``) planning over the budget forecast weighted by the
   CO2 (or price) signal: it banks spend away from dirty rounds and
   toward clean ones.

Per tier the bench records total measured improvement (value), grams CO2
(sum of intensity x spent watts per round), dollars (price x spent), and
the derived perf-per-CO2 / perf-per-dollar.  **Compliance is validated
per round**: every policy's spent watts must stay under that round's
instantaneous budget (the planner only ever *shrinks* a round's budget).
The acceptance bar: MPC strictly beats myopic on perf-per-CO2 on the
CO2-day scenario.

Run as a module to emit ``BENCH_budget_horizon.json``:

    PYTHONPATH=src python -m benchmarks.budget_horizon [--fast]

``--check BENCH_budget_horizon.json`` guards fresh per-round times
against the committed reference (generous factor, shared-runner noise).
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmarks.common import csv_line, get_suite
from repro.cluster import ClusterSim, PowerTopology, scenario as sc
from repro.cluster import budget as bm
from repro.cluster.controller import make_controller
from repro.kernels.ops import use_compile_cache

#: planner knobs (full tiers); ``--fast`` shortens the horizon with the day
HORIZON = 12
ECO = 0.7


def _sim(system, apps, surfs, n, topology=None) -> ClusterSim:
    return ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=0,
        initial_caps=(150.0, 150.0), topology=topology,
    )


def _play(system, apps, surfs, n, scen, policy, topology=None, **ctrl_kw):
    """One full scenario replay; returns (result, seconds-per-round)."""
    sim = _sim(system, apps, surfs, n, topology=topology)
    ctrl = make_controller(policy, system, **ctrl_kw)
    t0 = time.perf_counter()
    res = sim.run(scen, ctrl)
    dt = time.perf_counter() - t0
    return res, dt / max(res.n_rounds, 1)


def _scores(res) -> dict:
    """Value / CO2 / dollars totals with per-round compliance validation."""
    value = 0.0
    grams = 0.0
    dollars = 0.0
    for rec in res.records:
        spent = rec.result.allocation.spent
        assert spent <= rec.result.budget + 1e-6, (
            f"round {rec.round}: spent {spent:.1f} W exceeds instantaneous "
            f"budget {rec.result.budget:.1f} W"
        )
        value += rec.avg_improvement
        if rec.carbon_intensity is not None:
            grams += rec.carbon_intensity * spent
        if rec.power_price is not None:
            dollars += rec.power_price * spent
    return {
        "value": value,
        "co2_g": grams,
        "dollars": dollars,
        "perf_per_co2": value / grams if grams > 0 else None,
        "perf_per_dollar": value / dollars if dollars > 0 else None,
        "compliant": True,
    }


def _policy_entry(name, res, per_round_s) -> dict:
    return {"policy": name, "round_s": per_round_s, **_scores(res)}


def _co2_day_tier(system, apps, surfs, *, fast: bool) -> dict:
    """Flat cluster through a grid-CO2 day under a constant site budget."""
    n = 64 if fast else 256
    n_rounds = 32 if fast else 96
    horizon = 8 if fast else HORIZON
    budget = 2.0 * n
    scen = sc.Scenario.carbon_aware(
        n_rounds, bm.ConstantProvider(budget)
    )
    cases = [
        ("myopic", scen, {}),
        (
            "reactive",
            scen.with_budget_provider(
                bm.ScaledProvider(bm.ConstantProvider(budget), ECO)
            ),
            {},
        ),
        ("mpc", scen, {"horizon": horizon, "eco_factor": ECO}),
    ]
    entry = {
        "tier": "co2_day_flat",
        "n_nodes": n,
        "n_rounds": n_rounds,
        "budget_w": budget,
        "horizon": horizon,
        "eco_factor": ECO,
        "policies": [],
    }
    for name, s, kw in cases:
        res, per_round = _play(system, apps, surfs, n, s, "ecoshift", **kw)
        entry["policies"].append(_policy_entry(name, res, per_round))
    by = {p["policy"]: p for p in entry["policies"]}
    assert by["mpc"]["perf_per_co2"] > by["myopic"]["perf_per_co2"], (
        f"MPC perf-per-CO2 {by['mpc']['perf_per_co2']:.4g} does not beat "
        f"myopic {by['myopic']['perf_per_co2']:.4g}"
    )
    entry["ppc_gain_vs_myopic"] = (
        by["mpc"]["perf_per_co2"] / by["myopic"]["perf_per_co2"]
    )
    entry["ppc_gain_vs_reactive"] = (
        by["mpc"]["perf_per_co2"] / by["reactive"]["perf_per_co2"]
    )
    return entry


def _solar_hier_tier(system, apps, surfs, *, fast: bool) -> dict:
    """Racked cluster on a solar-following budget (grid-backstop floor),
    CO2-weighted MPC vs myopic — the composed-provider scenario."""
    n = 48 if fast else 128
    n_racks = 4 if fast else 8
    n_rounds = 32 if fast else 96
    horizon = 8 if fast else HORIZON
    peak = 2.5 * n
    floor = 0.5 * n
    # racks comfortably above committed draw (~300 W/node at the initial
    # caps): the *solar budget* is the binding constraint in this tier
    topo = PowerTopology.uniform_racks(
        n, n_racks, rack_cap=320.0 * (n // n_racks) + peak / n_racks
    )
    provider = bm.solar_budget(peak, floor_watts=floor, n_rounds=n_rounds)
    scen = (
        sc.Scenario(
            n_rounds=n_rounds,
            budget=provider,
            carbon=bm.fixture_trace("co2_day", n_rounds),
            power_price=bm.fixture_trace("price_day", n_rounds),
        )
        .with_topology(topo)
    )
    entry = {
        "tier": "solar_hier",
        "n_nodes": n,
        "n_racks": n_racks,
        "n_rounds": n_rounds,
        "peak_w": peak,
        "floor_w": floor,
        "horizon": horizon,
        "eco_factor": ECO,
        "policies": [],
    }
    for name, kw in (
        ("myopic", {}),
        ("mpc", {"horizon": horizon, "eco_factor": ECO}),
    ):
        res, per_round = _play(
            system, apps, surfs, n, scen, "ecoshift_hier", topology=topo, **kw
        )
        entry["policies"].append(_policy_entry(name, res, per_round))
    by = {p["policy"]: p for p in entry["policies"]}
    entry["ppc_gain_vs_myopic"] = (
        by["mpc"]["perf_per_co2"] / by["myopic"]["perf_per_co2"]
    )
    return entry


def run(lines: list[str], *, fast: bool = False, results: list | None = None):
    system, apps, surfs = get_suite("system1-a100")
    for tier_fn in (_co2_day_tier, _solar_hier_tier):
        entry = tier_fn(system, apps, surfs, fast=fast)
        if results is not None:
            results.append(entry)
        for p in entry["policies"]:
            ppc = p["perf_per_co2"]
            lines.append(csv_line(
                f"budget_horizon.{entry['tier']}.{p['policy']}",
                p["round_s"] * 1e6,
                f"value={p['value']:.3f};co2_g={p['co2_g']:.0f};"
                f"ppc={ppc * 1e6 if ppc else 0.0:.3f}",
            ))


#: regression-guard tolerance vs a committed reference (benchmarks.*
#: convention: generous for shared-runner noise)
CHECK_FACTOR = 5.0
CHECK_SLACK_S = 0.25


def check_against(reference: dict, results: list) -> list[str]:
    """Fresh per-round times and the MPC quality bar vs the committed run."""
    ref_by_key = {
        (t["tier"], p["policy"]): p
        for t in reference.get("tiers", [])
        for p in t["policies"]
    }
    problems = []
    for tier in results:
        for p in tier["policies"]:
            ref = ref_by_key.get((tier["tier"], p["policy"]))
            if ref is None:
                continue
            allowed = CHECK_FACTOR * ref["round_s"] + CHECK_SLACK_S
            if p["round_s"] > allowed:
                problems.append(
                    f"{tier['tier']}.{p['policy']}: round "
                    f"{p['round_s']:.3f}s exceeds {allowed:.3f}s "
                    f"({CHECK_FACTOR}x ref {ref['round_s']:.3f}s "
                    f"+ {CHECK_SLACK_S}s)"
                )
        if tier["tier"] == "co2_day_flat" and tier["ppc_gain_vs_myopic"] <= 1.0:
            problems.append(
                f"{tier['tier']}: MPC perf-per-CO2 gain "
                f"{tier['ppc_gain_vs_myopic']:.3f}x fell to/under 1.0"
            )
    return problems


def main() -> None:
    use_compile_cache()
    import argparse
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="trimmed day")
    ap.add_argument(
        "--out", default="BENCH_budget_horizon.json", help="JSON output"
    )
    ap.add_argument(
        "--check",
        default=None,
        metavar="REF_JSON",
        help="compare fresh per-round times + the MPC quality bar against "
        "a committed reference (loaded before --out overwrites it); "
        "exit 1 on regression",
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
        "benchmark": "budget_horizon",
        "fast": args.fast,
        "elapsed_s": time.time() - t0,
        "horizon": HORIZON,
        "eco_factor": ECO,
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
