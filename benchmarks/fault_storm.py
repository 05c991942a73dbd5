"""Chaos benchmark: value retention + cap safety under fault storms
(DESIGN.md §18).

Three tiers over identical sims:

 * **storm_sweep** — a flat cluster under seeded fault storms of rising
   intensity (per-channel per-round probability 0 -> 0.30: telemetry
   drops/corruption + actuation NACK/partial/delay).  Per rate the bench
   records delivered value, value retention vs the clean run, the worst
   pre-derate PowerGuard excursion, and the number of rounds whose
   *settled* draw exceeded the budget — the chaos invariant is that the
   last number is **zero at every rate** (a stuck actuator causes at most
   a sub-round excursion, clawed back by the same round's derate).
 * **storm_hier** — a racked cluster under the heaviest storm plus
   controller crashes; the invariant extends to every power-domain cap
   (settled per-domain draw <= cap, every round, no consecutive-round
   excursions).
 * **crash_restore** — controller crash mid-run with snapshot restore:
   ``recovery_rounds`` counts post-crash rounds whose allocation differs
   from the uninterrupted reference (bit-for-bit restore => 0).

Run as a module to emit ``BENCH_fault_storm.json``:

    PYTHONPATH=src python -m benchmarks.fault_storm [--fast]

``--check BENCH_fault_storm.json`` guards fresh per-round times and the
chaos invariants against the committed reference.
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmarks.common import csv_line, get_suite
from repro.cluster import ClusterSim, PowerTopology, Scenario
from repro.cluster.controller import make_controller
from repro.cluster.faults import ControllerCrash
from repro.kernels.ops import use_compile_cache

#: per-channel per-round fault probabilities swept by the flat tier
RATES = (0.0, 0.05, 0.15, 0.30)


def _budget_trace(n_rounds: int, nominal: float) -> list[float]:
    """Deterministic varying budget (NACKs are invisible on a constant
    trace: keeping yesterday's caps *is* the command)."""
    t = np.arange(n_rounds)
    return (nominal * (1.0 + 0.5 * np.sin(2.0 * np.pi * t / 7.0))).tolist()


def _storm(scen: Scenario, rate: float, *, seed: int, crash_rounds=()):
    if rate <= 0.0 and not crash_rounds:
        return scen
    return scen.with_fault_storm(
        seed=seed,
        telemetry_drop=rate / 2,
        telemetry_delay=rate / 2,
        telemetry_corrupt=rate,
        telemetry_stale=rate / 2,
        actuation_nack=rate,
        actuation_partial=rate,
        actuation_delay=rate / 2,
        node_fraction=0.3,
        crash_rounds=crash_rounds,
    )


def _play(system, apps, surfs, n, scen, policy, topology=None):
    sim = ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=0, topology=topology
    )
    ctrl = make_controller(policy, system)
    t0 = time.perf_counter()
    res = sim.run(scen, ctrl)
    dt = time.perf_counter() - t0
    return res, dt / max(res.n_rounds, 1)


def _safety(res) -> dict:
    """Settled-draw safety counters over a trace (chaos invariants)."""
    overdraw_rounds = 0
    consecutive = 0
    max_consecutive = 0
    max_excursion = 0.0
    derate_total = 0.0
    nack_rounds = 0
    for rec in res.records:
        extra = sum(
            float(np.sum(t.allocated_caps) - np.sum(t.baseline_caps))
            for t in rec.telemetry
        )
        violated = extra > rec.result.budget + 1e-6
        if rec.domain_draw:
            violated = violated or any(
                w > rec.domain_caps[d] + 1e-6
                for d, w in rec.domain_draw.items()
            )
        if violated:
            overdraw_rounds += 1
            consecutive += 1
            max_consecutive = max(max_consecutive, consecutive)
        else:
            consecutive = 0
        max_excursion = max(max_excursion, rec.overdraw_w)
        derate_total += rec.derate_w
        nack_rounds += bool(rec.nacked)
    return {
        "overdraw_rounds": overdraw_rounds,
        "max_consecutive_overdraw": max_consecutive,
        "max_excursion_w": max_excursion,
        "derate_total_w": derate_total,
        "nack_rounds": nack_rounds,
    }


def _storm_sweep_tier(system, apps, surfs, *, fast: bool) -> dict:
    n = 32 if fast else 64
    n_rounds = 12 if fast else 24
    budgets = _budget_trace(n_rounds, 40.0 * n)
    entry = {
        "tier": "storm_sweep_flat",
        "n_nodes": n,
        "n_rounds": n_rounds,
        "rates": [],
    }
    clean_value = None
    for rate in RATES:
        scen = _storm(Scenario(n_rounds, budget=budgets), rate, seed=17)
        res, per_round = _play(system, apps, surfs, n, scen, "ecoshift")
        value = float(sum(r.avg_improvement for r in res.records))
        if rate == 0.0:
            clean_value = value
        safety = _safety(res)
        assert safety["overdraw_rounds"] == 0, (
            f"rate {rate}: settled draw exceeded the budget in "
            f"{safety['overdraw_rounds']} round(s)"
        )
        entry["rates"].append({
            "rate": rate,
            "round_s": per_round,
            "value": value,
            "value_retention": value / clean_value if clean_value else None,
            **safety,
        })
    return entry


def _storm_hier_tier(system, apps, surfs, *, fast: bool) -> dict:
    n = 30 if fast else 60
    n_racks = 3 if fast else 6
    n_rounds = 12 if fast else 24
    budgets = _budget_trace(n_rounds, 35.0 * n)
    # racks sized so both the budget and the rack caps bind under NACKs
    topo = PowerTopology.uniform_racks(
        n, n_racks, rack_cap=300.0 * (n // n_racks) + 18.0 * n
    )
    scen = _storm(
        Scenario(n_rounds, budget=budgets).with_topology(topo),
        0.30,
        seed=23,
        crash_rounds=(n_rounds // 2,),
    )
    res, per_round = _play(
        system, apps, surfs, n, scen, "ecoshift_hier", topology=topo
    )
    safety = _safety(res)
    assert safety["overdraw_rounds"] == 0, (
        f"settled domain draw exceeded a cap in "
        f"{safety['overdraw_rounds']} round(s)"
    )
    assert safety["max_consecutive_overdraw"] == 0
    return {
        "tier": "storm_hier",
        "n_nodes": n,
        "n_racks": n_racks,
        "n_rounds": n_rounds,
        "rate": 0.30,
        "round_s": per_round,
        "value": float(sum(r.avg_improvement for r in res.records)),
        **safety,
    }


def _crash_restore_tier(system, apps, surfs, *, fast: bool) -> dict:
    n = 32 if fast else 64
    n_rounds = 12 if fast else 24
    crash_at = n_rounds // 2
    budgets = _budget_trace(n_rounds, 40.0 * n)
    clean = Scenario(n_rounds, budget=budgets)
    ref, _ = _play(system, apps, surfs, n, clean, "ecoshift")
    entry = {
        "tier": "crash_restore",
        "n_nodes": n,
        "n_rounds": n_rounds,
        "crash_round": crash_at,
        "cases": [],
    }
    for name, restore in (("restore", True), ("cold", False)):
        scen = clean.with_faults(
            [ControllerCrash(round=crash_at, restore=restore)]
        )
        res, per_round = _play(system, apps, surfs, n, scen, "ecoshift")
        recovery = sum(
            dict(a.result.allocation.caps) != dict(b.result.allocation.caps)
            for a, b in zip(
                ref.records[crash_at:], res.records[crash_at:]
            )
        )
        if restore:
            assert recovery == 0, (
                f"snapshot-restored run diverged for {recovery} round(s)"
            )
        entry["cases"].append({
            "case": name,
            "round_s": per_round,
            "recovery_rounds": int(recovery),
        })
    return entry


def run(lines: list[str], *, fast: bool = False, results: list | None = None):
    system, apps, surfs = get_suite("system1-a100")
    for tier_fn in (_storm_sweep_tier, _storm_hier_tier, _crash_restore_tier):
        entry = tier_fn(system, apps, surfs, fast=fast)
        if results is not None:
            results.append(entry)
        if entry["tier"] == "storm_sweep_flat":
            for r in entry["rates"]:
                ret = r["value_retention"]
                lines.append(csv_line(
                    f"fault_storm.sweep.rate{r['rate']:.2f}",
                    r["round_s"] * 1e6,
                    f"value={r['value']:.3f};"
                    f"retention={ret if ret is not None else 1.0:.3f};"
                    f"max_excursion_w={r['max_excursion_w']:.1f};"
                    f"overdraw_rounds={r['overdraw_rounds']}",
                ))
        elif entry["tier"] == "storm_hier":
            lines.append(csv_line(
                "fault_storm.hier.rate0.30",
                entry["round_s"] * 1e6,
                f"value={entry['value']:.3f};"
                f"max_excursion_w={entry['max_excursion_w']:.1f};"
                f"overdraw_rounds={entry['overdraw_rounds']}",
            ))
        else:
            for c in entry["cases"]:
                lines.append(csv_line(
                    f"fault_storm.crash.{c['case']}",
                    c["round_s"] * 1e6,
                    f"recovery_rounds={c['recovery_rounds']}",
                ))


#: regression-guard tolerance vs a committed reference (benchmarks.*
#: convention: generous for shared-runner noise)
CHECK_FACTOR = 5.0
CHECK_SLACK_S = 0.25


def check_against(reference: dict, results: list) -> list[str]:
    """Fresh per-round times + chaos invariants vs the committed run."""
    ref_times = {}
    for t in reference.get("tiers", []):
        if t["tier"] == "storm_sweep_flat":
            for r in t["rates"]:
                ref_times[("sweep", r["rate"])] = r["round_s"]
        elif t["tier"] == "storm_hier":
            ref_times[("hier", t["rate"])] = t["round_s"]
        else:
            for c in t["cases"]:
                ref_times[("crash", c["case"])] = c["round_s"]

    problems = []

    def _time_check(key, round_s):
        ref = ref_times.get(key)
        if ref is None:
            return
        allowed = CHECK_FACTOR * ref + CHECK_SLACK_S
        if round_s > allowed:
            problems.append(
                f"{key}: round {round_s:.3f}s exceeds {allowed:.3f}s "
                f"({CHECK_FACTOR}x ref {ref:.3f}s + {CHECK_SLACK_S}s)"
            )

    for t in results:
        if t["tier"] == "storm_sweep_flat":
            for r in t["rates"]:
                _time_check(("sweep", r["rate"]), r["round_s"])
                if r["overdraw_rounds"] != 0:
                    problems.append(
                        f"sweep rate {r['rate']}: "
                        f"{r['overdraw_rounds']} settled overdraw round(s)"
                    )
        elif t["tier"] == "storm_hier":
            _time_check(("hier", t["rate"]), t["round_s"])
            if t["overdraw_rounds"] != 0:
                problems.append(
                    f"hier: {t['overdraw_rounds']} settled overdraw round(s)"
                )
        else:
            for c in t["cases"]:
                _time_check(("crash", c["case"]), c["round_s"])
                if c["case"] == "restore" and c["recovery_rounds"] != 0:
                    problems.append(
                        f"crash_restore: restored run diverged for "
                        f"{c['recovery_rounds']} round(s)"
                    )
    return problems


def main() -> None:
    use_compile_cache()
    import argparse
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="trimmed storm")
    ap.add_argument(
        "--out", default="BENCH_fault_storm.json", help="JSON output"
    )
    ap.add_argument(
        "--check",
        default=None,
        metavar="REF_JSON",
        help="compare fresh per-round times + chaos invariants against a "
        "committed reference (loaded before --out overwrites it); "
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
        "benchmark": "fault_storm",
        "fast": args.fast,
        "elapsed_s": time.time() - t0,
        "rates": list(RATES),
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
