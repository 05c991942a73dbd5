"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--fast`` trims sweeps for CI;
``--only fig10`` runs a single module.
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.kernels.ops import use_compile_cache


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="trimmed sweeps")
    ap.add_argument("--only", default=None, help="substring filter on modules")
    args = ap.parse_args()

    from benchmarks import (
        budget_horizon,
        cluster_scaling,
        fault_storm,
        hier_alloc,
        incremental_alloc,
        fig1_heatmaps,
        fig2_marginal_gain,
        fig5_budget_sweep,
        fig6_cap_sweep,
        fig9_distribution,
        fig10_oracle_gap,
        fig11_fairness,
        online_adaptation,
        pod_power_allocation,
        predictor_accuracy,
        roofline_report,
        straggler_response,
        table2_case_study,
    )

    modules = [
        ("fig1", fig1_heatmaps.run, False),
        ("fig2", fig2_marginal_gain.run, False),
        ("table2", table2_case_study.run, False),
        ("predictor", predictor_accuracy.run, False),
        ("fig5_7", fig5_budget_sweep.run, True),
        ("fig6_8", fig6_cap_sweep.run, True),
        ("fig9", fig9_distribution.run, True),
        ("fig10", fig10_oracle_gap.run, True),
        ("fig11", fig11_fairness.run, True),
        ("cluster_scaling", cluster_scaling.run, True),
        ("hier_alloc", hier_alloc.run, True),
        ("incremental_alloc", incremental_alloc.run, True),
        ("budget_horizon", budget_horizon.run, True),
        ("fault_storm", fault_storm.run, True),
        ("roofline", roofline_report.run, False),
        ("pod_power", pod_power_allocation.run, True),
        ("straggler", straggler_response.run, True),
        ("online_adaptation", online_adaptation.run, True),
    ]

    lines: list[str] = ["name,us_per_call,derived"]
    for name, fn, takes_fast in modules:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            if takes_fast:
                fn(lines, fast=args.fast)
            else:
                fn(lines)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - report, keep the harness alive
            lines.append(f"{name}.ERROR,0,{type(e).__name__}: {e}")
            print(f"# {name} FAILED: {e}", file=sys.stderr)
    print("\n".join(lines))


if __name__ == "__main__":
    main()
