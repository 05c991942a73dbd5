"""Per-phase wall-clock breakdown of cluster redistribution rounds.

Runs a short scenario at a chosen scale/topology and prints, per round,
the engine's phase timings (``ClusterSim.last_round_profile``):

    partition  donor/receiver split + per-domain headroom accounting
    batch      receiver-batch materialization (delta-patched when warm)
    allocate   the controller's solve (grouping + DP + assembly)
    conserve   sim-side per-domain draw accounting / cap enforcement
    measure    vectorized measurement + telemetry emission

With ``--fused`` the controller runs the device-resident fused round
(DESIGN.md §14) and each row also shows the device/host split of the
allocate phase (the fused round's ``dispatch_s`` — seconds in the jitted
pipeline call, launch and wait — plus which solver produced the round).  With ``--fused --churn > 0`` the
allocate phase of each structure-changing round further breaks into the
fused segments (DESIGN.md §17): ``prep`` (host row prep + layout),
``patch`` (donated dirty-row scatter), ``compact`` (device-side bank
repack), ``dispatch`` (the jitted pipeline), ``backtrack`` (decision
readback) and ``assembly`` (host pick assembly) — so a churn regression
is attributable to one segment.  ``--json`` emits the whole run as
one JSON object on stdout (per-round phase timings in ms, device-vs-host
split, fused segments, fused-state counters) for tooling; the human
table is suppressed.

plus a cProfile top-N of one steady-state round, so future perf PRs can
see exactly where round time goes before touching anything.

With ``--depth N`` (N >= 3) the run uses an N-level uniform tree
(site → row → … → chassis, via ``benchmarks.hier_alloc._deep_topology``)
instead of the two-level rack topology, and the run ends with a
per-level breakdown — domains, aggregate draw vs capped headroom, worst
utilization and how many caps bind at each level of the tree.

    PYTHONPATH=src python tools/profile_round.py [--nodes 10000]
        [--racks 16] [--depth 4] [--churn 0.01] [--rounds 6]
        [--policy ecoshift_hier] [--from-scratch] [--fused] [--json]
        [--top 20]
"""

from __future__ import annotations

import argparse
import cProfile
import dataclasses
import io
import json
import os
import pstats
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import get_suite  # noqa: E402
from repro.kernels.ops import use_compile_cache  # noqa: E402
from benchmarks.incremental_alloc import (  # noqa: E402
    _budget,
    _churn_events,
    _sim,
    _topology,
)
from repro.cluster.controller import make_controller  # noqa: E402

PHASES = ("partition_s", "batch_s", "allocate_s", "conserve_s", "measure_s")

#: fused allocate-phase segments (DESIGN.md §17), in execution order
SEGMENTS = (
    "prep_s", "patch_s", "compact_s", "dispatch_s", "backtrack_s",
    "assembly_s",
)


def _level_summary(sim, topo) -> list[dict]:
    """Per-tree-level aggregate of the last round's domain accounting:
    domain count, total draw, total (finite) cap, worst utilization and
    how many caps bind (>= 99.9% utilized) at each depth."""
    if topo is None or not sim.last_domain_draw:
        return []
    levels: dict[int, dict] = {}
    for i, dom in enumerate(topo.domains):
        d = int(topo.depth[i])
        lv = levels.setdefault(d, {
            "level": d, "domains": 0, "draw_w": 0.0, "cap_w": 0.0,
            "max_util": 0.0, "binding": 0,
        })
        draw = float(sim.last_domain_draw.get(dom.name, 0.0))
        cap = float(sim.last_domain_caps.get(dom.name, float("inf")))
        lv["domains"] += 1
        lv["draw_w"] += draw
        if cap < 1e17:  # finite (constraining) cap
            lv["cap_w"] += cap
            util = draw / cap if cap > 0 else 0.0
            lv["max_util"] = max(lv["max_util"], util)
            lv["binding"] += util >= 0.999
    return [levels[k] for k in sorted(levels)]


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=10000)
    ap.add_argument("--racks", type=int, default=16,
                    help="0 = flat (no topology)")
    ap.add_argument("--depth", type=int, default=0,
                    help="N >= 3: use an N-level uniform tree (fan-out 4 "
                    "per level) instead of the two-level rack topology, "
                    "and print a per-level breakdown")
    ap.add_argument("--churn", type=float, default=0.01,
                    help="per-round churn fraction (0 = event-free)")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--policy", default=None,
                    help="controller policy (default: ecoshift_hier with "
                    "racks, ecoshift flat)")
    ap.add_argument("--from-scratch", action="store_true",
                    help="profile the incremental=False baseline instead")
    ap.add_argument("--fused", action="store_true",
                    help="device-resident fused rounds (DESIGN.md §14)")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON object instead of the table "
                    "(implies no cProfile pass)")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args()

    system, apps, surfs = get_suite("system1-a100")
    n = args.nodes
    budget = _budget(n)
    if args.depth >= 3:
        from benchmarks.hier_alloc import _deep_topology

        topo = _deep_topology(
            system, apps, surfs, n, (4,) * (args.depth - 1), budget
        )
    elif args.racks > 0:
        topo = _topology(system, apps, surfs, n, args.racks, budget)
    else:
        topo = None
    policy = args.policy or ("ecoshift_hier" if topo is not None else "ecoshift")
    sim = _sim(system, apps, surfs, n, topology=topo)
    ctrl = make_controller(
        policy, system,
        incremental=not args.from_scratch,
        fused=args.fused,
    )

    rng = np.random.default_rng(11)
    _, recv, _ = sim.partition_rows()
    recv_apps = sorted(
        {sim.table.strings[g] for g in sim.table.base_gid[recv]}
    )
    app_by_name = {a.name: a for a in apps}
    racks = (
        [d.name for d in topo.domains if d.is_leaf] if topo is not None else None
    )

    def one_round(r: int) -> float:
        if args.churn > 0 and r >= 1:
            ev = _churn_events(
                sim, rng, r, int(n * args.churn), recv_apps, app_by_name, racks
            )
            touched = sim.apply_events(ev)
            ctrl.invalidate(touched)
        t0 = time.perf_counter()
        sim.run_round(ctrl, budget=budget, round_index=r)
        return time.perf_counter() - t0

    show_segments = args.fused and args.churn > 0
    rounds: list[dict] = []
    if not args.json:
        header = "round  total_ms  " + "  ".join(p[:-2] for p in PHASES)
        if args.fused:
            header += "  device_ms  solver"
        print(f"{policy} n={n} racks={args.racks} depth={args.depth} "
              f"churn={args.churn:.1%} "
              f"incremental={not args.from_scratch} fused={args.fused}")
        print(header)
        if show_segments:
            print("       segments: " + "  ".join(s[:-2] for s in SEGMENTS))
    for r in range(args.rounds):
        total = one_round(r)
        prof = sim.last_round_profile
        solver = str(prof.get("alloc_solver", "")) or "-"
        fallback = str(prof.get("alloc_fallback_reason", ""))
        segments = ctrl.fused_segments() if args.fused else {}
        device_s = float(segments.get("dispatch_s", 0.0)) if solver == "fused" else 0.0
        fstats = ctrl.fused_stats() if args.fused else None
        rounds.append({
            "round": r,
            "total_ms": total * 1e3,
            **{p[:-2] + "_ms": float(prof.get(p, 0.0)) * 1e3 for p in PHASES},
            "alloc_device_ms": device_s * 1e3,
            "alloc_host_ms": (float(prof.get("allocate_s", 0.0)) - device_s)
            * 1e3,
            "alloc_solver": solver,
            "alloc_fallback_reason": fallback,
            **(
                {
                    "segments_ms": {
                        s[:-2]: float(segments.get(s, 0.0)) * 1e3
                        for s in SEGMENTS
                    },
                    "alloc_fused_rebuilds": fstats.rebuilds,
                    "alloc_fused_compactions": fstats.compactions,
                    "alloc_fused_slack_utilization": fstats.slack_utilization,
                }
                if args.fused
                else {}
            ),
        })
        if not args.json:
            cols = "  ".join(
                f"{float(prof.get(p, 0.0)) * 1e3:9.1f}" for p in PHASES
            )
            row = f"{r:5d}  {total * 1e3:8.1f}  {cols}"
            if args.fused:
                row += f"  {device_s * 1e3:9.2f}  {solver}"
                if fallback:
                    row += f" ({fallback})"
            print(row)
            if show_segments and segments:
                seg_cols = "  ".join(
                    f"{s[:-2]}={float(segments.get(s, 0.0)) * 1e3:.1f}"
                    for s in SEGMENTS
                )
                print(f"       {seg_cols}")

    levels = _level_summary(sim, topo)
    if not args.json and levels:
        print("\nlevel  domains     draw_w      cap_w  max_util  binding")
        for lv in levels:
            cap = f"{lv['cap_w']:10.0f}" if lv["cap_w"] else "       inf"
            print(f"{lv['level']:5d}  {lv['domains']:7d}  "
                  f"{lv['draw_w']:9.0f}  {cap}  "
                  f"{lv['max_util']:8.3f}  {lv['binding']:7d}")

    if args.json:
        out = {
            "policy": policy,
            "nodes": n,
            "racks": args.racks,
            "depth": args.depth,
            "churn": args.churn,
            "incremental": not args.from_scratch,
            "fused": args.fused,
            "rounds": rounds,
            "levels": levels,
        }
        if args.fused:
            out["fused_stats"] = dataclasses.asdict(ctrl.fused_stats())
        json.dump(out, sys.stdout, indent=2)
        print()
        return

    pr = cProfile.Profile()
    pr.enable()
    one_round(args.rounds)
    pr.disable()
    s = io.StringIO()
    pstats.Stats(pr, stream=s).sort_stats("cumulative").print_stats(args.top)
    print(s.getvalue())


if __name__ == "__main__":
    main()
