"""Hierarchical vs flat allocation benchmark (DESIGN.md §12).

For n ∈ {1k, 10k} nodes and rack fan-outs {1, 4, 16}, times one
redistribution round through

 * **flat**: the group-collapsed columnar engine (no topology) — the PR 3
   reference path;
 * **hier**: the same engine with a site → rack PowerTopology attached and
   the two-level capped-frontier solver (``ecoshift_hier``);

and reports achieved performance (average measured improvement) plus each
path's worst per-domain overdraw — the flat allocator ignores rack caps
and overdraws tight racks, the hierarchical one never does (engine-
asserted).  Rack caps are set to committed draw + 60% of the rack's
budget share, so the caps genuinely bind.

At fan-out 1 the topology degenerates to a single root and the
hierarchical allocation is asserted cap-for-cap equal to the flat one; at
10k nodes the multi-domain warm round must finish within 2x the flat warm
round (the DESIGN.md §12 acceptance bar).

Deep tiers (ISSUE 8) then time 4-level site → row → PDU → chassis trees
with binding caps at every level — up to 100k nodes, whose warm round
must land within 3x the same run's 10k hier-16 warm round — through both
the host incremental controller and the fused device-resident one.
``--smoke-1m`` builds (and coverage-validates) a million-node 4-level
tree and solves one sampled-PDU sub-tree round.

Run as a module to emit ``BENCH_hier_alloc.json``:

    PYTHONPATH=src python -m benchmarks.hier_alloc [--fast]
"""

from __future__ import annotations

import json
import time

from benchmarks.common import csv_line, get_suite
from repro.cluster import ClusterSim, PowerDomain, PowerTopology
from repro.cluster.controller import make_controller
from repro.kernels.ops import use_compile_cache

#: acceptance bar: multi-domain round time vs the flat grouped round
MAX_RATIO_VS_FLAT = 2.0

#: rack headroom as a fraction of the rack's even budget share
RACK_HEADROOM_FRAC = 0.6

#: acceptance bar (ISSUE 8): the 100k-node 4-level warm round must land
#: within this factor of the 10k hier-16 warm round — the larger of the
#: same run's measurement and the committed anchor below, so an
#: unusually quick 10k round on a fast machine doesn't turn a 10x node
#: scale-up into a flaky failure
DEEP_MAX_RATIO_VS_10K = 3.0

#: committed BENCH_hier_alloc.json 10k hier-16 warm round (seconds) at
#: the time the deep tiers landed; floors the ratio bar's denominator
DEEP_ANCHOR_10K_WARM_S = 0.1543

#: deep-tree per-level headroom fractions (level 1 = rows, then PDUs,
#: then leaf chassis) of each domain's node-proportional budget share —
#: strictly tightening down the tree, so every level genuinely binds
DEEP_LEVEL_FRACS = (0.9, 0.75, 0.6)

#: deep bench tiers: (n_nodes, fanouts) — 4-level site → row → PDU →
#: chassis trees; the 100k tier is the ISSUE 8 scale target
DEEP_TIERS = [(1000, (2, 2, 2)), (100_000, (4, 5, 5))]


def _sim(system, apps, surfs, n: int, topology=None) -> ClusterSim:
    return ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=0,
        initial_caps=(150.0, 150.0), topology=topology,
    )


def _budget(n: int) -> float:
    return float(min(2.0 * n, 8000.0))


def _topology(system, apps, surfs, n: int, n_racks: int, budget: float):
    """Site → rack tree with *per-rack* binding caps: each rack gets its
    own committed draw + 60% of its even budget share, so every rack's
    cap genuinely binds (fan-out 1 keeps an unconstrained root — the
    parity anchor)."""
    if n_racks == 1:
        return PowerTopology.single_root(n, cap=1e18)
    probe = _sim(
        system, apps, surfs, n,
        topology=PowerTopology.uniform_racks(n, n_racks, rack_cap=1e15),
    )
    _, committed, _ = probe.domain_headroom(0)
    rack_extra = RACK_HEADROOM_FRAC * budget / n_racks
    racks = tuple(
        PowerDomain(
            name=probe.topology.domains[i].name,
            cap=float(committed[i]) + rack_extra,
            nodes=probe.topology.domains[i].nodes,
        )
        for i in probe.topology.leaf_ids
    )
    return PowerTopology(PowerDomain(name="site", cap=1e18, children=racks))


def _node_counts(dom, index, out) -> int:
    i = index[dom.name]
    if dom.children:
        out[i] = sum(_node_counts(c, index, out) for c in dom.children)
    else:
        out[i] = sum(hi - lo for lo, hi in dom.nodes)
    return out[i]


def _deep_topology(system, apps, surfs, n: int, fanouts, budget: float):
    """Arbitrary-depth site → row → PDU → chassis tree with binding caps
    at *every* level: each domain gets its committed draw plus a
    per-level fraction of its node-proportional budget share, the
    fractions tightening toward the leaves (root stays unconstrained —
    the cluster budget is the binding root signal)."""
    probe = _sim(
        system, apps, surfs, n,
        topology=PowerTopology.uniform_tree(
            n, fanouts, [1e15] * (len(fanouts) + 1)
        ),
    )
    _, committed, _ = probe.domain_headroom(0)
    index = probe.topology.index
    counts: dict[int, int] = {}
    _node_counts(probe.topology.domains[0], index, counts)

    def recap(dom, depth):
        i = index[dom.name]
        if depth == 0:
            cap = 1e18
        else:
            frac = DEEP_LEVEL_FRACS[min(depth - 1, len(DEEP_LEVEL_FRACS) - 1)]
            cap = float(committed[i]) + frac * budget * counts[i] / n
        return PowerDomain(
            name=dom.name,
            cap=cap,
            nodes=dom.nodes,
            children=tuple(recap(c, depth + 1) for c in dom.children),
        )

    return PowerTopology(recap(probe.topology.domains[0], 0), n_nodes=n)


def _timed_round(sim, ctrl, budget: float) -> tuple[float, object]:
    t0 = time.perf_counter()
    res = sim.run_round(ctrl, budget=budget)
    return time.perf_counter() - t0, res


def _max_overdraw(sim) -> float:
    if not sim.last_domain_draw:
        return 0.0
    return max(
        0.0,
        max(
            sim.last_domain_draw[k] - sim.last_domain_caps[k]
            for k in sim.last_domain_draw
        ),
    )


def run(lines: list[str], *, fast: bool = False, results: list | None = None):
    system, apps, surfs = get_suite("system1-a100")
    tiers = [1000] if fast else [1000, 10000]
    fanouts = [1, 4, 16]
    warm_10k_hier16 = None
    for n in tiers:
        budget = _budget(n)

        # flat grouped reference (no topology): cold + warm round
        sim_f = _sim(system, apps, surfs, n)
        ctrl_f = make_controller("ecoshift", system)
        t_flat_cold, res_flat = _timed_round(sim_f, ctrl_f, budget)
        t_flat_warm, _ = _timed_round(sim_f, ctrl_f, budget)

        tier = {
            "n_nodes": n,
            "budget_w": budget,
            "flat_round_s": {"cold": t_flat_cold, "warm": t_flat_warm},
            "fanouts": [],
        }
        for n_racks in fanouts:
            topo = _topology(system, apps, surfs, n, n_racks, budget)

            sim_h = _sim(system, apps, surfs, n, topology=topo)
            ctrl_h = make_controller("ecoshift_hier", system)
            t_cold, res_h = _timed_round(sim_h, ctrl_h, budget)
            hier_over = _max_overdraw(sim_h)
            t_warm, _ = _timed_round(sim_h, ctrl_h, budget)

            if n_racks == 1:
                # single-root degenerate topology == flat, cap for cap
                assert dict(res_h.allocation.caps) == dict(
                    res_flat.allocation.caps
                ), "single-root hierarchical diverged from flat grouped"

            # what a flat allocator does to the same rack caps
            sim_v = _sim(system, apps, surfs, n, topology=topo)
            sim_v.run_round(make_controller("ecoshift", system), budget=budget)
            flat_over = _max_overdraw(sim_v)

            ratio = t_warm / t_flat_warm
            if n == 10000 and n_racks == 16:
                warm_10k_hier16 = t_warm
            if n >= 10000 and n_racks > 1:
                assert ratio <= MAX_RATIO_VS_FLAT, (
                    f"hier round at n={n}, {n_racks} racks took "
                    f"{ratio:.2f}x the flat round (bar {MAX_RATIO_VS_FLAT}x)"
                )
            entry = {
                "n_racks": n_racks,
                "hier_round_s": {"cold": t_cold, "warm": t_warm},
                "ratio_warm_vs_flat": ratio,
                "hier_avg_improvement": res_h.avg_improvement,
                "flat_avg_improvement": res_flat.avg_improvement,
                "hier_max_overdraw_w": hier_over,
                "flat_max_overdraw_w": flat_over,
            }
            assert hier_over <= 1e-6, "hierarchical path overdrew a domain"
            tier["fanouts"].append(entry)
            lines.append(
                csv_line(
                    f"hier_alloc.n{n}.racks{n_racks}",
                    t_warm * 1e6,
                    f"hier_warm_s={t_warm:.4f};flat_warm_s={t_flat_warm:.4f};"
                    f"ratio={ratio:.2f}x;"
                    f"hier_imp={res_h.avg_improvement * 100:.2f}%;"
                    f"flat_imp={res_flat.avg_improvement * 100:.2f}%;"
                    f"flat_overdraw_w={flat_over:.0f};"
                    f"hier_overdraw_w={hier_over:.0f}",
                )
            )
        if results is not None:
            results.append(tier)

    # deep (>= 4-level) tiers: site -> row -> PDU -> chassis trees with
    # binding caps at every level (ISSUE 8).  The 100k tier is the scale
    # target: its warm round must land within DEEP_MAX_RATIO_VS_10K x the
    # same run's 10k hier-16 warm round.
    deep_tiers = DEEP_TIERS[:1] if fast else DEEP_TIERS
    for n, fanouts_t in deep_tiers:
        budget = _budget(n)
        topo = _deep_topology(system, apps, surfs, n, fanouts_t, budget)

        sim_d = _sim(system, apps, surfs, n, topology=topo)
        ctrl_d = make_controller("ecoshift_hier", system)
        t_cold, res_d = _timed_round(sim_d, ctrl_d, budget)
        over = _max_overdraw(sim_d)
        assert over <= 1e-6, "deep hierarchical path overdrew a domain"
        t_warm, _ = _timed_round(sim_d, ctrl_d, budget)
        assert _max_overdraw(sim_d) <= 1e-6, (
            "deep hierarchical warm round overdrew a domain"
        )

        # fused (device-resident) controller on a fresh identical sim:
        # round 1 falls back (structure build), round 2 compiles, round 3
        # is the steady-state warm round the envelope bar measures.
        sim_u = _sim(system, apps, surfs, n, topology=topo)
        ctrl_u = make_controller("ecoshift_hier", system, fused=True)
        _, res_u = _timed_round(sim_u, ctrl_u, budget)
        assert dict(res_u.allocation.caps) == dict(res_d.allocation.caps), (
            "fused deep cold round diverged from the host controller"
        )
        sim_u.run_round(ctrl_u, budget=budget)
        t_fused_warm, _ = _timed_round(sim_u, ctrl_u, budget)
        assert _max_overdraw(sim_u) <= 1e-6, (
            "fused deep warm round overdrew a domain"
        )

        if n >= 100_000:
            anchor = max(warm_10k_hier16 or 0.0, DEEP_ANCHOR_10K_WARM_S)
            bar = DEEP_MAX_RATIO_VS_10K * anchor
            best = min(t_warm, t_fused_warm)
            assert best <= bar, (
                f"deep {n}-node warm round took {best:.3f}s, above "
                f"{DEEP_MAX_RATIO_VS_10K}x the 10k hier-16 warm anchor "
                f"({anchor:.3f}s -> bar {bar:.3f}s)"
            )

        depth = len(fanouts_t) + 1
        entry = {
            "n_nodes": n,
            "budget_w": budget,
            "fanouts_tree": list(fanouts_t),
            "depth": depth,
            "n_domains": len(topo.domains),
            "hier_round_s": {"cold": t_cold, "warm": t_warm},
            "fused_round_s": {"warm": t_fused_warm},
            "max_overdraw_w": over,
            "avg_improvement": res_d.avg_improvement,
        }
        if results is not None:
            results.append(entry)
        lines.append(
            csv_line(
                f"hier_alloc.deep.n{n}.d{depth}",
                t_warm * 1e6,
                f"warm_s={t_warm:.4f};fused_warm_s={t_fused_warm:.4f};"
                f"cold_s={t_cold:.4f};domains={len(topo.domains)};"
                f"imp={res_d.avg_improvement * 100:.2f}%;"
                f"overdraw_w={over:.0f}",
            )
        )


def smoke_1m(lines: list[str]) -> None:
    """1M-node smoke: build (and coverage-validate) a 4-level million-node
    tree, then run one allocation round on a sampled PDU sub-tree (~10k
    nodes) shifted to the origin — proof the builder and the deep solver
    hold up at the million-node topology scale without paying a full
    million-node simulation."""
    system, apps, surfs = get_suite("system1-a100")
    n = 1_000_000
    t0 = time.perf_counter()
    topo = PowerTopology.uniform_tree(
        n, (10, 10, 10), [1e18, 1e15, 1e15, 1e15]
    )
    t_build = time.perf_counter() - t0
    assert len(topo.domains) == 1 + 10 + 100 + 1000

    # sample one PDU (10 chassis, n/100 nodes); shift node ids to 0
    pdu = topo.domains[0].children[0].children[0]
    off = min(lo for leaf in pdu.children for lo, _hi in leaf.nodes)
    n_sub = sum(hi - lo for leaf in pdu.children for lo, hi in leaf.nodes)

    def shift(dom):
        return PowerDomain(
            name=dom.name,
            cap=dom.cap,
            nodes=tuple((lo - off, hi - off) for lo, hi in dom.nodes),
            children=tuple(shift(c) for c in dom.children),
        )

    sub = PowerTopology(
        PowerDomain(name="site", cap=1e18, children=(shift(pdu),)),
        n_nodes=n_sub,
    )
    budget = _budget(n_sub)
    sim = _sim(system, apps, surfs, n_sub, topology=sub)
    ctrl = make_controller("ecoshift_hier", system)
    t_round, res = _timed_round(sim, ctrl, budget)
    assert _max_overdraw(sim) <= 1e-6, "1M-smoke sub-tree overdrew a domain"
    lines.append(
        csv_line(
            "hier_alloc.smoke1m",
            t_round * 1e6,
            f"build_s={t_build:.4f};round_s={t_round:.4f};"
            f"sampled_nodes={n_sub};"
            f"imp={res.avg_improvement * 100:.2f}%",
        )
    )


#: regression-guard tolerance vs a committed reference (mirrors
#: benchmarks.cluster_scaling; generous for shared-runner noise)
CHECK_FACTOR = 5.0
CHECK_SLACK_S = 0.25


def check_against(reference: dict, results: list) -> list[str]:
    """Warm hierarchical-round regressions vs a committed reference run.

    Compares (n_nodes, n_racks) pairs present in both runs; a fresh warm
    round above ``CHECK_FACTOR x ref + CHECK_SLACK_S`` regresses.
    """
    ref_by_key = {
        (t["n_nodes"], f["n_racks"]): f
        for t in reference.get("tiers", [])
        for f in t.get("fanouts", [])
    }
    ref_deep = {
        (t["n_nodes"], tuple(t["fanouts_tree"])): t
        for t in reference.get("tiers", [])
        if "fanouts_tree" in t
    }
    problems = []
    for tier in results:
        if "fanouts_tree" in tier:
            ref = ref_deep.get((tier["n_nodes"], tuple(tier["fanouts_tree"])))
            if ref is None:
                continue
            for key, fresh, base in (
                ("hier", tier["hier_round_s"]["warm"],
                 ref["hier_round_s"]["warm"]),
                ("fused", tier["fused_round_s"]["warm"],
                 ref["fused_round_s"]["warm"]),
            ):
                budget = CHECK_FACTOR * base + CHECK_SLACK_S
                if fresh > budget:
                    problems.append(
                        f"deep n={tier['n_nodes']}, "
                        f"fanouts={tier['fanouts_tree']}: warm {key} round "
                        f"{fresh:.3f}s exceeds {budget:.3f}s "
                        f"({CHECK_FACTOR}x ref {base:.3f}s + {CHECK_SLACK_S}s)"
                    )
            continue
        for f in tier["fanouts"]:
            ref = ref_by_key.get((tier["n_nodes"], f["n_racks"]))
            if ref is None:
                continue
            fresh = f["hier_round_s"]["warm"]
            budget = CHECK_FACTOR * ref["hier_round_s"]["warm"] + CHECK_SLACK_S
            if fresh > budget:
                problems.append(
                    f"n={tier['n_nodes']}, racks={f['n_racks']}: warm hier "
                    f"round {fresh:.3f}s exceeds {budget:.3f}s "
                    f"({CHECK_FACTOR}x ref {ref['hier_round_s']['warm']:.3f}s "
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
        "--out", default="BENCH_hier_alloc.json", help="JSON output path"
    )
    ap.add_argument(
        "--check",
        default=None,
        metavar="REF_JSON",
        help="compare fresh warm hier-round times against a committed "
        "reference (loaded before --out overwrites it); exit 1 on regression",
    )
    ap.add_argument(
        "--smoke-1m",
        action="store_true",
        help="run only the 1M-node topology smoke (build + sampled "
        "sub-tree round); no JSON is written",
    )
    args = ap.parse_args()

    if args.smoke_1m:
        smoke_lines = ["name,us_per_call,derived"]
        smoke_1m(smoke_lines)
        print("\n".join(smoke_lines))
        return

    reference = None
    if args.check:
        with open(args.check) as f:
            reference = json.load(f)

    lines: list[str] = ["name,us_per_call,derived"]
    results: list = []
    t0 = time.time()
    run(lines, fast=args.fast, results=results)
    payload = {
        "benchmark": "hier_alloc",
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
