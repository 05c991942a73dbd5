"""CI smoke: a seeded multi-round scenario through the cluster engine.

5 rounds, 50 nodes, one failure and one straggler, for both the
``ecoshift`` and ``dps`` controllers, on CPU.  Also reports the vectorized-vs-loop measurement
speedup at 100 nodes, runs the **1k-node scaling tier** (group-collapsed
columnar engine: a 6-round scenario with failure/straggler/arrival under
its own wall-clock guard, plus a grouped-vs-legacy allocation parity spot
check), the **4-rack hierarchical tier** (1k nodes under binding rack/PDU
caps with a mid-run ``DomainCapChange`` derating; every round must respect
every domain cap), the **low-churn incremental tier** (1k nodes through a
sparse event trickle: the delta-driven incremental controller must match
the from-scratch controller bit-for-bit every round and beat it decisively
on steady-state rounds, DESIGN.md §13), the **receding-horizon MPC tier**
(a CO2-day scenario: per-round budget compliance, strictly better
perf-per-CO2 than myopic, and horizon=1 bit-for-bit parity,
DESIGN.md §15), the **fused-churn tier** (1k nodes under a 4-rack
topology through the device-resident fused controller while mixed
structure-changing events land: bit-for-bit parity with the host
incremental controller every round and zero post-warmup fallbacks —
structure churn must be absorbed by capacity-slack row patches and
device-side bank compaction, DESIGN.md §17), and exercises the
online-prediction path: a cold-start arrival (no pretrained surface)
converging under the ``ecoshift_online`` controller within a handful of
telemetry rounds.  The **fault-storm tier** (DESIGN.md §18) drives a
racked cluster through a heavy seeded storm (telemetry drops/corruption,
actuation NACK/partial/delay, a mid-run controller crash+restore) and
asserts the chaos invariants: settled draw under every domain cap and
the budget each round, and the crash-restored run finishing without
divergence from its own scheduled rounds.  Exits nonzero on any
regression; hard wall-clock budget < 90 s.

    PYTHONPATH=src python tools/smoke_scenario.py
"""

from __future__ import annotations

import time

import numpy as np

from repro.cluster import (
    ClusterSim,
    OnlinePredictor,
    OnlinePredictorConfig,
    PowerTopology,
    Scenario,
)
from repro.cluster import scenario as types_scenario
from repro.cluster.controller import make_controller
from repro.core import ncf, surfaces, types
from repro.core.allocator import EcoShiftAllocator

#: hard wall-clock budget for the whole smoke (shared CI runners)
BUDGET_S = 90.0

#: wall-clock guard for the 1k-node scaling tier alone
SCALING_BUDGET_S = 15.0

#: wall-clock guard for the 4-rack hierarchical tier alone
HIER_BUDGET_S = 15.0

#: wall-clock guard for the low-churn incremental tier alone
INCR_BUDGET_S = 15.0

#: wall-clock guard for the receding-horizon (MPC) tier alone
MPC_BUDGET_S = 15.0

#: wall-clock guard for the fused-churn tier alone (first rounds pay the
#: jitted-pipeline compiles; steady churn rounds are milliseconds)
FUSED_CHURN_BUDGET_S = 30.0

#: wall-clock guard for the fault-storm tier alone
FAULT_BUDGET_S = 15.0


def scaling_smoke(system, apps, surfs) -> None:
    """1k-node tier through the group-collapsed columnar engine."""
    n = 1000
    t0 = time.perf_counter()
    sim = ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=0, initial_caps=(150.0, 150.0)
    )
    scen = (
        Scenario.constant(6, budget=2000.0)
        .with_failure(1, *range(10))
        .with_straggler(2, 500, 1.7)
        .with_arrival(3, apps[0])
    )
    trace = sim.run(scen, make_controller("ecoshift", system))
    elapsed = time.perf_counter() - t0
    imp = trace.improvement_trace
    assert trace.n_rounds == 6
    assert trace.records[1].n_alive == n - 10, "failures not applied"
    assert trace.records[3].n_alive == n - 9, "arrival not applied"
    assert np.isfinite(imp).all() and (imp > 0).all(), imp
    assert elapsed < SCALING_BUDGET_S, (
        f"1k-node scaling tier took {elapsed:.1f} s "
        f"(guard {SCALING_BUDGET_S} s)"
    )
    print(
        f"scaling   {n} nodes x {trace.n_rounds} rounds in {elapsed:.1f} s "
        f"({trace.n_rounds / elapsed:.1f} rounds/s), "
        f"avg_improvement={imp.mean() * 100:.1f}%"
    )

    # grouped-vs-legacy allocation parity spot check (200 nodes)
    sim_g = ClusterSim.build(
        system, apps, surfs, n_nodes=200, seed=1, initial_caps=(150.0, 150.0)
    )
    res_g = sim_g.run_round(make_controller("ecoshift", system), budget=1500.0)
    sim_l = ClusterSim.build(
        system, apps, surfs, n_nodes=200, seed=1, initial_caps=(150.0, 150.0)
    )
    res_l = sim_l.run_round(
        make_controller("ecoshift", system, grouped=False), budget=1500.0
    )
    assert dict(res_g.allocation.caps) == dict(res_l.allocation.caps), (
        "grouped allocation diverged from the per-instance path"
    )
    assert res_g.improvements == res_l.improvements
    print("scaling   grouped == legacy per-instance at 200 nodes (bit-for-bit)")


def hier_smoke(system, apps, surfs) -> None:
    """4-rack 1k-node tier through the hierarchical allocator, with a
    mid-run rack-PDU derating (DomainCapChange) that must visibly bind."""
    n, n_racks = 1000, 4
    t0 = time.perf_counter()
    # probe committed draw, then set binding rack caps (+150 W headroom)
    probe = ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=0, initial_caps=(150.0, 150.0),
        topology=PowerTopology.uniform_racks(n, n_racks, rack_cap=1e15),
    )
    _, committed, _ = probe.domain_headroom(0)
    rack_cap = float(committed[1:].max()) + 150.0
    derated = float(committed[1:].max()) + 50.0
    topo = PowerTopology.uniform_racks(n, n_racks, rack_cap=rack_cap)
    scen = (
        Scenario.constant(6, budget=2000.0)
        .with_topology(topo)
        .with_failure(1, *range(10))
        .with_straggler(2, 500, 1.7)
        .with_domain_cap(3, "rack2", derated)
    )
    sim = ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=0,
        initial_caps=(150.0, 150.0), topology=topo,
    )
    trace = sim.run(scen, make_controller("ecoshift_hier", system))
    elapsed = time.perf_counter() - t0
    imp = trace.improvement_trace
    assert trace.n_rounds == 6
    assert np.isfinite(imp).all() and (imp > 0).all(), imp
    for rec in trace.records:
        for name, draw in rec.domain_draw.items():
            assert draw <= rec.domain_caps[name] + 1e-6, (
                f"round {rec.round}: {name} over cap"
            )
    assert trace.records[3].domain_caps["rack2"] == derated, "derate missing"
    assert elapsed < HIER_BUDGET_S, (
        f"hier tier took {elapsed:.1f} s (guard {HIER_BUDGET_S} s)"
    )
    print(
        f"hier      {n} nodes x {n_racks} racks x {trace.n_rounds} rounds "
        f"in {elapsed:.1f} s, caps respected every round "
        f"(rack2 derated to {derated:.0f} W at round 3), "
        f"avg_improvement={imp.mean() * 100:.1f}%"
    )


def incremental_smoke(system, apps, surfs) -> None:
    """Low-churn 1k-node steady-state tier (DESIGN.md §13): the delta-driven
    incremental controller must (a) allocate bit-for-bit like the
    from-scratch controller through a sparse event trickle, and (b) be
    decisively faster on the event-free steady-state rounds."""
    n = 1000
    t0 = time.perf_counter()
    times = {True: [], False: []}
    pair = []
    for inc in (True, False):
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=0,
            initial_caps=(150.0, 150.0),
        )
        ctrl = make_controller("ecoshift", system, incremental=inc)
        pair.append((sim, ctrl))
    scen_events = {
        2: [types_scenario.StragglerOnset(round=2, node_id=500, slowdown=1.7)],
        4: [types_scenario.PhaseChange(
            round=4, node_id=123, surface_id=apps[1].name)],
        6: [types_scenario.NodeFailure(round=6, node_ids=(7, 8))],
    }
    for r in range(8):
        allocs = []
        for sim, ctrl in pair:
            ev = scen_events.get(r, [])
            if ev:
                touched = sim.apply_events(ev)
                ctrl.invalidate(touched)
            t1 = time.perf_counter()
            res = sim.run_round(ctrl, budget=2000.0, round_index=r)
            times[ctrl.incremental].append(time.perf_counter() - t1)
            allocs.append(res)
        a, b = allocs
        assert dict(a.allocation.caps) == dict(b.allocation.caps), (
            f"incremental != from-scratch at round {r}"
        )
        assert a.allocation.spent == b.allocation.spent
    # steady-state rounds (no events, warm): 1, 3, 5, 7
    steady_inc = float(np.median([times[True][r] for r in (1, 3, 5, 7)]))
    steady_scr = float(np.median([times[False][r] for r in (1, 3, 5, 7)]))
    elapsed = time.perf_counter() - t0
    assert elapsed < INCR_BUDGET_S, (
        f"incremental tier took {elapsed:.1f} s (guard {INCR_BUDGET_S} s)"
    )
    # generous floor for shared runners; the >=5x acceptance runs in
    # benchmarks.incremental_alloc at the 10k tier
    assert steady_scr / steady_inc >= 1.5, (
        f"incremental steady-state round only "
        f"{steady_scr / steady_inc:.1f}x faster than from-scratch"
    )
    print(
        f"increment {n} nodes x 8 rounds in {elapsed:.1f} s, parity OK, "
        f"steady-state {steady_inc * 1e3:.1f} ms vs from-scratch "
        f"{steady_scr * 1e3:.1f} ms ({steady_scr / steady_inc:.1f}x)"
    )


def mpc_smoke(system, apps, surfs) -> None:
    """Receding-horizon tier (DESIGN.md §15): a CO2-day scenario through
    the MPC controller must (a) never exceed any round's instantaneous
    budget, (b) emit strictly less carbon than myopic at strictly better
    perf-per-CO2, and (c) be bit-for-bit myopic when horizon=1."""
    from repro.cluster import budget as bm

    n, n_rounds = 100, 24
    t0 = time.perf_counter()
    scen = Scenario.carbon_aware(n_rounds, bm.ConstantProvider(2.0 * n))
    runs = {}
    for name, kw in (
        ("myopic", {}),
        ("h1", {"horizon": 1, "eco_factor": 0.7}),
        ("mpc", {"horizon": 8, "eco_factor": 0.7}),
    ):
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=0,
            initial_caps=(150.0, 150.0),
        )
        runs[name] = sim.run(scen, make_controller("ecoshift", system, **kw))
    for ra, rb in zip(runs["myopic"].records, runs["h1"].records):
        assert ra.result.allocation.caps == rb.result.allocation.caps, (
            "horizon=1 diverged from the plain controller"
        )
    def score(res):
        value = sum(r.avg_improvement for r in res.records)
        grams = 0.0
        for rec in res.records:
            spent = rec.result.allocation.spent
            assert spent <= rec.result.budget + 1e-6, (
                f"round {rec.round}: spent {spent:.1f} W over budget "
                f"{rec.result.budget:.1f} W"
            )
            grams += rec.carbon_intensity * spent
        return value, grams
    v0, g0 = score(runs["myopic"])
    v1, g1 = score(runs["mpc"])
    assert g1 < g0, f"MPC emitted no less carbon ({g1:.0f} vs {g0:.0f})"
    assert v1 / g1 > v0 / g0, (
        f"MPC perf-per-CO2 {v1 / g1:.3g} not better than myopic {v0 / g0:.3g}"
    )
    elapsed = time.perf_counter() - t0
    assert elapsed < MPC_BUDGET_S, (
        f"MPC tier took {elapsed:.1f} s (guard {MPC_BUDGET_S} s)"
    )
    print(
        f"mpc       {n} nodes x {n_rounds} rounds in {elapsed:.1f} s, "
        f"h1==myopic bit-for-bit, CO2 {g0 / 1e3:.0f}->{g1 / 1e3:.0f} kg-ish "
        f"units, perf-per-CO2 {v0 / g0 * 1e6:.3f}->{v1 / g1 * 1e6:.3f}"
    )


def fused_churn_smoke(system, apps, surfs) -> None:
    """Fused-under-churn tier (DESIGN.md §17): 1k nodes, 4 racks, mixed
    structure-changing events (straggler / phase change / failure /
    arrival) through the device-resident fused controller.  Every round
    must match the host incremental controller bit-for-bit, and after the
    cold-start warmup there must be zero host fallbacks — structure churn
    is served fused by capacity-slack row patches and device compaction,
    never by the retired ``structure_change`` fallback."""
    n, n_racks = 1000, 4
    t0 = time.perf_counter()
    topo = PowerTopology.uniform_racks(n, n_racks, rack_cap=70000.0)
    pair = []
    for kw in ({"fused": True}, {}):
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=0,
            initial_caps=(150.0, 150.0), topology=topo,
        )
        ctrl = make_controller("ecoshift_hier", system, **kw)
        pair.append((sim, ctrl))
    fused_ctrl = pair[0][1]
    scen_events = {
        2: [types_scenario.StragglerOnset(round=2, node_id=500, slowdown=1.7)],
        3: [types_scenario.PhaseChange(
            round=3, node_id=123, surface_id=apps[1].name)],
        4: [types_scenario.NodeFailure(round=4, node_ids=(7, 8, 9))],
        5: [types_scenario.NodeArrival(
            round=5, app=apps[0], domain="rack1", caps=(150.0, 150.0))],
        6: [
            types_scenario.NodeFailure(round=6, node_ids=(42,)),
            types_scenario.PhaseChange(
                round=6, node_id=321, surface_id=apps[2].name),
        ],
    }
    warmup_fallbacks = 0
    for r in range(8):
        allocs = []
        for sim, ctrl in pair:
            ev = scen_events.get(r, [])
            if ev:
                touched = sim.apply_events(ev)
                ctrl.invalidate(touched)
            res = sim.run_round(
                ctrl, budget=2000.0 - 25.0 * r, round_index=r
            )
            allocs.append(res)
        a, b = allocs
        assert dict(a.allocation.caps) == dict(b.allocation.caps), (
            f"fused != host at round {r}"
        )
        assert a.allocation.spent == b.allocation.spent
        if r == 1:
            warmup_fallbacks = fused_ctrl.fused_stats().fallbacks
    stats = fused_ctrl.fused_stats()
    assert stats.fallbacks - warmup_fallbacks == 0, (
        f"structure-changing rounds fell back to host: "
        f"{stats.fallbacks - warmup_fallbacks} post-warmup fallbacks "
        f"(last reason: {stats.fallback_reason!r})"
    )
    assert stats.rebuilds == 1, (
        f"resident banks were host-rebuilt {stats.rebuilds} times "
        f"(only the cold start may rebuild)"
    )
    elapsed = time.perf_counter() - t0
    assert elapsed < FUSED_CHURN_BUDGET_S, (
        f"fused-churn tier took {elapsed:.1f} s "
        f"(guard {FUSED_CHURN_BUDGET_S} s)"
    )
    print(
        f"fusedchurn {n} nodes x {n_racks} racks x 8 rounds in "
        f"{elapsed:.1f} s, parity OK, 0 post-warmup fallbacks, "
        f"rebuilds={stats.rebuilds} compactions={stats.compactions} "
        f"row_uploads={stats.row_uploads} "
        f"slack={stats.slack_utilization:.2f}"
    )


def fault_storm_smoke(system, apps, surfs) -> None:
    """Chaos tier (DESIGN.md §18): a racked cluster under a heavy seeded
    fault storm with a mid-run crash+restore.  PowerGuard must keep the
    settled draw under every domain cap and the round budget, a restored
    clean run must be bit-for-bit, and value must survive the storm."""
    n, n_racks, n_rounds = 200, 4, 10
    t0 = time.perf_counter()
    probe = ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=0, initial_caps=(150.0, 150.0),
        topology=PowerTopology.uniform_racks(n, n_racks, rack_cap=1e15),
    )
    _, committed, _ = probe.domain_headroom(0)
    topo = PowerTopology.uniform_racks(
        n, n_racks, rack_cap=float(committed[1:].max()) + 400.0
    )
    budgets = [
        1600.0, 800.0, 1400.0, 600.0, 1600.0,
        1000.0, 1500.0, 700.0, 1600.0, 900.0,
    ]
    scen = (
        Scenario(n_rounds, budget=budgets)
        .with_topology(topo)
        .with_fault_storm(
            seed=13, telemetry_drop=0.15, telemetry_corrupt=0.35,
            telemetry_stale=0.15, actuation_nack=0.4,
            actuation_partial=0.25, actuation_delay=0.25,
            node_fraction=0.3, crash_rounds=(n_rounds // 2,),
        )
    )
    sim = ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=0,
        initial_caps=(150.0, 150.0), topology=topo,
    )
    trace = sim.run(scen, make_controller("ecoshift_hier", system))
    assert trace.n_rounds == n_rounds
    n_nack_rounds = sum(bool(r.nacked) for r in trace.records)
    assert n_nack_rounds > 0, "storm produced no visible actuation faults"
    for rec in trace.records:
        extra = sum(
            float(np.sum(t.allocated_caps) - np.sum(t.baseline_caps))
            for t in rec.telemetry
        )
        assert extra <= rec.result.budget + 1e-6, (
            f"round {rec.round}: settled draw {extra:.1f} W over budget "
            f"{rec.result.budget:.1f} W"
        )
        for name, draw in rec.domain_draw.items():
            assert draw <= rec.domain_caps[name] + 1e-6, (
                f"round {rec.round}: {name} over cap after settlement"
            )
    # crash+restore on a clean channel replays the uninterrupted run
    clean = Scenario(n_rounds, budget=budgets).with_topology(topo)
    ref_sim = ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=0,
        initial_caps=(150.0, 150.0), topology=topo,
    )
    ref = ref_sim.run(clean, make_controller("ecoshift_hier", system))
    crash_sim = ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=0,
        initial_caps=(150.0, 150.0), topology=topo,
    )
    from repro.cluster import ControllerCrash

    crashed = crash_sim.run(
        clean.with_faults([ControllerCrash(round=n_rounds // 2)]),
        make_controller("ecoshift_hier", system),
    )
    for a, b in zip(ref.records, crashed.records):
        assert dict(a.result.allocation.caps) == dict(
            b.result.allocation.caps
        ), f"crash-restored run diverged at round {a.round}"
    elapsed = time.perf_counter() - t0
    assert elapsed < FAULT_BUDGET_S, (
        f"fault-storm tier took {elapsed:.1f} s (guard {FAULT_BUDGET_S} s)"
    )
    worst = max(r.overdraw_w for r in trace.records)
    print(
        f"faults    {n} nodes x {n_racks} racks x {n_rounds} rounds in "
        f"{elapsed:.1f} s, {n_nack_rounds} NACK rounds, worst pre-derate "
        f"excursion {worst:.0f} W (settled draw under every cap), "
        f"crash+restore bit-for-bit"
    )


def online_prediction_smoke(system, apps, surfs) -> None:
    """Cold-start arrival through the telemetry-driven prediction loop."""
    train = [a for a in apps if a.sclass in "CGB"][:8]
    cold = [
        a
        for a in apps
        if a.sclass == "B" and all(a.name != t.name for t in train)
    ][0]
    cfg = ncf.NCFConfig(train_steps=250, online_steps=150, embed_dim=8)
    alloc = EcoShiftAllocator.train_offline(
        system, {a.name: surfs[a.name] for a in train}, cfg
    )
    for a in train:
        alloc.onboard_known(a.name)

    pred = OnlinePredictor(alloc.predictor, OnlinePredictorConfig())
    pred.seed_surfaces(alloc.predicted)
    ctrl = make_controller("ecoshift_online", system, predictor=pred)

    n_nodes, n_rounds = 14, 6
    sim = ClusterSim.build(system, train, surfs, n_nodes=n_nodes, seed=0)
    budgets = tuple(600.0 + 300.0 * ((3 * r) % 4) for r in range(n_rounds))
    scen = Scenario(n_rounds=n_rounds, budget=budgets).with_arrival(1, cold)
    trace = sim.run(scen, ctrl)

    inst = f"{cold.name}#n{n_nodes}"
    imp = trace.improvements_of(inst)
    assert np.isfinite(imp[1:]).all(), imp
    assert not pred.is_cold(cold.name), "arrival never left cold start"
    assert pred.n_refits > 0, "telemetry never triggered an online fit"
    err = pred.prediction_error.get(cold.name, np.inf)
    assert err < 0.05, f"online surface still mispredicts: err={err:.3f}"
    print(
        f"online    cold-start {cold.name}: refits={pred.n_refits} "
        f"pred_err={err:.4f} "
        f"improvements={[f'{x * 100:.1f}%' for x in imp[1:]]}"
    )


def main() -> None:
    t_start = time.perf_counter()
    system = types.SYSTEM_1
    apps, surfs = surfaces.build_paper_suite(system)

    probe = ClusterSim.build(system, apps, surfs, n_nodes=50, seed=0)
    victim_f = probe.alive_nodes()[0].node_id
    victim_s = [n for n in probe.alive_nodes() if n.app.sclass in "CG"][0]
    scen = (
        Scenario.constant(5, budget=2000.0)
        .with_failure(2, victim_f)
        .with_straggler(3, victim_s.node_id, 1.8)
    )

    for policy in ("ecoshift", "dps"):
        sim = ClusterSim.build(system, apps, surfs, n_nodes=50, seed=0)
        trace = sim.run(scen, policy)
        imp = trace.improvement_trace
        assert trace.n_rounds == 5
        assert trace.records[2].n_alive == 49, "failure not applied"
        assert np.isfinite(imp).all() and (imp > 0).all(), imp
        print(
            f"{policy:9s} rounds={trace.n_rounds} "
            f"avg_improvement={[f'{x*100:.1f}%' for x in imp]}"
        )

    # vectorized measurement speedup at 100 nodes
    sim = ClusterSim.build(system, apps, surfs, n_nodes=100, seed=0)
    ctrl = make_controller("dps", system)
    _, recv, _ = sim.partition()
    baselines = {n.app.name: n.caps for n in recv}
    seen = {n.app.name: sim._surface(n) for n in recv}
    alloc = ctrl.allocate([n.app for n in recv], baselines, 2000.0, seen)

    def best(fn, k=3):
        ts = []
        for _ in range(k):
            rng = sim.round_rng("dps", 0)
            t0 = time.perf_counter()
            fn(recv, alloc, rng)
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t_loop = best(sim.measure_improvements_loop)
    t_vec = best(sim.measure_improvements)
    speedup = t_loop / t_vec
    print(
        f"measurement at {len(recv)} receivers: loop {t_loop*1e3:.2f} ms, "
        f"vectorized {t_vec*1e3:.2f} ms ({speedup:.1f}x)"
    )
    # generous floor: shared CI runners are noisy; the >=5x acceptance
    # check runs in tests/test_cluster.py
    assert speedup >= 2.0, f"vectorized speedup regressed to {speedup:.1f}x"

    scaling_smoke(system, apps, surfs)

    hier_smoke(system, apps, surfs)

    incremental_smoke(system, apps, surfs)

    mpc_smoke(system, apps, surfs)

    fused_churn_smoke(system, apps, surfs)

    fault_storm_smoke(system, apps, surfs)

    online_prediction_smoke(system, apps, surfs)

    elapsed = time.perf_counter() - t_start
    assert elapsed < BUDGET_S, f"smoke took {elapsed:.1f} s (budget {BUDGET_S} s)"
    print(f"smoke scenario OK in {elapsed:.1f} s")


if __name__ == "__main__":
    main()
