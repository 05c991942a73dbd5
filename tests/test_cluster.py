"""Cluster control-loop tests: scenario engine, controllers, batched DP.

Certifies the refactor's contracts:
 * vectorized measurement is bit-for-bit equal to the legacy per-node loop
   on identical RNG streams (and >= 5x faster at 100 nodes);
 * round 0 of every migrated policy equals the single-round emulator path;
 * multi-round regression: failure -> pool return -> warm re-optimization.
"""

import time

import numpy as np
import pytest

from repro.cluster import ClusterSim, Scenario
from repro.cluster.controller import make_controller
from repro.core import policies, surfaces, types
from repro.core.emulator import ClusterEmulator


@pytest.fixture(scope="module")
def suite():
    system = types.SYSTEM_1
    apps, surfs = surfaces.build_paper_suite(system)
    return system, apps, surfs


def _sim(suite, n_nodes=40, seed=0):
    system, apps, surfs = suite
    return ClusterSim.build(system, apps, surfs, n_nodes=n_nodes, seed=seed)


# ---------------------------------------------------------------------------
# Vectorized measurement == legacy loop
# ---------------------------------------------------------------------------


class TestMeasurementEquivalence:
    @pytest.mark.parametrize("policy", ["dps", "ecoshift", "mixed_adaptive"])
    def test_bitwise_equal_on_same_rng_stream(self, suite, policy):
        import dataclasses

        sim = _sim(suite)
        sim.nodes = [  # include a straggler in the measured set
            n if n.node_id != 3 else dataclasses.replace(n, slowdown=2.0)
            for n in sim.nodes
        ]
        controller = make_controller(policy, suite[0])
        _, recv, _ = sim.partition()
        baselines = {n.app.name: n.caps for n in recv}
        seen = {n.app.name: sim._surface(n) for n in recv}
        alloc = controller.allocate([n.app for n in recv], baselines, 1500.0, seen)
        vec = sim.measure_improvements(recv, alloc, sim.round_rng(policy, 0))
        loop = sim.measure_improvements_loop(recv, alloc, sim.round_rng(policy, 0))
        assert vec == loop  # bit-for-bit, not allclose

    def test_zero_noise_path(self, suite):
        system, apps, surfs = suite
        quiet = types.SystemSpec(
            name=system.name, grid=system.grid, init_cpu=system.init_cpu,
            init_gpu=system.init_gpu, noise_sigma=0.0,
        )
        sim = ClusterSim.build(quiet, apps, surfs, n_nodes=20, seed=1)
        controller = make_controller("dps", quiet)
        res1 = sim.run_round(controller, budget=800.0)
        res2 = sim.run_round(controller, budget=800.0)
        assert res1.improvements == res2.improvements

    def test_speedup_at_100_nodes(self, suite):
        sim = _sim(suite, n_nodes=100, seed=0)
        controller = make_controller("dps", suite[0])
        _, recv, _ = sim.partition()
        baselines = {n.app.name: n.caps for n in recv}
        seen = {n.app.name: sim._surface(n) for n in recv}
        alloc = controller.allocate([n.app for n in recv], baselines, 2000.0, seen)

        def best_of(fn, trials=3):
            ts = []
            for _ in range(trials):
                rng = sim.round_rng("dps", 0)
                t0 = time.perf_counter()
                fn(recv, alloc, rng)
                ts.append(time.perf_counter() - t0)
            return min(ts)

        t_loop = best_of(sim.measure_improvements_loop)
        t_vec = best_of(sim.measure_improvements)
        assert t_loop / t_vec >= 5.0, f"only {t_loop / t_vec:.1f}x"


# ---------------------------------------------------------------------------
# Round 0 of the engine == the single-round emulator
# ---------------------------------------------------------------------------


class TestRoundZeroParity:
    @pytest.mark.parametrize(
        "policy", ["uniform", "dps", "mixed_adaptive", "ecoshift", "oracle"]
    )
    def test_scenario_round0_matches_run_round(self, suite, policy):
        system, apps, surfs = suite
        emu = ClusterEmulator.build(system, apps, surfs, n_nodes=25, seed=7)
        want = emu.run_round(policy, budget=1200.0)

        sim = ClusterSim.build(system, apps, surfs, n_nodes=25, seed=7)
        trace = sim.run(Scenario.constant(1, budget=1200.0), policy)
        got = trace.records[0].result
        assert got.improvements == want.improvements
        assert dict(got.allocation.caps) == dict(want.allocation.caps)
        assert got.budget == want.budget

    @pytest.mark.parametrize(
        "grouped", [False, True], ids=["sparse", "sparse_grouped"]
    )
    def test_warm_controller_matches_pure_policy(self, suite, grouped):
        """Budget-independent cached option tables solve identically to the
        per-call tables the pure policy function builds, ungrouped or
        group-collapsed."""
        system, apps, surfs = suite
        sim = _sim(suite, n_nodes=20, seed=4)
        _, recv, _ = sim.partition()
        baselines = {n.app.name: n.caps for n in recv}
        seen = {n.app.name: sim._surface(n) for n in recv}
        ctrl = make_controller("ecoshift", system)
        for budget in (400.0, 1100.0, 2500.0):  # warm after first call
            got = ctrl.allocate([n.app for n in recv], baselines, budget, seen)
            want = policies.ecoshift(
                [n.app for n in recv], baselines, budget, system, seen,
                grouped=grouped,
            )
            assert dict(got.caps) == dict(want.caps)
            assert got.spent == want.spent
        assert ctrl.cached_tables == len(recv)


# ---------------------------------------------------------------------------
# Multi-round scenarios
# ---------------------------------------------------------------------------


class TestScenarios:
    def test_failure_returns_pool_and_reoptimizes(self, suite):
        system, apps, surfs = suite
        sim = _sim(suite, n_nodes=20, seed=2)
        victim = sim.alive_nodes()[0].node_id
        scen = Scenario(n_rounds=3).with_failure(1, victim)  # donor-derived pool
        trace = sim.run(scen, "ecoshift")
        assert trace.n_rounds == 3
        pre, post = trace.records[0], trace.records[1]
        assert post.n_alive == pre.n_alive - 1
        # the dead node's whole cap allotment joins the pool
        assert post.result.budget > pre.result.budget
        # survivors get more watts -> re-optimized improvement not worse
        assert post.result.avg_improvement >= pre.result.avg_improvement - 0.01
        # the victim is no longer a receiver
        assert np.isnan(trace.improvements_of(f_victim_name(sim, victim))[1])

    def test_straggler_invalidates_warm_state(self, suite):
        system, _, _ = suite
        sim = _sim(suite, n_nodes=15, seed=5)
        victim = [n for n in sim.alive_nodes() if n.app.sclass in "CG"][0]
        ctrl = make_controller("ecoshift", system)
        scen = Scenario.constant(2, budget=1000.0).with_straggler(
            1, victim.node_id, 2.0
        )
        trace = sim.run(scen, ctrl)
        # slowdown scales the true surface but not relative improvements of a
        # multiplicatively-slowed app; both rounds must still measure sanely
        v = trace.improvements_of(victim.app.name)
        assert np.isfinite(v).all()
        node = [n for n in sim.nodes if n.node_id == victim.node_id][0]
        assert node.slowdown == 2.0

    def test_arrival_and_phase_change(self, suite):
        system, apps, surfs = suite
        sim = _sim(suite, n_nodes=10, seed=6)
        newcomer = apps[0]
        other = apps[1].name
        target = sim.alive_nodes()[0].node_id
        scen = (
            Scenario.constant(2, budget=900.0)
            .with_arrival(1, newcomer)
            .with_phase_change(1, target, other)
        )
        trace = sim.run(scen, "dps")
        assert trace.records[1].n_alive == 11
        changed = [n for n in sim.nodes if n.node_id == target][0]
        assert changed.base_app == other

    def test_budget_traces(self):
        scen = Scenario(n_rounds=4, budget=(100.0, 200.0))
        assert scen.budget_at(0) == 100.0
        assert scen.budget_at(3) == 200.0  # short trace holds last value
        scen = Scenario(n_rounds=4, budget=lambda r: 50.0 * (r + 1))
        assert scen.budget_at(2) == 150.0
        scen = Scenario.price_capped(
            2, pool_watts=500.0, prices=(0.1, 0.5), spend_cap=100.0
        )
        assert scen.budget_at(0) == 500.0  # cheap power: full pool
        assert scen.budget_at(1) == 200.0  # expensive power: cap / price
        assert scen.price_at(1) == 0.5

    def test_event_round_validation(self):
        with pytest.raises(ValueError):
            Scenario.constant(2).with_failure(5, 0)


def f_victim_name(sim, node_id):
    return [n for n in sim.nodes if n.node_id == node_id][0].app.name


# ---------------------------------------------------------------------------
# Acceptance scenario: >=5 rounds, >=50 nodes, one failure + one straggler
# ---------------------------------------------------------------------------


class TestAcceptanceScenario:
    @pytest.mark.parametrize("policy", ["ecoshift", "dps"])
    def test_seeded_multi_round(self, suite, policy):
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=50, seed=0)
        victim_f = sim.alive_nodes()[0].node_id
        victim_s = [n for n in sim.alive_nodes() if n.app.sclass in "CG"][0]
        scen = (
            Scenario.constant(5, budget=2000.0)
            .with_failure(2, victim_f)
            .with_straggler(3, victim_s.node_id, 1.8)
        )
        trace = sim.run(scen, policy)
        assert trace.n_rounds == 5
        assert trace.records[2].n_alive == 49
        assert np.isfinite(trace.improvement_trace).all()
        assert (trace.improvement_trace > 0).all()
        # replay with a fresh sim: fully deterministic
        sim2 = ClusterSim.build(system, apps, surfs, n_nodes=50, seed=0)
        trace2 = sim2.run(scen, policy)
        for a, b in zip(trace.records, trace2.records):
            assert a.result.improvements == b.result.improvements
