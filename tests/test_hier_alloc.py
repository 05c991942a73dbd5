"""Hierarchical allocation parity + conservation suite (DESIGN.md §12).

The load-bearing contracts of the topology-aware two-level solver:

 * **single-root parity**: a topology degenerating to one domain whose cap
   covers the cluster budget is *bit-for-bit* the flat grouped solve
   ``solve_sparse_grouped`` — picks, total_value and spent — on the host
   DP and on the device pipeline (``solve_hierarchical_fused``);
 * **cap feasibility**: randomized multi-domain instances never spend above
   any domain cap, and match an exhaustive cap-constrained brute force on
   small cases;
 * **engine level**: topology sims never violate a domain cap in any round
   (the sim-side conservation check), through failures, stragglers and
   mid-scenario ``DomainCapChange`` deratings.
"""

import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # image without hypothesis: property tests skip
    from _hypothesis_stub import hypothesis, st

from repro.cluster import ClusterSim, PowerTopology, Scenario
from repro.cluster.controller import make_controller
from repro.core import curves, mckp, policies, surfaces, types


@pytest.fixture(scope="module")
def suite():
    system = types.SYSTEM_1
    apps, surfs = surfaces.build_paper_suite(system)
    return system, apps, surfs


def _random_groups(rng, budget, n_groups=None, prefix="x"):
    """Random behaviour classes (same generator family as the grouped
    parity suite, with a name prefix so domains never collide)."""
    n_groups = n_groups or int(rng.integers(1, 5))
    sizes = [int(rng.integers(1, 6)) for _ in range(n_groups)]
    slots = []
    for g, m in enumerate(sizes):
        slots += [g] * m
    rng.shuffle(slots)
    members = {g: [] for g in range(n_groups)}
    for i, g in enumerate(slots):
        members[g].append(f"{prefix}{i:03d}")
    groups = []
    for g in range(n_groups):
        k = int(rng.integers(1, 6))
        costs = np.unique(
            rng.integers(1, max(2, int(budget / 25)), size=k)
        ).astype(float) * 25.0
        values = np.sort(rng.uniform(0.01, 0.5, size=len(costs)))
        caps = np.stack(
            [100.0 + costs, np.full_like(costs, 100.0)], axis=-1
        )
        table = curves.OptionTable(
            name=f"class{g}",
            costs=np.concatenate([[0.0], costs]),
            values=np.concatenate([[0.0], values]),
            caps=np.concatenate([[[100.0, 100.0]], caps], axis=0),
        )
        groups.append(
            mckp.GroupedOptions(table=table, members=tuple(sorted(members[g])))
        )
    return groups


def _assert_bitwise_equal(a: mckp.MCKPSolution, b: mckp.MCKPSolution):
    assert a.picks == b.picks
    assert a.total_value == b.total_value
    assert a.spent == b.spent


# ---------------------------------------------------------------------------
# Single-root parity: hierarchical == flat grouped, bit-for-bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(10))
def test_single_root_sparse_parity(seed):
    rng = np.random.default_rng(seed)
    for _ in range(10):
        budget = float(rng.integers(3, 40)) * 25.0
        groups = _random_groups(rng, budget)
        flat = mckp.solve_sparse_grouped(groups, budget)
        root = mckp.DomainGroups(name="root", cap=budget, groups=tuple(groups))
        hier = mckp.solve_hierarchical(root, budget)
        _assert_bitwise_equal(flat, hier)
        assert hier.domain_spent is not None
        assert abs(hier.domain_spent["root"] - hier.spent) < 1e-6


@pytest.mark.parametrize("seed", range(4))
def test_single_root_fused_parity(seed):
    """The device pipeline on a single root domain (CPU: interpret mode,
    float64 values) is bit-for-bit the flat grouped host DP."""
    rng = np.random.default_rng(11 + seed)
    fstate = mckp.FusedState()
    for _ in range(3):
        budget = float(rng.integers(3, 10)) * 25.0
        groups = _random_groups(rng, budget)
        flat = mckp.solve_sparse_grouped(groups, budget)
        root = mckp.DomainGroups(name="root", cap=budget, groups=tuple(groups))
        hier = mckp.solve_hierarchical_fused(
            root, budget, state=mckp.HierState(), fstate=fstate
        )
        assert hier is not None, fstate.stats["fallback_reason"]
        _assert_bitwise_equal(flat, hier)


@hypothesis.given(seed=st.integers(0, 2**31 - 1), budget_u=st.integers(3, 50))
@hypothesis.settings(max_examples=30, deadline=None)
def test_single_root_parity_property(seed, budget_u):
    rng = np.random.default_rng(seed)
    budget = budget_u * 25.0
    groups = _random_groups(rng, budget)
    flat = mckp.solve_sparse_grouped(groups, budget)
    root = mckp.DomainGroups(name="root", cap=budget, groups=tuple(groups))
    _assert_bitwise_equal(flat, mckp.solve_hierarchical(root, budget))


# ---------------------------------------------------------------------------
# Multi-domain: cap feasibility + constrained brute-force optimality
# ---------------------------------------------------------------------------


def _constrained_brute(domains, budget):
    """Exhaustive optimum under per-domain caps: (cap, [tables]) pairs."""
    import itertools

    tabs = [(di, t) for di, (_, ts) in enumerate(domains) for t in ts]
    best = -1.0
    for choice in itertools.product(*[range(t.k) for _, t in tabs]):
        spend = np.zeros(len(domains))
        val = 0.0
        for (di, t), j in zip(tabs, choice):
            spend[di] += t.costs[j]
            val += t.values[j]
        if spend.sum() <= budget + 1e-9 and all(
            spend[d] <= domains[d][0] + 1e-9 for d in range(len(domains))
        ):
            best = max(best, val)
    return best


def _random_domain_instance(rng, budget):
    doms, kids = [], []
    for d in range(int(rng.integers(1, 4))):
        gs = _random_groups(rng, budget, n_groups=1, prefix=f"d{d}x")
        g = mckp.GroupedOptions(
            table=gs[0].table, members=gs[0].members[:2]
        )
        cap = float(rng.integers(1, 8)) * 25.0
        doms.append((cap, mckp.expand_groups([g])))
        kids.append(mckp.DomainGroups(name=f"d{d}", cap=cap, groups=(g,)))
    root = mckp.DomainGroups(name="root", cap=budget, children=tuple(kids))
    return doms, root


@pytest.mark.parametrize("seed", range(12))
def test_multi_domain_matches_constrained_brute_force(seed):
    rng = np.random.default_rng(400 + seed)
    budget = float(rng.integers(4, 12)) * 25.0
    doms, root = _random_domain_instance(rng, budget)
    hier = mckp.solve_hierarchical(root, budget)
    best = _constrained_brute(doms, budget)
    np.testing.assert_allclose(hier.total_value, best, atol=1e-9)
    for d, (cap, _) in enumerate(doms):
        assert hier.domain_spent[f"d{d}"] <= cap + 1e-6
    assert hier.spent <= budget + 1e-9
    # the device pipeline agrees on the optimum (float64 on the CPU)
    fstate = mckp.FusedState()
    fused = mckp.solve_hierarchical_fused(
        root, budget, state=mckp.HierState(), fstate=fstate
    )
    assert fused is not None, fstate.stats["fallback_reason"]
    np.testing.assert_allclose(fused.total_value, best, atol=1e-9)


@hypothesis.given(seed=st.integers(0, 2**31 - 1))
@hypothesis.settings(max_examples=20, deadline=None)
def test_multi_domain_feasibility_property(seed):
    rng = np.random.default_rng(seed)
    budget = float(rng.integers(4, 30)) * 25.0
    _, root = _random_domain_instance(rng, budget)
    hier = mckp.solve_hierarchical(root, budget)
    assert hier.spent <= budget + 1e-9
    for kid in root.children:
        assert hier.domain_spent[kid.name] <= kid.cap + 1e-6
    # picks re-aggregate to the reported per-domain spends
    for kid in root.children:
        members = {m for g in kid.groups for m in g.members}
        got = sum(hier.picks[m][0] for m in members if m in hier.picks)
        np.testing.assert_allclose(got, hier.domain_spent[kid.name], atol=1e-6)


def test_three_level_tree_caps_bind_at_every_level():
    rng = np.random.default_rng(77)
    budget = 500.0
    gA = _random_groups(rng, budget, n_groups=1, prefix="a")[0]
    gB = _random_groups(rng, budget, n_groups=1, prefix="b")[0]
    row = mckp.DomainGroups(
        name="row",
        cap=75.0,
        children=(
            mckp.DomainGroups(name="r0", cap=50.0, groups=(gA,)),
            mckp.DomainGroups(name="r1", cap=75.0, groups=(gB,)),
        ),
    )
    root = mckp.DomainGroups(name="site", cap=budget, children=(row,))
    hier = mckp.solve_hierarchical(root, budget)
    assert hier.domain_spent["r0"] <= 50.0 + 1e-6
    assert hier.domain_spent["row"] <= 75.0 + 1e-6
    np.testing.assert_allclose(
        hier.domain_spent["row"],
        hier.domain_spent["r0"] + hier.domain_spent["r1"],
        atol=1e-6,
    )


def test_frontier_and_curve_cache_reuse():
    rng = np.random.default_rng(5)
    budget = 400.0
    _, root = _random_domain_instance(rng, budget)
    curve_cache: dict = {}
    frontier_cache: dict = {}
    a = mckp.solve_hierarchical(
        root, budget, curve_cache=curve_cache, frontier_cache=frontier_cache
    )
    assert curve_cache and frontier_cache
    b = mckp.solve_hierarchical(
        root, budget, curve_cache=curve_cache, frontier_cache=frontier_cache
    )
    _assert_bitwise_equal(a, b)


def test_empty_leaf_domains_are_inert():
    rng = np.random.default_rng(9)
    budget = 300.0
    g = _random_groups(rng, budget, n_groups=1, prefix="a")[0]
    root = mckp.DomainGroups(
        name="root",
        cap=budget,
        children=(
            mckp.DomainGroups(name="empty", cap=100.0),
            mckp.DomainGroups(name="full", cap=budget, groups=(g,)),
        ),
    )
    hier = mckp.solve_hierarchical(root, budget)
    flat = mckp.solve_sparse_grouped([g], budget)
    assert hier.picks == flat.picks
    assert hier.domain_spent["empty"] == 0.0


# ---------------------------------------------------------------------------
# Controller / engine level
# ---------------------------------------------------------------------------


class TestEngineConservation:
    def test_single_root_engine_parity(self, suite):
        """ecoshift_hier on a one-domain topology allocates exactly like
        flat grouped ecoshift, round for round, through failures and
        stragglers.  (Measured improvements differ only in their noise —
        the measurement RNG is keyed by policy name.)"""
        system, apps, surfs = suite
        n = 40
        scen = (
            Scenario.constant(4, budget=1500.0)
            .with_failure(1, 2, 5)
            .with_straggler(2, 8, 1.8)
        )
        topo = PowerTopology.single_root(n, cap=1e18)
        sim_h = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=0, topology=topo
        )
        trace_h = sim_h.run(scen, make_controller("ecoshift_hier", system))
        sim_f = ClusterSim.build(system, apps, surfs, n_nodes=n, seed=0)
        trace_f = sim_f.run(scen, make_controller("ecoshift", system))
        for rh, rf in zip(trace_h.records, trace_f.records):
            assert dict(rh.result.allocation.caps) == dict(
                rf.result.allocation.caps
            )
            assert rh.result.allocation.spent == rf.result.allocation.spent

    @pytest.mark.parametrize("seed", range(4))
    def test_randomized_scenarios_never_violate_caps(self, suite, seed):
        """Acceptance: randomized multi-domain scenarios keep every domain
        at or under its cap in every round (engine-asserted + re-checked
        here from the records)."""
        system, apps, surfs = suite
        rng = np.random.default_rng(seed)
        n = 60
        n_racks = int(rng.integers(2, 5))
        # feasible but binding caps: per-rack committed baseline is
        # 300 W x (n / n_racks); give each rack a little headroom and the
        # site slightly less than the racks sum to, so both levels bind
        rack_committed = 300.0 * n / n_racks
        rack_cap = rack_committed + float(rng.integers(2, 8)) * 50.0
        site_cap = 300.0 * n + float(rng.integers(2, 8)) * 100.0
        topo = PowerTopology.uniform_racks(
            n, n_racks, rack_cap=rack_cap, site_cap=site_cap
        )
        scen = (
            Scenario.constant(5, budget=float(rng.integers(5, 30)) * 100.0)
            .with_topology(topo)
            .with_failure(1, *rng.choice(n, size=3, replace=False).tolist())
            .with_straggler(2, int(rng.integers(0, n)), 1.6)
            .with_domain_cap(3, f"rack{rng.integers(0, n_racks)}",
                             rack_committed + 50.0)
        )
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=seed,
            initial_caps=(150.0, 150.0), topology=topo,
        )
        trace = sim.run(scen, make_controller("ecoshift_hier", system))
        for rec in trace.records:
            assert rec.domain_draw is not None
            for name, draw in rec.domain_draw.items():
                assert draw <= rec.domain_caps[name] + 1e-6, (
                    rec.round, name, draw, rec.domain_caps[name]
                )

    def test_domain_cap_change_binds(self, suite):
        """A mid-run PDU derating visibly constrains the derated rack."""
        system, apps, surfs = suite
        n = 40
        # probe the rack's committed baseline draw (donors commit natural
        # draw, receivers their caps), then set caps just above it so the
        # rack cap genuinely binds
        probe = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=3,
            initial_caps=(150.0, 150.0),
            topology=PowerTopology.uniform_racks(n, 2, rack_cap=1e15),
        )
        _, committed, _ = probe.domain_headroom(0)
        c0 = float(committed[1])  # rack0's committed draw
        cap0, derated = c0 + 150.0, c0 + 50.0
        topo = PowerTopology.uniform_racks(n, 2, rack_cap=cap0)
        scen = (
            Scenario.constant(4, budget=2000.0)
            .with_topology(topo)
            .with_domain_cap(2, "rack0", derated)
        )
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=3,
            initial_caps=(150.0, 150.0), topology=topo,
        )
        trace = sim.run(scen, make_controller("ecoshift_hier", system))
        before = trace.records[1]
        after = trace.records[2]
        assert before.domain_caps["rack0"] == cap0
        assert after.domain_caps["rack0"] == derated
        assert before.domain_draw["rack0"] > derated  # the derate has teeth
        assert after.domain_draw["rack0"] <= derated + 1e-6
        assert after.domain_draw["rack0"] < before.domain_draw["rack0"]

    def test_flat_controller_on_topology_sim_records_draws(self, suite):
        """Flat controllers get accounting (no enforcement): the tight-rack
        violation a flat allocator commits is visible in the records."""
        system, apps, surfs = suite
        n = 40
        probe = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=3,
            initial_caps=(150.0, 150.0),
            topology=PowerTopology.uniform_racks(n, 2, rack_cap=1e15),
        )
        _, committed, _ = probe.domain_headroom(0)
        # tight racks: 25 W of headroom each, 2000 W of budget — a flat
        # allocator must push some rack over its PDU cap
        rack_cap = float(committed[1:].max()) + 25.0
        topo = PowerTopology.uniform_racks(n, 2, rack_cap=rack_cap)
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=3,
            initial_caps=(150.0, 150.0), topology=topo,
        )
        sim.run_round(make_controller("ecoshift", system), budget=2000.0)
        assert sim.last_domain_draw is not None
        over = [
            sim.last_domain_draw[k] - sim.last_domain_caps[k]
            for k in ("rack0", "rack1")
        ]
        assert max(over) > 0, over

    def test_committed_draw_respects_explicit_receivers(self, suite):
        """A donor passed explicitly via run_round(receivers=...) still
        gets grown from its baseline, so the domain accounting must commit
        its caps — not its (lower) natural draw — or the headroom would be
        overstated and the cap could be silently exceeded."""
        system, apps, surfs = suite
        topo = PowerTopology.uniform_racks(20, 2, rack_cap=1e15)
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=20, seed=0, topology=topo
        )
        donors, _, _ = sim.partition_rows()
        assert len(donors)
        d = donors[:1]
        caps_sum = float(sim.table.caps[d[0]].sum())
        assert sim._committed_draw()[d[0]] < caps_sum  # donor: natural draw
        assert sim._committed_draw(recv_rows=d)[d[0]] == caps_sum
        # threads through the per-domain headroom
        loose = sim.domain_headroom(0)[0]
        tight = sim.domain_headroom(0, recv_rows=d)[0]
        leaf = int(sim.table.domain_id[d[0]])
        assert tight[leaf] < loose[leaf]

    def test_hier_controller_warm_caches(self, suite):
        system, apps, surfs = suite
        n = 50
        topo = PowerTopology.uniform_racks(n, 4, rack_cap=16000.0)
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=2,
            initial_caps=(150.0, 150.0), topology=topo,
        )
        ctrl = make_controller("ecoshift_hier", system)
        sim.run_round(ctrl, budget=800.0)
        n_tables = len(ctrl._group_tables)
        n_frontiers = len(ctrl._frontiers)
        assert n_tables > 0 and n_frontiers > 0
        sim.run_round(ctrl, budget=800.0, round_index=1)
        assert len(ctrl._group_tables) == n_tables
        assert len(ctrl._frontiers) == n_frontiers

    def test_pure_policy_matches_controller(self, suite):
        system, apps, surfs = suite
        n = 30
        topo = PowerTopology.uniform_racks(
            n, 3, rack_cap=9800.0, site_cap=29000.0
        )
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=1,
            initial_caps=(150.0, 150.0), topology=topo,
        )
        _, recv, _ = sim.partition()
        baselines = {nd.app.name: nd.caps for nd in recv}
        seen = {nd.app.name: sim._surface(nd) for nd in recv}
        node_of = {nd.app.name: nd.node_id for nd in recv}
        extra, _, _ = sim.domain_headroom(0)
        domain_extra = dict(zip(topo.names, extra.tolist()))
        want = policies.ecoshift_hier(
            [nd.app for nd in recv], baselines, 900.0, system, seen,
            topology=topo, node_of=node_of, domain_extra=domain_extra,
        )
        got = sim.run_round(
            make_controller("ecoshift_hier", system), budget=900.0
        )
        assert dict(got.allocation.caps) == dict(want.caps)
        assert got.allocation.spent == want.spent

    def test_predictor_backed_hier_controller(self, suite):
        """ecoshift_hier with a predictor serves its own surfaces (the
        online path composes with the topology path)."""
        from repro.cluster.predictor import (
            OnlinePredictor,
            OnlinePredictorConfig,
        )

        system, apps, surfs = suite

        class _StubNCF:
            def __init__(self, system):
                self.system = system
                self.app_index = {}

        served = {
            a.name: surfaces.tabulate(surfs[a.name], system) for a in apps[:6]
        }
        pred = OnlinePredictor(_StubNCF(system), OnlinePredictorConfig())
        pred.seed_surfaces(served)
        n = 18
        topo = PowerTopology.uniform_racks(n, 2, rack_cap=6000.0)
        sim = ClusterSim.build(
            system, apps[:6], surfs, n_nodes=n, seed=1, topology=topo
        )
        ctrl = make_controller("ecoshift_hier", system, predictor=pred)
        assert ctrl.serves_own_surfaces
        res = sim.run_round(ctrl, budget=900.0)
        assert np.isfinite(list(res.improvements.values())).all()
        for name, draw in sim.last_domain_draw.items():
            assert draw <= sim.last_domain_caps[name] + 1e-6
