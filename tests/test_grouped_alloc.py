"""Group-collapsed allocation parity suite (DESIGN.md §11).

The load-bearing contracts of the multiplicity-aware MCKP solvers:

 * ``solve_sparse_grouped`` is **bit-for-bit** equal to ``solve_sparse`` on
   the name-sorted ungrouped expansion — picks, total_value and spent —
   on randomized mixed clusters, including interleaved member names and
   byte-identical duplicate tables (the straggler split);
 * the device pipeline's grouped solve (``solve_grouped_fused``) is
   bit-for-bit ``solve_sparse`` on the same expansion;
 * end-to-end: a grouped controller stepping a scenario with failures and
   stragglers produces exactly the legacy per-instance controller's
   allocations and measured improvements, round for round.
"""

import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # image without hypothesis: property tests skip
    from _hypothesis_stub import hypothesis, st

from repro.cluster import ClusterSim, Scenario
from repro.cluster.controller import make_controller
from repro.core import curves, mckp, policies, surfaces, types


@pytest.fixture(scope="module")
def suite():
    system = types.SYSTEM_1
    apps, surfs = surfaces.build_paper_suite(system)
    return system, apps, surfs


# ---------------------------------------------------------------------------
# Randomized mixed clusters: sparse grouped == sparse ungrouped, bit-for-bit
# ---------------------------------------------------------------------------


def _random_groups(rng: np.random.Generator, budget: float):
    """Random behaviour classes with interleaved member names and an
    occasional byte-identical duplicate table (straggler split)."""
    n_groups = int(rng.integers(1, 6))
    sizes = [int(rng.integers(1, 8)) for _ in range(n_groups)]
    slots: list[int] = []
    for g, m in enumerate(sizes):
        slots += [g] * m
    rng.shuffle(slots)
    members: dict[int, list[str]] = {g: [] for g in range(n_groups)}
    for i, g in enumerate(slots):
        members[g].append(f"x{i:03d}")

    groups = []
    for g in range(n_groups):
        k = int(rng.integers(1, 7))
        costs = np.unique(
            rng.integers(1, max(2, int(budget / 25)), size=k)
        ).astype(float) * 25.0
        values = np.sort(rng.uniform(0.01, 0.5, size=len(costs)))
        caps = np.stack([100.0 + costs, np.full_like(costs, 100.0)], axis=-1)
        table = curves.OptionTable(
            name=f"class{g}",
            costs=np.concatenate([[0.0], costs]),
            values=np.concatenate([[0.0], values]),
            caps=np.concatenate([[[100.0, 100.0]], caps], axis=0),
        )
        groups.append(
            mckp.GroupedOptions(table=table, members=tuple(sorted(members[g])))
        )
    if n_groups >= 2 and rng.random() < 0.4:
        t0 = groups[0].table
        dup = curves.OptionTable(
            name="dup",
            costs=t0.costs.copy(),
            values=t0.values.copy(),
            caps=t0.caps.copy(),
        )
        groups[1] = mckp.GroupedOptions(table=dup, members=groups[1].members)
    return groups


def _assert_bitwise_equal(a: mckp.MCKPSolution, b: mckp.MCKPSolution):
    assert a.picks == b.picks
    assert a.total_value == b.total_value
    assert a.spent == b.spent


@pytest.mark.parametrize("seed", range(12))
def test_sparse_grouped_parity_grid_sweep(seed):
    rng = np.random.default_rng(seed)
    for _ in range(15):
        budget = float(rng.integers(3, 40)) * 25.0
        groups = _random_groups(rng, budget)
        sp = mckp.solve_sparse(mckp.expand_groups(groups), budget)
        gr = mckp.solve_sparse_grouped(groups, budget)
        _assert_bitwise_equal(sp, gr)


@hypothesis.given(seed=st.integers(0, 2**31 - 1), budget_u=st.integers(3, 60))
@hypothesis.settings(max_examples=30, deadline=None)
def test_sparse_grouped_parity_property(seed, budget_u):
    rng = np.random.default_rng(seed)
    budget = budget_u * 25.0
    groups = _random_groups(rng, budget)
    sp = mckp.solve_sparse(mckp.expand_groups(groups), budget)
    gr = mckp.solve_sparse_grouped(groups, budget)
    _assert_bitwise_equal(sp, gr)


def test_sparse_grouped_curve_cache_reuse():
    rng = np.random.default_rng(5)
    groups = _random_groups(rng, 500.0)
    cache: dict = {}
    a = mckp.solve_sparse_grouped(groups, 500.0, curve_cache=cache)
    assert cache  # aggregate curves were stored
    b = mckp.solve_sparse_grouped(groups, 500.0, curve_cache=cache)
    _assert_bitwise_equal(a, b)


def test_aggregate_curve_matches_sequential_stages():
    """The binary-split m-fold self-convolution equals brute force over a
    small group."""
    rng = np.random.default_rng(9)
    for _ in range(10):
        budget = float(rng.integers(4, 12)) * 25.0
        groups = _random_groups(rng, budget)[:1]
        g = mckp.GroupedOptions(
            table=groups[0].table, members=groups[0].members[:4] or ("a",)
        )
        bf = mckp.brute_force(mckp.expand_groups([g]), budget)
        gr = mckp.solve_sparse_grouped([g], budget)
        np.testing.assert_allclose(gr.total_value, bf.total_value, atol=1e-9)
        assert gr.spent <= budget + 1e-9


# ---------------------------------------------------------------------------
# Device pipeline
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(3))
def test_fused_grouped_matches_expanded_sparse(seed):
    """The fused grouped solve (CPU: interpret mode, float64 values) is
    bit-for-bit the ungrouped host DP on the name-sorted expansion."""
    rng = np.random.default_rng(30 + seed)
    fstate = mckp.FusedState()
    for _ in range(3):
        budget = float(rng.integers(3, 25)) * 25.0
        groups = _random_groups(rng, budget)
        sol = mckp.solve_grouped_fused(groups, budget, fstate=fstate)
        assert sol is not None, fstate.stats["fallback_reason"]
        _assert_bitwise_equal(
            mckp.solve_sparse(mckp.expand_groups(groups), budget), sol
        )


# ---------------------------------------------------------------------------
# Controller / engine level
# ---------------------------------------------------------------------------


class TestControllerParity:
    def test_grouped_controller_equals_pure_policy(self, suite):
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=25, seed=4)
        _, recv, _ = sim.partition()
        baselines = {n.app.name: n.caps for n in recv}
        seen = {n.app.name: sim._surface(n) for n in recv}
        rows = sim.table.rows_for_ids([n.node_id for n in recv])
        batch = sim._receiver_batch(rows, None, False)
        ctrl = make_controller("ecoshift", system)
        for budget in (400.0, 1500.0):
            got = ctrl.allocate_grouped(batch, budget)
            want = policies.ecoshift(
                [n.app for n in recv], baselines, budget, system, seen
            )
            assert dict(got.caps) == dict(want.caps)
            assert got.spent == want.spent

    def test_pure_policy_grouped_kwarg(self, suite):
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=30, seed=6)
        _, recv, _ = sim.partition()
        baselines = {n.app.name: n.caps for n in recv}
        seen = {n.app.name: sim._surface(n) for n in recv}
        recv_apps = [n.app for n in recv]
        a = policies.ecoshift(recv_apps, baselines, 900.0, system, seen)
        b = policies.ecoshift(
            recv_apps, baselines, 900.0, system, seen, grouped=True
        )
        assert dict(a.caps) == dict(b.caps)
        assert a.spent == b.spent

    @pytest.mark.parametrize("policy", ["ecoshift", "oracle"])
    def test_scenario_grouped_equals_legacy_per_instance(self, suite, policy):
        """Full multi-round certification: grouped columnar controller ==
        legacy per-instance controller, through failures and stragglers
        (the straggler's byte-identical table exercises class merging)."""
        system, apps, surfs = suite
        scen = (
            Scenario.constant(4, budget=1500.0)
            .with_failure(1, 2, 5)
            .with_straggler(2, 8, 1.8)
        )
        kw = {"exhaustive": False} if policy == "oracle" else {}
        sim_g = ClusterSim.build(system, apps, surfs, n_nodes=40, seed=0)
        trace_g = sim_g.run(scen, make_controller(policy, system, **kw))
        sim_l = ClusterSim.build(system, apps, surfs, n_nodes=40, seed=0)
        ctrl_l = make_controller(policy, system, **kw)
        if policy == "ecoshift":
            ctrl_l.grouped = False
        else:
            ctrl_l.supports_grouped = False
        trace_l = sim_l.run(scen, ctrl_l)
        for rg, rl in zip(trace_g.records, trace_l.records):
            assert dict(rg.result.allocation.caps) == dict(
                rl.result.allocation.caps
            )
            assert rg.result.improvements == rl.result.improvements

    def test_online_controller_grouped_path(self, suite):
        """ecoshift_online allocates through the grouped path with
        predictor-served surfaces (one class per served app)."""
        from repro.cluster.predictor import OnlinePredictor, OnlinePredictorConfig

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
        sim = ClusterSim.build(system, apps[:6], surfs, n_nodes=18, seed=1)
        ctrl = make_controller("ecoshift_online", system, predictor=pred)
        assert ctrl.supports_grouped
        res = sim.run_round(ctrl, budget=900.0)
        assert np.isfinite(list(res.improvements.values())).all()
        # served surfaces are shared per app: warm cache holds one table
        # per (app class, baseline), not one per node
        assert len(ctrl._group_tables) <= len(served)

    def test_grouped_cache_warm_across_budgets(self, suite):
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=50, seed=2)
        ctrl = make_controller("ecoshift", system)
        sim.run_round(ctrl, budget=500.0)
        n_tables = len(ctrl._group_tables)
        assert n_tables > 0
        sim.run_round(ctrl, budget=2500.0)  # budget-independent tables
        assert len(ctrl._group_tables) == n_tables
