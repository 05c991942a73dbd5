"""MCKP solver equivalence + invariants (paper §3.2.2, Algorithm 1)."""

import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # image without hypothesis: property tests skip
    from _hypothesis_stub import hypothesis, st

from repro.core import curves, mckp


def random_options(rng: np.random.Generator, n_apps: int, budget: float):
    """Random pruned option tables (staircase form, integer costs)."""
    opts = []
    for i in range(n_apps):
        k = int(rng.integers(1, 7))
        costs = np.unique(rng.integers(1, max(2, int(budget)), size=k)).astype(float)
        values = np.sort(rng.uniform(0.01, 0.5, size=len(costs)))
        caps = np.stack([100.0 + costs, np.full_like(costs, 100.0)], axis=-1)
        costs = np.concatenate([[0.0], costs])
        values = np.concatenate([[0.0], values])
        caps = np.concatenate([[[100.0, 100.0]], caps], axis=0)
        opts.append(
            curves.OptionTable(name=f"app{i}", costs=costs, values=values, caps=caps)
        )
    return opts


@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    n_apps=st.integers(1, 5),
    budget=st.integers(5, 60),
)
@hypothesis.settings(max_examples=40, deadline=None)
def test_all_solvers_match_brute_force(seed, n_apps, budget):
    rng = np.random.default_rng(seed)
    opts = random_options(rng, n_apps, float(budget))
    bf = mckp.brute_force(opts, float(budget))
    sp = mckp.solve_sparse(opts, float(budget))
    np.testing.assert_allclose(sp.total_value, bf.total_value, atol=1e-9)
    assert sp.spent <= budget + 1e-9


@pytest.mark.parametrize("seed", range(10))
def test_fused_matches_brute_force(seed):
    """The device pipeline (CPU: interpret mode, float64 values) reaches
    the exhaustive optimum, within budget, with picks that add up."""
    rng = np.random.default_rng(500 + seed)
    budget = float(rng.integers(10, 60))
    opts = random_options(rng, int(rng.integers(1, 6)), budget)
    groups = [mckp.GroupedOptions(table=o, members=(o.name,)) for o in opts]
    fstate = mckp.FusedState()
    sol = mckp.solve_grouped_fused(groups, budget, fstate=fstate)
    assert sol is not None, fstate.stats["fallback_reason"]
    bf = mckp.brute_force(opts, budget)
    np.testing.assert_allclose(sol.total_value, bf.total_value, atol=1e-9)
    assert sol.spent <= budget + 1e-9
    assert set(sol.picks) == {o.name for o in opts}
    total = sum(v for _, v, _ in sol.picks.values())
    np.testing.assert_allclose(total, sol.total_value, atol=1e-9)
    spent = sum(c for c, _, _ in sol.picks.values())
    np.testing.assert_allclose(spent, sol.spent, atol=1e-9)


def test_picks_consistent_with_value():
    """Reported picks must sum to the reported total (backtrack integrity)."""
    rng = np.random.default_rng(3)
    opts = random_options(rng, 6, 80.0)
    sol = mckp.solve_sparse(opts, 80.0)
    total = sum(v for _, v, _ in sol.picks.values())
    np.testing.assert_allclose(total, sol.total_value, atol=1e-6)
    spent = sum(c for c, _, _ in sol.picks.values())
    np.testing.assert_allclose(spent, sol.spent, atol=1e-6)


@hypothesis.given(seed=st.integers(0, 2**31 - 1))
@hypothesis.settings(max_examples=20, deadline=None)
def test_value_monotone_in_budget(seed):
    """More reclaimed power never decreases the optimum."""
    rng = np.random.default_rng(seed)
    opts = random_options(rng, 4, 100.0)
    vals = [mckp.solve_sparse(opts, float(b)).total_value for b in (0, 20, 50, 100)]
    assert all(v2 >= v1 - 1e-12 for v1, v2 in zip(vals, vals[1:]))


def test_zero_budget_zero_value():
    rng = np.random.default_rng(11)
    opts = random_options(rng, 4, 50.0)
    sol = mckp.solve_sparse(opts, 0.0)
    assert sol.total_value == 0.0
    assert sol.spent == 0.0
    for _, (cost, val, _) in sol.picks.items():
        assert cost == 0.0 and val == 0.0


class TestBuildOptions:
    def test_staircase_properties(self):
        from repro.core import surfaces, types

        s = surfaces.cfd_surface()
        opts = curves.build_options(
            "cfd", s, (300.0, 200.0), types.SYSTEM_2.grid, 150.0
        )
        assert opts.costs[0] == 0.0 and opts.values[0] == 0.0
        assert np.all(np.diff(opts.costs) > 0)
        assert np.all(np.diff(opts.values) > 0)  # dominated options pruned
        assert np.all(opts.costs <= 150.0 + 1e-9)
        # every option's caps are >= baseline and consistent with its cost
        for j in range(opts.k):
            c, g = opts.caps[j]
            assert c >= 300.0 - 1e-9 and g >= 200.0 - 1e-9
            np.testing.assert_allclose((c - 300.0) + (g - 200.0), opts.costs[j])
