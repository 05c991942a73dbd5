"""The fused round's on-chip numeric contract, checked on the CPU.

A TPU runs the fused pipeline with float32 values: its Mosaic kernels
take no float64, so the float64 bit-for-bit contract of the CPU cannot
hold there.  DESIGN.md §14 states what does: spends stay exact on the
int32 micro-watt lattice, so every domain cap and the budget hold
exactly, and the chosen allocation's value (assembled on the host in
float64) trails the host optimum by at most the round's
``value_bound`` — float32 resolution over its stage count and tree
depth.  These tests force float32 on the CPU (kernels in interpret
mode) and hold the flat, the 16-rack and a churn-storm round to that
contract; ``chip_smoke.py`` checks the same on the chip.
"""

import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mckp
from repro.kernels import ops

from test_hier_alloc import _random_groups

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

#: one step of the micro-watt lattice every spend lives on
_TOL_W = 1e-6


@pytest.fixture
def float32_values(monkeypatch):
    """The dtype a TPU picks, steered here in the test (not by an option
    of the program)."""
    monkeypatch.setattr(ops, "device_value_dtype", lambda: np.float32)


def _hold_contract(sol, host, fstate, budget, caps=()):
    assert sol is not None, fstate.stats["fallback_reason"]
    assert fstate.vb_dev.dtype == jnp.float32
    bound = fstate.stats["value_bound"]
    assert 0.0 < bound < 1e-4 * max(1.0, host.total_value)
    assert sol.spent <= budget + _TOL_W
    for name, cap in caps:
        assert sol.domain_spent[name] <= cap + _TOL_W, name
    gap = host.total_value - sol.total_value
    assert -1e-12 <= gap <= bound
    if sol.picks == host.picks:
        assert sol.total_value == host.total_value
        assert sol.spent == host.spent
    return sol.picks == host.picks


@pytest.mark.parametrize("seed", range(8))
def test_flat_float32_within_bound(float32_values, seed):
    rng = np.random.default_rng(9100 + seed)
    budget = float(rng.integers(8, 60)) * 25.0
    groups = _random_groups(rng, budget, n_groups=int(rng.integers(2, 6)))
    host = mckp.solve_sparse_grouped(groups, budget)
    fstate = mckp.FusedState()
    sol = mckp.solve_grouped_fused(groups, budget, fstate=fstate)
    _hold_contract(sol, host, fstate, budget)


@pytest.mark.parametrize("seed", range(6))
def test_hier16_float32_within_bound(float32_values, seed):
    """16 racks under one site, every rack cap binding."""
    rng = np.random.default_rng(9200 + seed)
    budget = float(rng.integers(40, 120)) * 25.0
    racks = []
    for i in range(16):
        groups = _random_groups(rng, budget, prefix=f"r{i:02d}x")
        cap = float(rng.integers(1, 10)) * 25.0
        racks.append(mckp.DomainGroups(name=f"r{i:02d}", cap=cap, groups=tuple(groups)))
    root = mckp.DomainGroups(name="site", cap=budget, children=tuple(racks))
    host = mckp.solve_hierarchical(root, budget)
    fstate = mckp.FusedState()
    sol = mckp.solve_hierarchical_fused(
        root, budget, state=mckp.HierState(), fstate=fstate
    )
    caps = [(d.name, d.cap) for d in racks] + [("site", budget)]
    _hold_contract(sol, host, fstate, budget, caps)


def test_churn_storm_float32_holds_contract(float32_values):
    """The chip smoke's own scenario and checks at 1k nodes: 16 binding
    racks, 10% mixed churn, every post-warmup round fused."""
    import chip_smoke

    summary = chip_smoke.smoke_rounds(1000, 16, 0.10)
    assert str(summary["fstate"].vb_dev.dtype) == "float32"
    assert all(r["solver"] == "fused" for r in summary["rounds"][4:])
    assert summary["max_gap"] <= summary["max_bound"]
