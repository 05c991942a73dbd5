"""Incremental-allocation certification suite (DESIGN.md §13).

The load-bearing contract of this PR: every incremental path — delta
tracking, warm content-keyed caches, the frontier aggregation tree,
batched leaf DPs — is **bit-for-bit** equal to the from-scratch solvers,
through arbitrary event sequences.  Plus: NodeTable dirty-row semantics,
LRU bounds on warm caches over long scenarios, and bitwise parity of the
batched (max,+) primitives against their per-instance forms.
"""

import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # image without hypothesis: property tests skip
    from _hypothesis_stub import hypothesis, st

from repro.cluster import ClusterSim, PowerTopology, scenario as sc
from repro.cluster.controller import make_controller
from repro.cluster.sim import NodeTable
from repro.core import curves, mckp, policies, surfaces, types


@pytest.fixture(scope="module")
def suite():
    system = types.SYSTEM_1
    apps, surfs = surfaces.build_paper_suite(system)
    return system, apps, surfs


# ---------------------------------------------------------------------------
# NodeTable delta tracking
# ---------------------------------------------------------------------------


class TestDirtyTracking:
    def _table(self, n=8):
        sim_nodes = []
        from repro.cluster.sim import NodeState
        from repro.core.types import AppSpec

        for i in range(n):
            app = AppSpec(name=f"a#{i}", sclass="B", surface_id="s")
            sim_nodes.append(
                NodeState(node_id=i, app=app, base_app="a", caps=(100.0, 100.0))
            )
        return NodeTable.from_nodes(sim_nodes)

    def test_bump_rows_accumulate(self):
        t = self._table()
        v0 = t.version
        t.bump(rows=[1, 3])
        t.bump(rows=[3, 5])
        assert t.dirty_since(v0).tolist() == [1, 3, 5]
        assert t.dirty_since(t.version).tolist() == []

    def test_unbounded_bump_poisons(self):
        t = self._table()
        v0 = t.version
        t.bump(rows=[2])
        t.bump()  # coarse: everything dirty
        assert t.dirty_since(v0) is None

    def test_horizon_exceeded_returns_none(self):
        from repro.cluster import sim as sim_mod

        t = self._table()
        v0 = t.version
        for i in range(sim_mod._DIRTY_HORIZON + 3):
            t.bump(rows=[i % 4])
        assert t.dirty_since(v0) is None
        # recent window still bounded
        v1 = t.version
        t.bump(rows=[7])
        assert t.dirty_since(v1).tolist() == [7]

    def test_unknown_version_returns_none(self):
        t = self._table()
        assert t.dirty_since(t.version + 5) is None

    def test_apply_events_logs_dirty_rows(self, suite):
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=12, seed=0)
        v0 = sim.table.version
        sim.apply_events([
            sc.StragglerOnset(round=1, node_id=3, slowdown=1.5),
            sc.NodeFailure(round=1, node_ids=(7,)),
        ])
        dirty = sim.table.dirty_since(v0)
        assert dirty is not None and set(dirty.tolist()) == {3, 7}

    def test_natural_draws_delta_patch(self, suite):
        """Only dirty rows are refilled; the result equals a cold rebuild."""
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=30, seed=0)
        nat0 = sim._natural_draws()
        other = next(
            a.name for a in apps if a.name != sim.table.strings[
                sim.table.base_gid[4]]
        )
        sim.apply_events([sc.PhaseChange(round=1, node_id=4, surface_id=other)])
        nat1 = sim._natural_draws()
        cold = ClusterSim.build(system, apps, surfs, n_nodes=30, seed=0)
        cold.apply_events([sc.PhaseChange(round=1, node_id=4, surface_id=other)])
        np.testing.assert_array_equal(nat1, cold._natural_draws())
        assert nat1 is not nat0 or (nat1 == nat0).all()

    def test_partition_memoized_per_version(self, suite):
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=20, seed=0)
        d0, r0, p0 = sim.partition_rows()
        d1, r1, p1 = sim.partition_rows()
        assert d0 is d1 and r0 is r1 and p0 == p1
        sim.apply_events([sc.NodeFailure(round=1, node_ids=(int(r0[0]),))])
        d2, r2, _ = sim.partition_rows()
        assert r2 is not r0


class TestDeltaPathSoundness:
    """The engine's delta-patch caches must fall back to full rebuilds
    whenever their positional assumptions don't hold (code-review
    regression tests)."""

    def test_unsorted_explicit_receivers_get_fresh_surfaces(self, suite):
        """run_round(receivers=...) in arbitrary order across an event:
        the batch must carry the post-event surfaces at every position."""
        system, apps, surfs = suite
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=30, seed=0,
            initial_caps=(150.0, 150.0),
        )
        _, recv, _ = sim.partition_rows()
        rows_rev = recv[::-1].copy()
        b0 = sim._receiver_batch(rows_rev, None, False)
        victim_row = int(rows_rev[len(rows_rev) // 2])
        victim_id = int(sim.table.node_ids[victim_row])
        sim.apply_events(
            [sc.StragglerOnset(round=1, node_id=victim_id, slowdown=1.6)]
        )
        b1 = sim._receiver_batch(rows_rev, None, False)
        pos = int(np.flatnonzero(rows_rev == victim_row)[0])
        want = sim._surface_of(
            sim.table.strings[sim.table.base_gid[victim_row]], 1.6
        )
        assert b1.surfaces[pos] is want, "stale surface at patched position"
        assert b1.surfaces[pos] is not b0.surfaces[pos]

    def test_unsorted_rows_measurement_not_stale(self, suite):
        """_measure_rows' baseline cache must not mis-place dirty rows
        when rows are not ascending."""
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=30, seed=0)
        _, recv, _ = sim.partition_rows()
        rows = recv[::-1].copy()
        base = sim.table.caps[rows]
        rng = sim.round_rng("x", 0)
        sim._measure_rows(rows, base, base, rng)  # warm the cache
        victim_row = int(rows[3])
        other = next(
            a.name
            for a in apps
            if a.name != sim.table.strings[sim.table.base_gid[victim_row]]
        )
        sim.apply_events([sc.PhaseChange(
            round=1, node_id=int(sim.table.node_ids[victim_row]),
            surface_id=other,
        )])
        _, recv2, _ = sim.partition_rows()
        rows2 = rows[np.isin(rows, recv2)]
        base2 = sim.table.caps[rows2]
        t0a, _, _ = sim._measure_rows(rows2, base2, base2, sim.round_rng("x", 1))
        cold = ClusterSim.build(system, apps, surfs, n_nodes=30, seed=0)
        cold.apply_events([sc.PhaseChange(
            round=1, node_id=int(sim.table.node_ids[victim_row]),
            surface_id=other,
        )])
        t0b, _, _ = cold._measure_rows(rows2, base2, base2, cold.round_rng("x", 1))
        np.testing.assert_array_equal(t0a, t0b)

    def test_controller_reused_across_sims(self, suite):
        """Batch seqs are process-global, so one controller driven by two
        sims can never mistake one sim's batch chain for the other's
        (code-review regression: a per-sim counter made both sims issue
        seq=1 and the grouping state served cluster A's receivers to B)."""
        system, apps, surfs = suite
        ctrl = make_controller("ecoshift", system)
        a = ClusterSim.build(system, apps, surfs, n_nodes=30, seed=0)
        b = ClusterSim.build(system, apps, surfs, n_nodes=20, seed=3)
        ra = a.run_round(ctrl, budget=900.0)
        rb = b.run_round(ctrl, budget=900.0)
        ra1 = a.run_round(ctrl, budget=900.0, round_index=1)
        rb1 = b.run_round(ctrl, budget=900.0, round_index=1)
        assert set(ra1.allocation.caps) == set(ra.allocation.caps)
        assert set(rb1.allocation.caps) == set(rb.allocation.caps)

    def test_surface_reregistration_reaches_patched_batch(self, suite):
        """NodeArrival(surface=...) re-registering an app's ground truth
        dirties only the new row; existing rows of that app must still
        see the new surface object in the next (patched) batch."""
        from repro.core.surfaces import tabulate

        system, apps, surfs = suite
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=20, seed=0,
            initial_caps=(150.0, 150.0),
        )
        _, recv, _ = sim.partition_rows()
        sim._receiver_batch(recv, None, False)  # warm the batch cache
        base_name = sim.table.strings[sim.table.base_gid[recv[0]]]
        spec = next(a for a in apps if a.name == base_name)
        new_surf = tabulate(surfs[base_name], system)
        sim.apply_events([sc.NodeArrival(
            round=1, app=spec, surface=new_surf,
        )])
        _, recv2, _ = sim.partition_rows()
        batch = sim._receiver_batch(recv2, None, False)
        pos = [
            i for i, nm in enumerate(batch.names)
            if nm.startswith(base_name + "#")
        ]
        assert pos, "no receivers of the re-registered app in the batch"
        for i in pos:
            assert batch.surfaces[i] is new_surf, (
                "existing rows kept the stale surface after re-registration"
            )


# ---------------------------------------------------------------------------
# Batched primitives == per-instance forms, bitwise
# ---------------------------------------------------------------------------


def _random_stage_curves(rng, n_stages=None):
    """Watt-lattice sparse stage curves (the production shape)."""
    n_stages = n_stages or int(rng.integers(2, 6))
    out = []
    for _ in range(n_stages):
        k = int(rng.integers(1, 7))
        costs = np.unique(
            np.concatenate([[0], rng.integers(1, 14, size=k) * 25])
        ).astype(np.float64)
        keys = mckp._qkey_np(costs)
        vals = np.concatenate([[0.0], np.sort(rng.uniform(0.01, 0.4, len(costs) - 1))])
        out.append((keys, vals))
    return out


@pytest.mark.parametrize("seed", range(8))
def test_superstage_dp_batch_bitwise(seed):
    """Batched leaf DPs == per-leaf ``_superstage_dp``: keys, values and
    every backtracked spend sequence."""
    rng = np.random.default_rng(seed)
    jobs = []
    for _ in range(int(rng.integers(2, 6))):
        eff = float(rng.integers(2, 20)) * 25.0
        jobs.append((_random_stage_curves(rng), eff))
    batch = mckp._superstage_dp_batch(jobs)
    assert batch is not None
    for (curves, eff), (bk, bv, bstages) in zip(jobs, batch):
        k, v, stages = mckp._superstage_dp(curves, eff)
        assert bk.tobytes() == k.tobytes()
        assert bv.tobytes() == v.tobytes()
        for u in k:
            assert mckp._backtrack_superstages(
                bstages, float(u)
            ) == mckp._backtrack_superstages(stages, float(u))


@pytest.mark.parametrize("seed", range(6))
def test_maxplus_pair_int_matches_generic(seed):
    """The integer-lattice fast path == the outer-product + lexsort dedupe
    path, bitwise, including backpointers."""
    rng = np.random.default_rng(100 + seed)
    budget = float(rng.integers(4, 40)) * 25.0
    a_keys = mckp._qkey_np(
        np.unique(np.concatenate([[0], rng.integers(1, 50, 40) * 25])).astype(float)
    )
    b_keys = mckp._qkey_np(
        np.unique(np.concatenate([[0], rng.integers(1, 50, 40) * 25])).astype(float)
    )
    a_vals = np.sort(rng.uniform(0, 1, len(a_keys)))
    b_vals = np.sort(rng.uniform(0, 1, len(b_keys)))
    ia, ib = mckp._micro_int(a_keys), mckp._micro_int(b_keys)
    fast = mckp._maxplus_pair_int(ia, a_keys, a_vals, ib, b_keys, b_vals, budget)
    assert fast is not None
    raw = (a_keys[:, None] + b_keys[None, :]).ravel()
    vals = (a_vals[:, None] + b_vals[None, :]).ravel()
    feas = np.flatnonzero(raw <= budget + 1e-9)
    keys, sel = mckp._dedupe_first_max(mckp._qkey_np(raw[feas]), vals[feas])
    sel = feas[sel]
    nb = len(b_keys)
    ref = (keys, vals[sel], a_keys[sel // nb], b_keys[sel % nb])
    for f, r in zip(fast, ref):
        assert f.tobytes() == r.tobytes()


def test_curve_cutoff_invariance():
    """Aggregate curves truncated from any cutoff >= the DP budget solve
    identically (states, values, unwound multisets)."""
    rng = np.random.default_rng(3)
    budget = 300.0
    curves = _random_stage_curves(rng, n_stages=1)
    keys, vals = curves[0]
    from repro.core.curves import OptionTable

    table = OptionTable(
        name="c",
        costs=keys.copy(),
        values=vals.copy(),
        caps=np.stack([100.0 + keys, np.full_like(keys, 100.0)], axis=-1),
    )
    a = mckp.aggregate_curve(table, 7, budget)
    b = mckp.aggregate_curve(table, 7, mckp._curve_cutoff(budget))
    cut = np.searchsorted(b.keys, budget + 1e-9)
    assert b.keys[:cut].tobytes() == a.keys.tobytes()
    assert b.vals[:cut].tobytes() == a.vals.tobytes()
    for u in a.keys:
        ja, jb = [], []
        a.unwind(float(u), ja)
        b.unwind(float(u), jb)
        assert sorted(ja) == sorted(jb)


# ---------------------------------------------------------------------------
# End-to-end: incremental == from-scratch through randomized event storms
# ---------------------------------------------------------------------------


def _random_events(rng, sim, apps, r, k=4, topo_racks=None):
    alive = sim.table.node_ids[sim.table.alive]
    recv_apps = [a.name for a in apps]
    ev = []
    for _ in range(k):
        kind = rng.integers(0, 4 if topo_racks else 3)
        v = int(rng.choice(alive))
        if kind == 0:
            ev.append(sc.StragglerOnset(
                round=r, node_id=v,
                slowdown=float(rng.choice([1.0, 1.4, 1.9]))))
        elif kind == 1:
            ev.append(sc.PhaseChange(
                round=r, node_id=v,
                surface_id=recv_apps[int(rng.integers(len(recv_apps)))]))
        elif kind == 2:
            ev.append(sc.NodeFailure(round=r, node_ids=(v,)))
        else:
            ev.append(sc.DomainCapChange(
                round=r,
                domain=topo_racks[int(rng.integers(len(topo_racks)))],
                cap=float(rng.integers(80, 140)) * 100.0,
            ))
    return ev


def _run_parity_scenario(system, apps, surfs, seed, *, hier: bool):
    """Two identical sims, incremental vs from-scratch controller; assert
    bitwise-equal allocations every round under a random event storm."""
    rng = np.random.default_rng(seed)
    n = 48
    if hier:
        topo = PowerTopology.uniform_racks(n, 4, rack_cap=7000.0)
        policy = "ecoshift_hier"
        racks = [f"rack{i}" for i in range(4)]
    else:
        topo, policy, racks = None, "ecoshift", None
    pair = []
    for inc in (True, False):
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=0,
            initial_caps=(150.0, 150.0),
            topology=(
                PowerTopology.uniform_racks(n, 4, rack_cap=7000.0)
                if hier else None
            ),
        )
        ctrl = make_controller(policy, system, incremental=inc)
        pair.append((sim, ctrl))
    budget = 1800.0
    for r in range(6):
        events = _random_events(rng, pair[0][0], apps, r, topo_racks=racks) \
            if r >= 1 else []
        allocs = []
        for sim, ctrl in pair:
            if events:
                touched = sim.apply_events(events)
                ctrl.invalidate(touched)
            res = sim.run_round(ctrl, budget=budget, round_index=r)
            allocs.append(res.allocation)
        a, b = allocs
        assert dict(a.caps) == dict(b.caps), f"seed {seed} round {r}"
        assert a.spent == b.spent


@pytest.mark.parametrize("seed", range(5))
def test_incremental_flat_parity_event_storm(suite, seed):
    system, apps, surfs = suite
    _run_parity_scenario(system, apps[:8], surfs, seed, hier=False)


@pytest.mark.parametrize("seed", range(5))
def test_incremental_hier_parity_event_storm(suite, seed):
    system, apps, surfs = suite
    _run_parity_scenario(system, apps[:8], surfs, seed, hier=True)


@hypothesis.given(seed=st.integers(0, 2**31 - 1))
@hypothesis.settings(max_examples=10, deadline=None)
def test_incremental_parity_property(seed):
    system = types.SYSTEM_1
    apps, surfs = surfaces.build_paper_suite(system)
    _run_parity_scenario(system, apps[:6], surfs, seed, hier=(seed % 2 == 0))


def test_incremental_matches_fresh_solver_each_round(suite):
    """The warm incremental controller's solution == a cold
    ``solve_hierarchical`` on the same round inputs (the from-scratch
    certification the ISSUE names)."""
    system, apps, surfs = suite
    n = 40
    topo = PowerTopology.uniform_racks(n, 4, rack_cap=6500.0)
    sim = ClusterSim.build(
        system, apps[:6], surfs, n_nodes=n, seed=1,
        initial_caps=(150.0, 150.0), topology=topo,
    )
    ctrl = make_controller("ecoshift_hier", system)
    rng = np.random.default_rng(5)
    budget = 1500.0
    from repro.core import policies

    for r in range(5):
        if r >= 1:
            ev = _random_events(rng, sim, apps[:6], r,
                                topo_racks=[f"rack{i}" for i in range(4)])
            touched = sim.apply_events(ev)
            ctrl.invalidate(touched)
        res = sim.run_round(ctrl, budget=budget, round_index=r)
        # re-derive the same round's inputs and solve from scratch
        _, recv, _ = sim.partition_rows()
        batch = sim._receiver_batch(recv, None, False)
        by_leaf = {}
        leaf_ids = np.asarray(batch.domain_ids)
        for leaf in np.unique(leaf_ids):
            ii = np.flatnonzero(leaf_ids == leaf)
            by_leaf[int(leaf)] = mckp.collapse_receivers(
                [batch.names[i] for i in ii],
                [batch.surfaces[i] for i in ii],
                batch.baselines[ii],
                lambda surf, base: ctrl._group_table(surf, base),
            )
        extra, _, _ = sim.domain_headroom(r, recv)
        root = policies.domain_tree(topo, extra, by_leaf)
        fresh = mckp.solve_hierarchical(root, budget)
        got = {nm: pick[2] for nm, pick in fresh.picks.items()}
        assert dict(res.allocation.caps) == got
        assert res.allocation.spent == fresh.spent


# ---------------------------------------------------------------------------
# LRU bounds: warm caches stay capped over long scenarios
# ---------------------------------------------------------------------------


def test_lru_cache_basics():
    c = mckp.LRUCache(3)
    for i in range(5):
        c[i] = i
    assert len(c) == 3 and 0 not in c and 4 in c
    _ = c[2]  # refresh
    c[5] = 5
    assert 2 in c and 3 not in c


def test_warm_caches_capped_over_200_rounds(suite):
    """ISSUE satellite: the hier controller's warm caches stay bounded
    across 200 rounds of distinct budgets and drifting digests."""
    system, apps, surfs = suite
    n = 24
    topo = PowerTopology.uniform_racks(n, 3, rack_cap=5000.0)
    sim = ClusterSim.build(
        system, apps[:6], surfs, n_nodes=n, seed=0,
        initial_caps=(150.0, 150.0), topology=topo,
    )
    ctrl = make_controller("ecoshift_hier", system)
    rng = np.random.default_rng(0)
    for r in range(200):
        if r % 3 == 1:
            victims = rng.choice(
                sim.table.node_ids[sim.table.alive], size=2, replace=False
            )
            ev = [
                sc.StragglerOnset(
                    round=r, node_id=int(v),
                    slowdown=float(rng.uniform(1.0, 2.0)),
                )
                for v in victims
            ]
            touched = sim.apply_events(ev)
            ctrl.invalidate(touched)
        budget = float(rng.integers(4, 60)) * 25.0  # drifting budgets
        sim.run_round(ctrl, budget=budget, round_index=r)
    assert len(ctrl._agg_curves) <= ctrl.MAX_AGG_CURVES
    assert len(ctrl._chain_cache) <= 512
    assert len(ctrl._pick_cache) <= ctrl.MAX_PICKS
    assert len(ctrl._plan_cache) <= ctrl.MAX_PLANS
    assert len(ctrl._alloc_cache) <= ctrl.MAX_ALLOCATIONS
    assert len(ctrl._frontiers) <= ctrl.MAX_FRONTIERS
    assert len(ctrl._group_tables) <= ctrl.MAX_GROUP_TABLES
    sizes = ctrl._hier_state.cache_sizes()
    assert sizes["combines"] <= ctrl.MAX_FRONTIERS
    assert sizes["leaf_solutions"] <= 128


def test_incremental_zero_churn_reuses_allocation(suite):
    """Event-free steady state returns the cached Allocation object."""
    system, apps, surfs = suite
    n = 30
    topo = PowerTopology.uniform_racks(n, 3, rack_cap=6000.0)
    sim = ClusterSim.build(
        system, apps[:6], surfs, n_nodes=n, seed=0, topology=topo,
    )
    ctrl = make_controller("ecoshift_hier", system)
    r0 = sim.run_round(ctrl, budget=900.0, round_index=0)
    r1 = sim.run_round(ctrl, budget=900.0, round_index=1)
    assert r1.allocation is r0.allocation
    # flat path too
    sim_f = ClusterSim.build(system, apps[:6], surfs, n_nodes=n, seed=0)
    ctrl_f = make_controller("ecoshift", system)
    f0 = sim_f.run_round(ctrl_f, budget=900.0, round_index=0)
    f1 = sim_f.run_round(ctrl_f, budget=900.0, round_index=1)
    assert f1.allocation is f0.allocation


# ---------------------------------------------------------------------------
# Device-resident fused round (DESIGN.md §14)
# ---------------------------------------------------------------------------


def _run_fused_parity(system, apps, surfs, seed, *, hier: bool, churn: float):
    """Fused controller vs PR-5 host incremental controller, bit-for-bit,
    under a churn-scaled random event storm.

    The budget drifts -25 W/round so event-free rounds still pay a real
    solve (the whole-solution cache misses), exercising the fused
    pipeline rather than the allocation cache.  Returns the fused
    controller so callers can inspect its round counters.
    """
    rng = np.random.default_rng(seed)
    n = 48
    if hier:
        policy = "ecoshift_hier"
        racks = [f"rack{i}" for i in range(4)]
    else:
        policy, racks = "ecoshift", None
    pair = []
    for kw in (dict(fused=True), {}):
        sim = ClusterSim.build(
            system, apps, surfs, n_nodes=n, seed=0,
            initial_caps=(150.0, 150.0),
            topology=(
                PowerTopology.uniform_racks(n, 4, rack_cap=7000.0)
                if hier else None
            ),
        )
        ctrl = make_controller(policy, system, **kw)
        pair.append((sim, ctrl))
    k = int(np.ceil(n * churn))
    for r in range(6):
        events = (
            _random_events(rng, pair[0][0], apps, r, k=k, topo_racks=racks)
            if churn > 0 and r >= 1 else []
        )
        budget = 1800.0 - 25.0 * r
        allocs = []
        for sim, ctrl in pair:
            if events:
                touched = sim.apply_events(events)
                ctrl.invalidate(touched)
            res = sim.run_round(ctrl, budget=budget, round_index=r)
            allocs.append(res.allocation)
        a, b = allocs
        assert dict(a.caps) == dict(b.caps), (
            f"seed {seed} churn {churn} round {r}: fused != host"
        )
        assert a.spent == b.spent
    return pair[0][1]


@pytest.mark.parametrize("churn", [0.0, 0.01, 0.10])
@pytest.mark.parametrize("seed", range(3))
def test_fused_flat_parity(suite, churn, seed):
    system, apps, surfs = suite
    ctrl = _run_fused_parity(
        system, apps[:8], surfs, seed, hier=False, churn=churn
    )
    stats = ctrl.fused_stats()
    assert stats.attempts > 0
    # structure churn is a fused fast path now (DESIGN.md §17): every
    # attempted round stays on device at every churn level
    assert stats.fallbacks == 0
    assert stats.rebuilds == 1  # cold start only


@pytest.mark.parametrize("churn", [0.0, 0.01, 0.10])
@pytest.mark.parametrize("seed", range(3))
def test_fused_hier_parity(suite, churn, seed):
    system, apps, surfs = suite
    ctrl = _run_fused_parity(
        system, apps[:8], surfs, seed, hier=True, churn=churn
    )
    stats = ctrl.fused_stats()
    assert stats.attempts > 0
    assert stats.fallbacks == 0
    assert stats.rebuilds == 1


@hypothesis.given(seed=st.integers(0, 2**31 - 1))
@hypothesis.settings(max_examples=8, deadline=None)
def test_fused_parity_property(seed):
    system = types.SYSTEM_1
    apps, surfs = surfaces.build_paper_suite(system)
    _run_fused_parity(
        system, apps[:6], surfs, seed, hier=(seed % 2 == 0), churn=0.10
    )


@pytest.mark.parametrize("hier", [False, True])
def test_fused_structure_change_stays_fused(suite, hier):
    """A mid-run class-layout change is served fused *in the same round*
    (DESIGN.md §17): no host fallback, parity maintained throughout, and
    the churn is visible only as row uploads against the resident banks."""
    system, apps, surfs = suite
    n = 40
    policy = "ecoshift_hier" if hier else "ecoshift"
    pair = []
    for kw in (dict(fused=True), {}):
        sim = ClusterSim.build(
            system, apps[:6], surfs, n_nodes=n, seed=0,
            initial_caps=(150.0, 150.0),
            topology=(
                PowerTopology.uniform_racks(n, 4, rack_cap=7000.0)
                if hier else None
            ),
        )
        ctrl = make_controller(policy, system, **kw)
        pair.append((sim, ctrl))
    fused_sim, fused_ctrl = pair[0]

    def round_(r, events=()):
        allocs = []
        for sim, ctrl in pair:
            if events:
                touched = sim.apply_events(list(events))
                ctrl.invalidate(touched)
            res = sim.run_round(
                ctrl, budget=1500.0 - 25.0 * r, round_index=r
            )
            allocs.append(res.allocation)
        a, b = allocs
        assert dict(a.caps) == dict(b.caps) and a.spent == b.spent, (
            f"round {r}: fused != host"
        )

    round_(0)
    round_(1)
    assert fused_ctrl.last_solver == "fused"
    stats_before = fused_ctrl.fused_stats()
    assert stats_before.rebuilds == 1  # the cold start, nothing else
    # vaporize one whole receiver behaviour class: its digest vanishes
    # from the class layout — historically a structure_change host
    # fallback, now pure row content patched under the same bank layout
    t = fused_sim.table
    _, recv, _ = fused_sim.partition_rows()
    gids = t.base_gid[recv]
    smallest = min(set(gids.tolist()), key=lambda g: (gids == g).sum())
    doomed = tuple(
        int(t.node_ids[i]) for i in recv[gids == smallest]
    )
    round_(2, events=[sc.NodeFailure(round=2, node_ids=doomed)])
    assert fused_ctrl.last_solver == "fused"
    stats_after = fused_ctrl.fused_stats()
    assert stats_after.fallbacks == stats_before.fallbacks
    assert stats_after.rebuilds == 1  # still only the cold start
    assert stats_after.row_uploads > stats_before.row_uploads
    assert 0.0 < stats_after.slack_utilization <= 1.0
    round_(3)
    assert fused_ctrl.last_solver == "fused"
    round_(4)
    assert fused_ctrl.last_solver == "fused"


def _toy_groups(n_classes, *, k=3, prefix="cls", cost0=25.0):
    """n behaviour classes of one member each, lattice-friendly costs."""
    out = []
    for g in range(n_classes):
        costs = cost0 * np.arange(1, k + 1) + 25.0 * g
        values = np.linspace(0.05, 0.4, k) + 0.01 * g
        caps = np.stack([100.0 + costs, np.full(k, 100.0)], axis=-1)
        table = curves.OptionTable(
            name=f"{prefix}{g}",
            costs=np.concatenate([[0.0], costs]),
            values=np.concatenate([[0.0], values]),
            caps=np.concatenate([[[100.0, 100.0]], caps], axis=0),
        )
        out.append(
            mckp.GroupedOptions(table=table, members=(f"{prefix}{g}n0",))
        )
    return out


def _fused_vs_host(groups, budget, fstate):
    sol = mckp.solve_grouped_fused(groups, budget, fstate=fstate)
    assert sol is not None
    ref = mckp.solve_sparse_grouped(groups, budget)
    assert sol.picks == ref.picks
    assert sol.spent == ref.spent and sol.total_value == ref.total_value
    return sol


def test_fused_compaction_on_slack_exhaustion():
    """Growing the class count past the padded stage tier triggers a
    device-side compaction — not a host rebuild, not a fallback — and the
    compacted solve stays bit-for-bit with the host solver."""
    fstate = mckp.FusedState()
    _fused_vs_host(_toy_groups(2), 900.0, fstate)
    assert fstate.stats["rebuilds"] == 1
    assert fstate.stats["compactions"] == 0
    # 2 classes fit the s_pad=8 tier; 11 classes exhaust it -> repack
    _fused_vs_host(_toy_groups(11), 900.0, fstate)
    assert fstate.stats["rebuilds"] == 1  # still only the cold start
    assert fstate.stats["compactions"] == 1
    assert fstate.stats["fallbacks"] == 0
    # shrinking back stays under the sticky (never-shrinking) tier: the
    # vacated rows mask to identity via delta patch, no second compaction
    _fused_vs_host(_toy_groups(3), 900.0, fstate)
    assert fstate.stats["compactions"] == 1
    assert fstate.stats["fallbacks"] == 0
    assert 0.0 < fstate.stats["slack_utilization"] <= 1.0


def test_fused_off_lattice_fallback_and_resume():
    """A cap-key that does not round-trip through the micro-watt lattice
    pins ``fallback_reason='off_lattice'``; the next clean round resumes
    fused against the same warm state."""
    fstate = mckp.FusedState()
    good = _toy_groups(2)
    _fused_vs_host(good, 900.0, fstate)
    n0 = fstate.stats["fallbacks"]
    # float64 micro-watt round-trip fails for this magnitude: the curve
    # key is off-lattice, so the fused path must hand the round to host
    bad_cost = 175111078930.00565
    bad = good + _toy_groups(1, prefix="bad", cost0=bad_cost)
    sol = mckp.solve_grouped_fused(bad, 2.0 * bad_cost, fstate=fstate)
    assert sol is None
    assert fstate.stats["fallback_reason"] == "off_lattice"
    assert fstate.stats["fallbacks"] == n0 + 1
    resumed = _fused_vs_host(good, 900.0, fstate)
    assert resumed is not None
    assert fstate.stats["fallback_reason"] == ""
    assert fstate.stats["fallbacks"] == n0 + 1


def test_fused_grid_overflow_fallback_and_resume():
    """Near-identical costs collapse the lattice pitch to ~1 uW, blowing
    the device grid bound: ``fallback_reason='grid_overflow'``, then the
    next clean round resumes fused."""
    fstate = mckp.FusedState()
    good = _toy_groups(2)
    _fused_vs_host(good, 900.0, fstate)
    n0 = fstate.stats["fallbacks"]
    costs = np.array([25.0, 25.000001])  # gcd pitch: 1 micro-watt
    table = curves.OptionTable(
        name="dense",
        costs=np.concatenate([[0.0], costs]),
        values=np.array([0.0, 0.1, 0.2]),
        caps=np.concatenate(
            [[[100.0, 100.0]], np.stack([100.0 + costs, 100.0 + 0 * costs], axis=-1)],
            axis=0,
        ),
    )
    bad = [mckp.GroupedOptions(table=table, members=("densen0",))]
    sol = mckp.solve_grouped_fused(bad, 100.0, fstate=fstate)
    assert sol is None
    assert fstate.stats["fallback_reason"] == "grid_overflow"
    assert fstate.stats["fallbacks"] == n0 + 1
    _fused_vs_host(good, 900.0, fstate)
    assert fstate.stats["fallback_reason"] == ""
    assert fstate.stats["fallbacks"] == n0 + 1


# ---------------------------------------------------------------------------
# DeviceView: device-resident NodeTable columns
# ---------------------------------------------------------------------------


class TestDeviceView:
    def test_patch_equals_rebuild(self, suite):
        """Steady-state dirty-row patches produce the same device arrays
        as a cold full upload."""
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=24, seed=0)
        view = sim.table.device_view()
        full0 = view.uploads_full
        sim.apply_events([
            sc.StragglerOnset(round=1, node_id=3, slowdown=1.5),
            sc.NodeFailure(round=1, node_ids=(7,)),
        ])
        view = sim.table.device_view()
        assert view.uploads_full == full0  # patched, not rebuilt
        assert view.uploads_rows >= 2
        for col in ("caps", "alive", "slowdown", "domain_id"):
            np.testing.assert_array_equal(
                np.asarray(getattr(view, col)),
                np.asarray(getattr(sim.table, col)),
            )

    def test_growth_extends_on_device(self, suite):
        """Arrivals no longer force a full host re-upload: the resident
        prefix is reused on device and only the appended tail uploads."""
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=16, seed=0)
        view = sim.table.device_view()
        full0, rows0 = view.uploads_full, view.uploads_rows
        sim.apply_events([
            sc.NodeArrival(round=1, app=apps[0], caps=(150.0, 150.0)),
        ])
        view = sim.table.device_view()
        assert view.uploads_full == full0  # extended, not rebuilt
        assert view.extends == 1
        assert view.uploads_rows >= rows0 + 1
        assert len(np.asarray(view.alive)) == len(sim.table)
        for col in ("caps", "alive", "slowdown", "domain_id"):
            np.testing.assert_array_equal(
                np.asarray(getattr(view, col)),
                np.asarray(getattr(sim.table, col)),
            )
        # growth mixed with same-round mutations of resident rows: the
        # below-prefix dirty rows scatter, the tail extends, still exact
        sim.apply_events([
            sc.NodeArrival(round=2, app=apps[1], caps=(150.0, 150.0)),
            sc.StragglerOnset(round=2, node_id=3, slowdown=1.7),
        ])
        view = sim.table.device_view()
        assert view.uploads_full == full0
        assert view.extends == 2
        for col in ("caps", "alive", "slowdown", "domain_id"):
            np.testing.assert_array_equal(
                np.asarray(getattr(view, col)),
                np.asarray(getattr(sim.table, col)),
            )

    def test_noop_when_clean(self, suite):
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=8, seed=0)
        v1 = sim.table.device_view()
        caps_before = v1.caps
        v2 = sim.table.device_view()
        assert v2 is v1 and v2.caps is caps_before

    def test_float64_residency(self, suite):
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=8, seed=0)
        view = sim.table.device_view()
        assert str(view.caps.dtype) == "float64"
        assert str(view.slowdown.dtype) == "float64"

    def test_float32_residency_on_chip_dtype(self, suite, monkeypatch):
        """With the TPU's device dtype the float columns mirror the table
        in float32 — through the full upload, the extend and the patch."""
        from repro.kernels import ops

        monkeypatch.setattr(ops, "device_value_dtype", lambda: np.float32)
        system, apps, surfs = suite
        sim = ClusterSim.build(system, apps, surfs, n_nodes=8, seed=0)
        sim.table.device_view()
        sim.apply_events([
            sc.NodeArrival(round=1, app=apps[0], caps=(150.0, 150.0)),
            sc.StragglerOnset(round=1, node_id=3, slowdown=1.3),
        ])
        view = sim.table.device_view()
        assert view.extends == 1
        for col in ("caps", "slowdown"):
            got = getattr(view, col)
            assert str(got.dtype) == "float32"
            np.testing.assert_array_equal(
                np.asarray(got), getattr(sim.table, col).astype(np.float32)
            )
        assert str(view.domain_id.dtype) == "int32"


# ---------------------------------------------------------------------------
# Allocation assembly from the batch's row arrays (DESIGN.md §11.5)
# ---------------------------------------------------------------------------


def _name_keyed_allocation(sol, names, baselines, budget, grid):
    """The Allocation as assembled by name: caps in the picks' own order,
    validated against a name -> baseline mapping."""
    alloc = types.Allocation(
        caps={nm: pick[2] for nm, pick in sol.picks.items()},
        spent=sol.spent,
        predicted_improvement=sol.average_improvement(),
    )
    by_name = dict(zip(names, map(tuple, np.asarray(baselines).tolist())))
    types.validate_allocation(alloc, by_name, budget, grid)
    return alloc


def _alloc_bits(alloc):
    return (
        sorted(
            (nm, float(c).hex(), float(g).hex())
            for nm, (c, g) in alloc.caps.items()
        ),
        float(alloc.spent).hex(),
        float(alloc.predicted_improvement).hex(),
    )


@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize(
    "policy,kw,nack",
    [
        ("ecoshift", {}, False),
        ("ecoshift_hier", {}, False),
        ("ecoshift", {}, True),
        ("ecoshift_hier", {}, True),
        ("ecoshift", {"fused": True}, False),
        ("ecoshift_hier", {"fused": True}, False),
    ],
    ids=["grouped", "hier", "pinned", "pinned_hier", "fused", "fused_hier"],
)
def test_allocation_from_batch_rows_matches_name_keyed(
    suite, monkeypatch, policy, kw, nack, seed
):
    """Every controller path assembles from the batch's row arrays the
    Allocation that the name-keyed construction gives, bit for bit, with
    the caps in the batch's row order; under random budgets, stragglers,
    phase changes and failures."""
    from repro.cluster.faults import ActuationNack

    system, apps, surfs = suite
    rng = np.random.default_rng(seed)
    assemble = policies.allocation_from_solution
    sizes = []

    def checked(sol, names, baselines, budget, grid):
        alloc = assemble(sol, names, baselines, budget, grid)
        ref = _name_keyed_allocation(sol, names, baselines, budget, grid)
        assert _alloc_bits(alloc) == _alloc_bits(ref)
        assert list(alloc.caps) == list(names)
        sizes.append(len(names))
        return alloc

    monkeypatch.setattr(policies, "allocation_from_solution", checked)
    n = int(rng.integers(32, 64))
    hier = policy == "ecoshift_hier"
    sim = ClusterSim.build(
        system, apps[:8], surfs, n_nodes=n, seed=int(rng.integers(1000)),
        initial_caps=(150.0, 150.0),
        topology=(
            PowerTopology.uniform_racks(n, 4, rack_cap=7000.0) if hier else None
        ),
    )
    rounds = 6
    budgets = [float(b) * 25.0 for b in rng.integers(36, 72, rounds)]
    victims = iter(rng.permutation(n).tolist())
    events = []
    for r in range(1, rounds):
        events += [
            sc.StragglerOnset(
                round=r, node_id=next(victims),
                slowdown=float(rng.choice([1.4, 1.9])),
            ),
            sc.PhaseChange(
                round=r, node_id=next(victims),
                surface_id=apps[int(rng.integers(8))].name,
            ),
            sc.NodeFailure(round=r, node_ids=(next(victims),)),
        ]
    scen = sc.Scenario(rounds, budget=budgets).with_events(events)
    if nack:
        scen = scen.with_faults(
            [ActuationNack(round=1, fraction=0.3, seed=seed)]
        )
    ctrl = make_controller(policy, system, **kw)
    pinned = []
    solve_pinned = ctrl._solve_pinned
    ctrl._solve_pinned = lambda *a, **k: pinned.append(1) or solve_pinned(*a, **k)
    sim.run(scen, ctrl)
    assert sizes
    assert bool(pinned) == nack
    if kw.get("fused"):
        assert ctrl.fused_stats().rounds > 0
