"""The benchmark's traffic generator and deployment model, on the CPU."""

import numpy as np
import pytest

from bench import suite
from bench.cluster import Deployment, initial_state, load_json
from bench.tests import mix_json
from bench.traffic import STREAM_POPULATION, Traffic, rng_for

SEED = 2**31 + 12345  # seeds may exceed 32 signed bits


def _traffic(config: str, mix: str, seed: int = SEED):
    dep = Deployment(load_json(config))
    state = initial_state(dep, rng_for(seed, STREAM_POPULATION))
    dep.set_domain_caps(state)
    return dep, Traffic(dep, mix_json(mix), seed, state)


@pytest.mark.parametrize("mix", ["churn10", "drift"])
def test_same_seed_same_traffic(mix):
    rounds = []
    for _ in range(2):
        _dep, tr = _traffic("tests/data/tiny_rack4.json", mix)
        rounds.append([tr.next_round() for _ in range(20)])
    assert rounds[0] == rounds[1]
    _dep, other = _traffic("tests/data/tiny_rack4.json", mix, seed=SEED + 1)
    assert [other.next_round() for _ in range(20)] != rounds[0]


@pytest.mark.parametrize("config,mix", [
    ("configs/sys1_10k_rack16.json", "churn10"),
    ("tests/data/tiny_rack4.json", "churn10"),
])
def test_churn_keeps_population_and_headroom(config, mix):
    dep, tr = _traffic(config, mix)
    st = tr.state
    alive0 = int(st.alive.sum())
    per_leaf0 = np.bincount(st.leaf[st.alive], minlength=len(dep.tree.leaf_ids))
    committed0 = dep.committed_by_leaf(st)
    recv0 = int((st.alive & ~dep.donor_app(st.app)).sum())
    kinds = set()
    for _ in range(15):
        _r, _b, ev = tr.next_round()
        kinds |= {e[0] for e in ev}
        assert int(st.alive.sum()) == alive0
        per_leaf = np.bincount(st.leaf[st.alive], minlength=len(dep.tree.leaf_ids))
        np.testing.assert_array_equal(per_leaf, per_leaf0)
        np.testing.assert_allclose(dep.committed_by_leaf(st), committed0, rtol=0, atol=1e-6)
        assert int((st.alive & ~dep.donor_app(st.app)).sum()) == recv0
    assert kinds == {"straggler", "phase", "failure", "arrival"}


@pytest.mark.parametrize("config", [
    "configs/sys1_10k_rack16.json", "tests/data/tiny_rack4.json",
])
def test_budgets_lie_in_the_envelope_and_move(config):
    dep, tr = _traffic(config, "drift")
    lo, hi = dep.envelope
    budgets = [tr.budget(r) for r in range(400)]
    assert all(lo <= b <= hi for b in budgets)
    assert len(set(budgets)) == len(budgets)  # no two rounds share a budget
    assert max(budgets) - min(budgets) > 0.5 * (hi - lo)


def test_churn10_event_counts():
    dep, tr = _traffic("configs/sys1_10k_rack16.json", "churn10")
    _r, _b, ev = tr.next_round()
    kinds = [e[0] for e in ev]
    assert kinds.count("failure") == kinds.count("arrival") == 25
    assert kinds.count("phase") == 375
    assert kinds.count("straggler") == 600


def test_suite_copy_matches_the_program():
    """The benchmark's copy of the paper suite draws the same surfaces
    as the program's own generator."""
    from repro.core import surfaces, types

    apps, surfs = surfaces.build_paper_suite(types.SYSTEMS["system1-a100"])
    dep = Deployment(load_json("configs/sys1_10k_rack16.json"))
    cl = np.arange(100.0, 401.0, 25.0)
    cc, gg = np.meshgrid(cl, cl, indexing="ij")
    for a in apps:
        p = dep.params[a.name]
        assert p["sclass"] == a.sclass
        np.testing.assert_array_equal(suite.runtime(p, cc, gg), surfs[a.name].runtime(cc, gg))
        assert p["natural"] == tuple(float(x) for x in surfs[a.name].power_draw(1e9, 1e9))


def test_topology_matches_the_config():
    """A four-level tree (site, 4 rows, 20 PDUs, 100 chassis) at 100,000
    nodes: leaves tile the node ids, caps add each level's headroom."""
    cfg = load_json("configs/sys1_10k_rack16.json")
    cfg = {**cfg, "n_nodes": 100000, "topology": {
        "fanouts": [4, 5, 5], "level_names": ["row", "pdu", "chassis"],
        "level_fracs": [0.9, 0.75, 0.6]}}
    dep = Deployment(cfg)
    tree = dep.tree
    assert len(tree.names) == 1 + 4 + 20 + 100
    assert len(tree.leaf_ids) == 100
    assert tree.leaf_ranges[0] == (0, 1000) and tree.leaf_ranges[-1] == (99000, 100000)
    state = initial_state(dep, rng_for(SEED, STREAM_POPULATION))
    caps = dep.set_domain_caps(state)
    committed = tree.aggregate(dep.committed_by_leaf(state))
    extra = caps - committed
    np.testing.assert_allclose(extra[tree.leaf_ids], 0.6 * 1e6 * 1000 / 100000, atol=1e-6)
    pdus = np.flatnonzero(tree.depth == 2)
    np.testing.assert_allclose(extra[pdus], 0.75 * 1e6 * 5000 / 100000, atol=1e-6)
    assert caps[0] == 1e18
