"""The plain reference, its control, and the program against it (CPU).

On the CPU the fused round keeps float64 values, so the program's answer
must match the reference's optimum to rounding; the reference put in the
program's place in bfloat16 (the control) must fail the comparison."""

import numpy as np
import pytest

from bench import harness, reference
from bench.cluster import Deployment, initial_state, load_json
from bench.tests import mix_json
from bench.traffic import STREAM_POPULATION, Traffic, rng_for

LIMITS = load_json("limits/rack16_drift.json")
CHECKS = harness.CHECKS


def _rounds(config: str, mix: str, seed: int, n: int):
    dep = Deployment(load_json(config))
    state = initial_state(dep, rng_for(seed, STREAM_POPULATION))
    dep.set_domain_caps(state)
    tr = Traffic(dep, mix_json(mix), seed, state)
    for _ in range(n):
        _r, budget, _ev = tr.next_round()
        yield dep, reference.Round(dep, state.copy(), budget)


def _fails(got: dict) -> bool:
    return any(got[k] > LIMITS[k] for k in CHECKS)


@pytest.mark.parametrize("seed", [3, 2**31 + 7, 99])
def test_reference_answer_passes_and_control_fails(seed):
    """The reference's own float64 answer passes every limit; the
    control (the same DP in bfloat16) fails one in every round."""
    for dep, rnd in _rounds("tests/data/tiny_rack4.json", "churn10", seed, 6):
        curves = reference.option_curves(dep)
        opt, units = reference.solve(rnd, curves)
        got = reference.check_round(rnd, curves, reference.caps_from_units(rnd, curves, units), opt)
        assert not _fails(got), got
        assert got["spent_w"] <= rnd.budget + 1e-9
        ctl = reference.check_round(rnd, curves, *reference.control_answer(rnd, curves))
        assert _fails(ctl), ctl


def test_dp_matches_brute_force():
    """On a 12-node, 3-rack instance the DP's optimum is the best of
    every feasible assignment."""
    import itertools

    cfg = load_json("tests/data/tiny_rack4.json")
    cfg = {**cfg, "n_nodes": 12,
           "topology": {**cfg["topology"], "fanouts": [3], "level_names": ["rack"]},
           "budget": {"w_per_node": 100.0 / 12, "floor_frac": 0.5}}
    dep = Deployment(cfg)
    state = initial_state(dep, rng_for(5, STREAM_POPULATION))
    dep.domain_caps = np.array([1e18] + [0.0] * 3)
    dep.domain_caps[1:] = dep.committed_by_leaf(state) + 50.0
    curves = reference.option_curves(dep)
    rnd = reference.Round(dep, state, 100.0)
    opt, units = reference.solve(rnd, curves)
    w = curves[0][state.app[rnd.recv]]
    leaf = state.leaf[rnd.recv]
    best = -np.inf
    choices = [np.flatnonzero(np.isfinite(w[i]) & (np.arange(w.shape[1]) <= 2))
               for i in range(len(rnd.recv))]
    for pick in itertools.product(*choices):
        pick = np.asarray(pick)
        if pick.sum() > rnd.units[0] or any(
            pick[leaf == k].sum() > rnd.units[dep.tree.leaf_ids[k]] for k in range(3)
        ):
            continue
        best = max(best, float(w[np.arange(len(pick)), pick].sum()))
    assert opt == pytest.approx(best, rel=1e-12)
    assert float(w[np.arange(len(units)), units].sum()) == pytest.approx(opt, rel=1e-12)


def test_program_round_matches_reference():
    """The program's hierarchical host DP agrees with the reference on
    the same inputs (rounds of the tiny deployment under churn)."""
    from bench import deploy

    dep = Deployment(load_json("tests/data/tiny_rack4.json"))
    state = initial_state(dep, rng_for(11, STREAM_POPULATION))
    dep.set_domain_caps(state)
    tr = Traffic(dep, mix_json("churn10"), 11, state)
    sim, _ctrl, by_name = deploy.build(dep, state.copy(), 11)
    from repro.cluster.controller import make_controller

    ctrl = make_controller("ecoshift_hier", deploy.system_spec(dep))
    leaves = deploy.leaf_names(dep)
    curves = reference.option_curves(dep)
    for _ in range(5):
        r, budget, ev = tr.next_round()
        touched = sim.apply_events(deploy.program_events(ev, r, by_name, dep, leaves))
        ctrl.invalidate(touched)
        alloc = sim.run_round(ctrl, budget=budget, round_index=r).allocation
        got = reference.check_round(
            reference.Round(dep, tr.state.copy(), budget), curves, alloc.caps,
            alloc.predicted_improvement * len(alloc.caps),
        )
        assert not _fails(got), got
        assert got["value_err_rel"] < 1e-12
