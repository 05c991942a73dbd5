"""BudgetProvider API certification (DESIGN.md §15).

Covers: provider semantics (constant / trace replay / composition /
step overrides), the ``as_provider`` shim and ``with_budget``
deprecation path, the ``OverrideBook`` round-aware ``DomainCapChange``
routing (including the same-round precedence + float-handling bugfix),
the shipped day-scale fixtures, and the ``ControllerConfig`` alias
contract (legacy kwargs == config, explicit kwarg beats config).
"""

import dataclasses
import warnings

import numpy as np
import pytest

from repro.cluster import ClusterSim, PowerTopology, scenario as sc
from repro.cluster import budget as bm
from repro.cluster.controller import (
    ControllerConfig,
    EcoShiftController,
    EcoShiftHierController,
    EcoShiftOnlineController,
    OracleController,
    make_controller,
)
from repro.core import surfaces, types


@pytest.fixture(scope="module")
def suite():
    system = types.SYSTEM_1
    apps, surfs = surfaces.build_paper_suite(system)
    return system, apps, surfs


# ---------------------------------------------------------------------------
# Provider semantics
# ---------------------------------------------------------------------------


class TestProviders:
    def test_constant(self):
        p = bm.ConstantProvider(150.0)
        assert p.budget_at(0) == 150.0
        assert p.budget_at(10**6) == 150.0
        assert p.forecast(3, 4) == (150.0, 150.0, 150.0, 150.0)
        assert bm.ConstantProvider(None).budget_at(0) is None

    def test_trace_scalar_and_sequence(self):
        assert bm.TraceReplayProvider(42).budget_at(7) == 42.0
        p = bm.TraceReplayProvider([10.0, 20.0, 30.0])
        assert [p.budget_at(r) for r in range(5)] == [10.0, 20.0, 30.0, 30.0, 30.0]
        # hold-last shows up in the forecast too
        assert p.forecast(1, 3) == (20.0, 30.0, 30.0)

    def test_trace_empty_and_callable(self):
        assert bm.TraceReplayProvider([]).budget_at(0) is None
        p = bm.TraceReplayProvider(lambda r: 100.0 + r)
        assert p.budget_at(5) == 105.0
        assert p.forecast(0, 3) == (100.0, 101.0, 102.0)

    def test_trace_rejects_junk(self):
        with pytest.raises(TypeError):
            bm.TraceReplayProvider(object())

    def test_trace_unwraps_nested(self):
        inner = bm.TraceReplayProvider([1.0, 2.0])
        outer = bm.TraceReplayProvider(inner)
        assert outer.trace == [1.0, 2.0]

    def test_scaled(self):
        p = bm.ScaledProvider([100.0, 200.0], 0.5)
        assert p.budget_at(0) == 50.0
        assert p.budget_at(1) == 100.0
        assert bm.ScaledProvider(None, 0.5).budget_at(0) is None

    def test_min_composition(self):
        p = bm.MinProvider([100.0, 300.0], bm.ConstantProvider(200.0))
        assert p.budget_at(0) == 100.0
        assert p.budget_at(1) == 200.0
        # unset members are ignored; all-unset rounds stay None
        q = bm.MinProvider(bm.ConstantProvider(None), 50.0)
        assert q.budget_at(0) == 50.0
        assert bm.MinProvider(None, None).budget_at(0) is None
        with pytest.raises(ValueError):
            bm.MinProvider()

    def test_composition_sugar(self):
        p = bm.ConstantProvider(100.0).scaled(0.5).min_with(40.0)
        assert p.budget_at(0) == 40.0
        q = bm.ConstantProvider(100.0).scaled(0.3)
        assert q.budget_at(0) == pytest.approx(30.0)

    def test_step_override_from_round_on(self):
        p = bm.StepOverrideProvider(100.0, [(3, 60.0)])
        assert [p.budget_at(r) for r in range(5)] == [100.0, 100.0, 100.0, 60.0, 60.0]
        # latest applicable step wins
        q = bm.StepOverrideProvider(100.0, [(2, 80.0), (4, 50.0)])
        assert q.budget_at(3) == 80.0
        assert q.budget_at(4) == 50.0

    def test_as_provider_shim(self):
        assert bm.as_provider(None) is None
        p = bm.ConstantProvider(1.0)
        assert bm.as_provider(p) is p  # idempotent passthrough
        w = bm.as_provider([1.0, 2.0])
        assert isinstance(w, bm.TraceReplayProvider)
        assert bm.as_provider(w) is w

    def test_as_watts_numpy_scalars(self):
        # the shared coercion accepts numpy scalars and agrees with float()
        v = np.float32(123.456)
        assert bm.as_watts(v) == float(v)
        assert bm.as_watts(np.float64(7.25)) == 7.25
        assert bm.as_watts(None) is None

    def test_protocol_conformance(self):
        for p in (
            bm.ConstantProvider(1.0),
            bm.TraceReplayProvider([1.0]),
            bm.ScaledProvider(1.0, 2.0),
            bm.MinProvider(1.0),
            bm.StepOverrideProvider(1.0, ()),
        ):
            assert isinstance(p, bm.BudgetProvider)


# ---------------------------------------------------------------------------
# OverrideBook: round-aware DomainCapChange routing
# ---------------------------------------------------------------------------


class TestOverrideBook:
    def test_step_applies_from_its_round(self):
        book = bm.OverrideBook()
        book.set(2, 5, 900.0)
        assert book.active(4) == {}  # future cap not visible earlier
        assert book.active(5) == {2: 900.0}
        assert book.active(9) == {2: 900.0}

    def test_latest_step_wins(self):
        book = bm.OverrideBook()
        book.set(1, 2, 800.0)
        book.set(1, 6, 500.0)
        assert book.active(3) == {1: 800.0}
        assert book.active(6) == {1: 500.0}

    def test_numpy_cap_coerces_like_budget(self):
        # a DomainCapChange carrying a numpy scalar resolves through the
        # same as_watts as a budget-trace step — bit-identical floats
        book = bm.OverrideBook()
        cap = np.float32(333.3)
        book.set(0, 0, cap)
        assert book.active(0)[0] == bm.TraceReplayProvider([cap]).budget_at(0)

    def test_provider_for(self):
        book = bm.OverrideBook()
        book.set(3, 4, 250.0)
        p = book.provider_for(3, base=1000.0)
        assert p.budget_at(3) == 1000.0
        assert p.budget_at(4) == 250.0
        assert book.provider_for(7, base=111.0).budget_at(0) == 111.0

    def test_clear_and_bool(self):
        book = bm.OverrideBook()
        assert not book
        book.set(0, 0, 1.0)
        assert book and len(book) == 1
        book.clear()
        assert not book


# ---------------------------------------------------------------------------
# Fixtures
# ---------------------------------------------------------------------------


class TestFixtures:
    def test_shipped_fixtures_load(self):
        for name in bm.FIXTURES:
            fix = bm.load_fixture(name)
            assert len(fix["values"]) == 96  # 15-minute day
            assert all(np.isfinite(v) for v in fix["values"])

    def test_resample(self):
        t24 = bm.fixture_trace("co2_day", 24)
        t96 = bm.fixture_trace("co2_day", 96)
        assert len(t24) == 24 and len(t96) == 96
        assert t24[0] == t96[0]

    def test_solar_budget_floor(self):
        p = bm.solar_budget(1000.0, floor_watts=200.0, n_rounds=96)
        vals = [p.budget_at(r) for r in range(96)]
        assert min(vals) == 200.0  # night rounds hit the grid backstop
        assert max(vals) <= 1000.0
        assert max(vals) > 500.0  # midday actually follows the sun


# ---------------------------------------------------------------------------
# Scenario integration: shim, deprecation, precedence
# ---------------------------------------------------------------------------


class TestScenarioIntegration:
    def test_raw_trace_auto_wraps(self):
        scen = sc.Scenario(n_rounds=4, budget=[100.0, 200.0])
        assert isinstance(scen.budget, bm.TraceReplayProvider)
        assert scen.budget_at(0) == 100.0
        assert scen.budget_at(3) == 200.0  # hold-last preserved

    def test_replace_keeps_provider(self):
        scen = sc.Scenario(n_rounds=4, budget=500.0)
        p = scen.budget
        scen2 = dataclasses.replace(scen, n_rounds=8)
        assert scen2.budget is p  # as_provider idempotence across replace

    def test_with_budget_deprecated_but_equivalent(self):
        base = sc.Scenario(n_rounds=6)
        with pytest.warns(DeprecationWarning, match="with_budget_provider"):
            old = base.with_budget([10.0, 20.0, 30.0])
        new = base.with_budget_provider([10.0, 20.0, 30.0])
        assert [old.budget_at(r) for r in range(6)] == [
            new.budget_at(r) for r in range(6)
        ]

    def test_with_budget_engine_parity_bit_for_bit(self, suite):
        """The deprecation shim must be a pure alias: a full engine run
        under ``with_budget(trace)`` equals ``with_budget_provider``."""
        system, apps, surfs = suite
        trace = [900.0, 600.0, 1200.0, 750.0]

        def _run(scen):
            sim = ClusterSim.build(system, apps, surfs, n_nodes=16, seed=2)
            return sim.run(scen, make_controller("ecoshift", system))

        with pytest.warns(DeprecationWarning):
            old = _run(sc.Scenario(n_rounds=4).with_budget(trace))
        new = _run(
            sc.Scenario(n_rounds=4).with_budget_provider(
                bm.TraceReplayProvider(trace)
            )
        )
        for a, b in zip(old.records, new.records):
            assert dict(a.result.allocation.caps) == dict(
                b.result.allocation.caps
            )
            assert a.result.improvements == b.result.improvements

    def test_with_budget_provider_no_warning(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            sc.Scenario(n_rounds=2).with_budget_provider(100.0)

    def test_forecast_none_and_values(self):
        scen = sc.Scenario(n_rounds=4)
        assert scen.budget_forecast(0, 3) == (None, None, None)
        scen = scen.with_budget_provider([10.0, 20.0])
        assert scen.budget_forecast(0, 3) == (10.0, 20.0, 20.0)

    def test_carbon_aware_defaults(self):
        scen = sc.Scenario.carbon_aware(24, 3000.0)
        assert scen.carbon_at(0) is not None
        assert scen.price_at(0) is not None
        assert scen.budget_at(0) == 3000.0
        assert len(scen.carbon_forecast(0, 24)) == 24

    def test_provider_budget_runs_unchanged(self, suite):
        # a provider-built scenario is bit-for-bit a raw-trace scenario
        system, apps, surfs = suite
        trace = [3000.0 + 100.0 * (r % 3) for r in range(8)]
        res = []
        for budget in (trace, bm.TraceReplayProvider(trace)):
            sim = ClusterSim.build(system, apps[:6], surfs, n_nodes=12, seed=0)
            scen = sc.Scenario(n_rounds=8, budget=budget)
            res.append(sim.run(scen, make_controller("ecoshift", system)))
        for ra, rb in zip(res[0].records, res[1].records):
            assert ra.result.allocation.caps == rb.result.allocation.caps


class TestSameRoundPrecedence:
    """DomainCapChange vs budget-trace step on the same round.

    Contract (Scenario.budget_at docstring): round ``r``'s events apply
    before round ``r``'s budget/headroom resolution, so both take effect
    *that* round; the cap override binds from its round on and never
    earlier; both coerce through ``as_watts``.
    """

    def _run(self, suite, cap_value):
        system, apps, surfs = suite
        n = 12
        topo = PowerTopology.uniform_racks(n, 2, rack_cap=4000.0)
        k = 3
        scen = (
            sc.Scenario(
                n_rounds=6,
                budget=[3000.0] * k + [2000.0] * 3,  # budget step at round k
            )
            .with_topology(topo)
            .with_domain_cap(k, "rack0", cap_value)  # cap change, same round
        )
        sim = ClusterSim.build(system, apps[:6], surfs, n_nodes=n, seed=0)
        return sim.run(scen, make_controller("ecoshift_hier", system)), k

    def test_both_take_effect_on_shared_round(self, suite):
        res, k = self._run(suite, 2500.0)
        # before round k: neither the budget step nor the cap change
        assert res.records[k - 1].result.budget == 3000.0
        assert res.records[k - 1].domain_caps["rack0"] == 4000.0
        # at round k: both, simultaneously
        assert res.records[k].result.budget == 2000.0
        assert res.records[k].domain_caps["rack0"] == 2500.0
        # and the override persists
        assert res.records[k + 1].domain_caps["rack0"] == 2500.0

    def test_numpy_cap_value_agrees_with_float(self, suite):
        # same scenario, cap passed as np.float32: recorded cap must be
        # exactly float(np.float32(...)) — the shared as_watts coercion
        cap = np.float32(2500.7)
        res, k = self._run(suite, cap)
        assert res.records[k].domain_caps["rack0"] == float(cap)


# ---------------------------------------------------------------------------
# ControllerConfig aliases
# ---------------------------------------------------------------------------


class TestControllerConfig:
    def test_legacy_kwargs_match_config(self, suite):
        system, _, _ = suite
        a = EcoShiftController(system, horizon=3, eco_factor=0.5, fused=True)
        b = EcoShiftController(
            system,
            config=ControllerConfig(horizon=3, eco_factor=0.5, fused=True),
        )
        assert (a.horizon, a.eco_factor, a.fused) == (
            b.horizon, b.eco_factor, b.fused,
        )
        assert a.config == b.config

    def test_explicit_kwarg_beats_config(self, suite):
        system, _, _ = suite
        cfg = ControllerConfig(horizon=8, eco_factor=0.7, fused=True)
        c = EcoShiftController(system, config=cfg, horizon=4)
        assert c.horizon == 4  # kwarg wins
        assert c.eco_factor == 0.7 and c.fused is True  # config holds

    def test_defaults_are_historical(self, suite):
        system, _, _ = suite
        c = EcoShiftController(system)
        assert (c.grouped, c.incremental, c.fused) == (True, True, False)
        assert c.horizon == 1 and c.eco_factor == 1.0

    def test_hier_config_carries_topology(self, suite):
        system, _, _ = suite
        topo = PowerTopology.single_root(8, cap=1e6)
        c = EcoShiftHierController(
            system, config=ControllerConfig(topology=topo)
        )
        assert c.topology is topo

    def test_online_requires_predictor(self, suite):
        system, _, _ = suite
        with pytest.raises(ValueError, match="predictor"):
            EcoShiftOnlineController(system)

    def test_oracle_exhaustive_alias(self, suite):
        system, _, _ = suite
        a = OracleController(system, exhaustive=True)
        b = OracleController(system, config=ControllerConfig(exhaustive=True))
        assert a.exhaustive is True and b.exhaustive is True

    def test_make_controller_accepts_config(self, suite):
        system, _, _ = suite
        c = make_controller(
            "ecoshift", system, config=ControllerConfig(horizon=6, eco_factor=0.8)
        )
        assert c.horizon == 6 and c.eco_factor == 0.8
