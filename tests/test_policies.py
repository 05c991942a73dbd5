"""Policy tests: Table-2 case study, invariants, policy ordering."""

import numpy as np
import pytest

from repro.core import mckp, policies, surfaces, types
from repro.core.types import (
    Allocation,
    AppSpec,
    CapGrid,
    SystemSpec,
    validate_allocation,
    validate_caps,
)


@pytest.fixture(scope="module")
def table2():
    """Paper §6.2: cfd + raytracing at (300, 200) with 200 W reclaimed."""
    grid = CapGrid(cpu_min=200, cpu_max=500, gpu_min=100, gpu_max=500, step=50)
    system = SystemSpec(name="system2-h100", grid=grid, init_cpu=300, init_gpu=200)
    apps = [
        AppSpec("cfd", "C", "cfd"),
        AppSpec("raytracing", "G", "raytracing"),
    ]
    surfs = {
        "cfd": surfaces.cfd_surface(),
        "raytracing": surfaces.raytracing_surface(),
    }
    baselines = {"cfd": (300.0, 200.0), "raytracing": (300.0, 200.0)}
    return system, apps, surfs, baselines


def _avg_gain(alloc, surfs, baselines):
    gains = []
    for name, (c, g) in alloc.caps.items():
        gains.append(float(surfs[name].improvement(baselines[name], c, g)))
    return float(np.mean(gains))


class TestTable2CaseStudy:
    def test_policy_ordering(self, table2):
        """EcoShift > MixedAdaptive > DPS in average improvement (Table 2)."""
        system, apps, surfs, baselines = table2
        g = {}
        for pname in ("ecoshift", "dps", "mixed_adaptive"):
            alloc = policies.POLICIES[pname](apps, baselines, 200.0, system, surfs)
            g[pname] = _avg_gain(alloc, surfs, baselines)
        assert g["ecoshift"] > g["mixed_adaptive"] > g["dps"]
        # paper: 16.96 / 13.16 / 9.21 — we require the same regime
        assert g["ecoshift"] > 0.14
        assert g["dps"] < 0.13

    def test_ecoshift_respects_dominant_sensitivity(self, table2):
        """EcoShift gives cfd CPU-only watts and raytracing GPU-only watts."""
        system, apps, surfs, baselines = table2
        alloc = policies.ecoshift(apps, baselines, 200.0, system, surfs)
        c_cfd, g_cfd = alloc.caps["cfd"]
        c_rt, g_rt = alloc.caps["raytracing"]
        assert c_cfd > 300.0 and g_cfd == 200.0  # all-CPU for cfd
        assert g_rt > 200.0 and c_rt == 300.0  # all-GPU for raytracing

    def test_dps_equal_split(self, table2):
        """DPS: 200 W -> 100 W each -> (350, 250) both (paper Table 2)."""
        system, apps, surfs, baselines = table2
        alloc = policies.dps(apps, baselines, 200.0, system, surfs)
        for name in ("cfd", "raytracing"):
            np.testing.assert_allclose(alloc.caps[name], (350.0, 250.0))

    def test_ecoshift_matches_oracle_here(self, table2):
        system, apps, surfs, baselines = table2
        eco = policies.ecoshift(apps, baselines, 200.0, system, surfs)
        orc = policies.oracle(apps, baselines, 200.0, system, surfs)
        np.testing.assert_allclose(
            _avg_gain(eco, surfs, baselines), _avg_gain(orc, surfs, baselines), atol=1e-9
        )


class TestInvariants:
    @pytest.mark.parametrize("pname", ["uniform", "dps", "mixed_adaptive", "ecoshift"])
    def test_budget_and_monotonic_upgrade(self, pname):
        system = types.SYSTEM_1
        apps, surfs = surfaces.build_paper_suite(system)
        apps = apps[:12]
        surfs = {a.name: surfs[a.name] for a in apps}
        baselines = {a.name: (system.init_cpu, system.init_gpu) for a in apps}
        for budget in (0.0, 300.0, 1500.0):
            alloc = policies.POLICIES[pname](apps, baselines, budget, system, surfs)
            validate_allocation(alloc, baselines, budget, system.grid)

    def test_dps_fair_share_exact(self):
        """No clamping -> every receiver gets exactly B/N split 50/50."""
        system = types.SYSTEM_2
        apps = [AppSpec(f"a{i}", "B", f"a{i}") for i in range(4)]
        baselines = {a.name: (250.0, 150.0) for a in apps}
        alloc = policies.dps(apps, baselines, 400.0, system, None)
        for a in apps:
            np.testing.assert_allclose(alloc.caps[a.name], (300.0, 200.0))

    def test_mixed_adaptive_proportional(self):
        """Allocations proportional to component demand (no clamps)."""
        system = types.SYSTEM_2
        apps = [AppSpec("hi", "B", "hi"), AppSpec("lo", "B", "lo")]
        baselines = {"hi": (250.0, 150.0), "lo": (250.0, 150.0)}
        surfs = {
            "hi": surfaces.AnalyticSurface(
                host_work=1,
                dev_work=1,
                phi_h=surfaces.SpeedCurve(100, 100),
                phi_d=surfaces.SpeedCurve(100, 100),
                natural_cpu=400.0,  # demand 150
                natural_gpu=150.0,  # demand 0
            ),
            "lo": surfaces.AnalyticSurface(
                host_work=1,
                dev_work=1,
                phi_h=surfaces.SpeedCurve(100, 100),
                phi_d=surfaces.SpeedCurve(100, 100),
                natural_cpu=250.0,  # demand 0
                natural_gpu=200.0,  # demand 50
            ),
        }
        alloc = policies.mixed_adaptive(apps, baselines, 100.0, system, surfs)
        # proportional: hi gets 75 CPU, lo gets 25 GPU
        np.testing.assert_allclose(alloc.caps["hi"], (325.0, 150.0))
        np.testing.assert_allclose(alloc.caps["lo"], (250.0, 175.0))

    @pytest.mark.parametrize(
        "caps,budget,text",
        [
            ({"x": (120.0, 150.0)}, 100, r"x: caps \(120.0,150.0\) below baseline"),
            ({"x": (425.0, 150.0)}, 1000, "x: cpu cap 425.0 outside grid"),
            ({"x": (140.0, 425.0)}, 1000, "x: gpu cap 425.0 outside grid"),
            ({"x": (240.0, 150.0)}, 50, "allocation spends 100.0 W > budget 50 W"),
            ({}, -1, "allocation spends 0.0 W > budget -1 W"),
        ],
        ids=["below_baseline", "cpu_off_grid", "gpu_off_grid", "over_budget", "empty"],
    )
    def test_validate_allocation_rejects_bad(self, caps, budget, text):
        """The mapping front door, the array core and the solution assembly
        refuse each bad allocation with the same message."""
        grid = types.SYSTEM_1.grid
        baselines = {"x": (140.0, 150.0)}
        names = list(caps)
        rows = np.array([caps[nm] for nm in names]).reshape(len(names), 2)
        base = np.array([baselines[nm] for nm in names]).reshape(len(names), 2)
        sol = mckp.MCKPSolution(
            total_value=0.0,
            spent=0.0,
            picks={nm: (0.0, 0.0, cg) for nm, cg in caps.items()},
        )
        messages = []
        for check in (
            lambda: validate_allocation(
                Allocation(caps=caps, spent=0), baselines, budget, grid
            ),
            lambda: validate_caps(rows, base, names, budget, grid),
            lambda: policies.allocation_from_solution(sol, names, base, budget, grid),
        ):
            with pytest.raises(ValueError, match=text) as err:
                check()
            messages.append(str(err.value))
        assert len(set(messages)) == 1

    @pytest.mark.parametrize(
        "caps", [{}, {"x": (140.0, 150.0), "y": (400.0, 400.0)}], ids=["empty", "two"]
    )
    def test_validate_allocation_accepts_good(self, caps):
        grid = types.SYSTEM_1.grid
        baselines = {"x": (140.0, 150.0), "y": (150.0, 150.0)}
        names = list(caps)
        rows = np.array([caps[nm] for nm in names]).reshape(len(names), 2)
        base = np.array([baselines[nm] for nm in names]).reshape(len(names), 2)
        validate_allocation(Allocation(caps=caps, spent=0), baselines, 500, grid)
        validate_caps(rows, base, names, 500, grid)

    @pytest.mark.parametrize(
        "names,text",
        [
            (["a", "b", "c"], "solution has no pick for receiver c"),
            (["b"], "solution picks a, which is not a receiver"),
        ],
        ids=["missing", "extra"],
    )
    def test_allocation_from_solution_rejects_receiver_mismatch(self, names, text):
        """The picks must cover exactly the receivers; the error names the
        first one missing or extra."""
        sol = mckp.MCKPSolution(
            total_value=0.0,
            spent=0.0,
            picks={"a": (0.0, 0.0, (150.0, 150.0)), "b": (0.0, 0.0, (150.0, 150.0))},
        )
        base = np.full((len(names), 2), 150.0)
        with pytest.raises(ValueError, match=text):
            policies.allocation_from_solution(
                sol, names, base, 1000.0, types.SYSTEM_1.grid
            )

    def test_ecoshift_at_least_heuristics_on_true_surfaces(self):
        """With perfect prediction EcoShift dominates DPS/MixedAdaptive."""
        system = types.SYSTEM_2
        apps, surfs = surfaces.build_paper_suite(system)
        apps = [a for a in apps if a.sclass in "CG"][:10]
        s = {a.name: surfs[a.name] for a in apps}
        baselines = {a.name: (250.0, 150.0) for a in apps}
        budget = 800.0
        gains = {}
        for pname in ("ecoshift", "dps", "mixed_adaptive"):
            alloc = policies.POLICIES[pname](apps, baselines, budget, system, s)
            gains[pname] = _avg_gain(alloc, s, baselines)
        assert gains["ecoshift"] >= gains["dps"] - 1e-9
        assert gains["ecoshift"] >= gains["mixed_adaptive"] - 1e-9
