"""EcoShift in 60 seconds: predict -> DP-allocate -> beat the baselines.

Runs the full pipeline on the paper's Table-2 scenario plus a small
emulated cluster: train the NCF predictor on historical apps, onboard two
unseen apps with a brief online profile, and distribute 200 W of reclaimed
power with the DP allocator.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import ncf, policies, surfaces, types
from repro.core.allocator import EcoShiftAllocator
from repro.core.emulator import ClusterEmulator
from repro.kernels.ops import use_compile_cache

SYSTEM = types.SYSTEM_2


def main() -> None:
    use_compile_cache()
    print("== EcoShift quickstart ==")
    apps, surfs = surfaces.build_paper_suite(SYSTEM)

    # 1. offline: train the NCF predictor on 30 historical applications
    hist = {a.name: surfs[a.name] for a in apps[:30]}
    print(f"training NCF predictor on {len(hist)} historical apps ...")
    allocator = EcoShiftAllocator.train_offline(
        SYSTEM, hist, ncf.NCFConfig(train_steps=1200)
    )

    # 2. online: two unseen apps arrive; profile 8 cap pairs each
    cfd, rt = surfaces.cfd_surface(), surfaces.raytracing_surface()
    allocator.onboard("cfd", cfd)
    allocator.onboard("raytracing", rt)

    # 3. distribute 200 W of reclaimed power (the paper's Table-2 case)
    recv = [types.AppSpec("cfd", "C", "cfd"), types.AppSpec("raytracing", "G", "raytracing")]
    baselines = {"cfd": (300.0, 200.0), "raytracing": (300.0, 200.0)}
    alloc = allocator.allocate(recv, baselines, budget=200.0)
    true = {"cfd": cfd, "raytracing": rt}
    print("\nEcoShift allocation (200 W reclaimed):")
    for name, (c, g) in sorted(alloc.caps.items()):
        gain = float(true[name].improvement(baselines[name], c, g))
        print(f"  {name:12s} -> ({c:.0f} W CPU, {g:.0f} W GPU)  measured gain {gain*100:.2f}%")

    for pname in ("dps", "mixed_adaptive"):
        a = policies.POLICIES[pname](recv, baselines, 200.0, SYSTEM, true)
        gains = [
            float(true[n].improvement(baselines[n], *a.caps[n])) for n in a.caps
        ]
        print(f"  baseline {pname:15s} avg gain {np.mean(gains)*100:.2f}%")

    # 4. a 40-node emulated cluster round
    emu = ClusterEmulator.build(SYSTEM, apps, surfs, n_nodes=40, seed=0)
    donors, receivers, pool = emu.partition()
    print(f"\ncluster: {len(donors)} donors reclaim {pool:.0f} W for {len(receivers)} receivers")
    for pname in ("ecoshift", "dps", "mixed_adaptive"):
        res = emu.run_round(pname)
        print(f"  {pname:15s} avg improvement {res.avg_improvement*100:.2f}%  jain {res.jain_index:.3f}")


if __name__ == "__main__":
    main()
