"""Device-sharded fused leaf DPs: parity across device counts.

The fused round ``shard_map``s its batched leaf DP scan over the leaf
axis (``repro.kernels.ops.leaf_shard_mesh``).  Each [L, NB] DP row is
independent, so the split is bitwise-neutral by construction — this
suite certifies it end to end on 4 virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``, the same smoke
CI runs): the sharded fused solve, the forced-single-device fused solve
(``REPRO_FUSED_SHARDS=1``) and the host sparse solve must agree
bit-for-bit on picks, total value, spends and per-domain spends.

XLA fixes the device count at backend init, so the comparison runs in a
subprocess with the flag set before the first jax import.
"""

import os
import pathlib
import subprocess
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]

_SCRIPT = r"""
import numpy as np
import jax

assert jax.device_count() == 4, jax.devices()

import sys
sys.path.insert(0, "tests")
from test_hier_alloc import _random_groups
from test_deep_tree import _random_deep_tree
from repro.core import mckp


def solve_fused(root, budget):
    fstate = mckp.FusedState()
    out = mckp.solve_hierarchical_fused(
        root, budget, state=mckp.HierState(), fstate=fstate
    )
    assert out is not None, fstate.stats["fallback_reason"]
    return out


for seed in range(6):
    rng = np.random.default_rng(7000 + seed)
    budget = float(rng.integers(6, 30)) * 25.0
    root, _ = _random_deep_tree(
        rng, budget, unconstrained_internal=bool(seed % 2)
    )
    host = mckp.solve_hierarchical(root, budget)

    assert mckp._fused_shards() == 4  # sharded path engaged
    sharded = solve_fused(root, budget)

    import os
    os.environ["REPRO_FUSED_SHARDS"] = "1"
    mckp._fused_shards.cache_clear()
    assert mckp._fused_shards() == 1
    single = solve_fused(root, budget)
    del os.environ["REPRO_FUSED_SHARDS"]
    mckp._fused_shards.cache_clear()

    for sol in (sharded, single):
        assert sol.picks == host.picks
        assert sol.total_value == host.total_value
        assert sol.spent == host.spent
        assert sol.domain_spent == host.domain_spent

# warm-state structure change under sharding (DESIGN.md §17): a second,
# differently-shaped tree against the *same* FusedState must repack the
# resident banks by device-side compaction — no host fallback — and stay
# bit-for-bit with the host solver
rng = np.random.default_rng(7777)
budget = 500.0
root_a, _ = _random_deep_tree(rng, budget, unconstrained_internal=False)
root_b, _ = _random_deep_tree(rng, budget, unconstrained_internal=True)
fstate = mckp.FusedState()
sa = mckp.solve_hierarchical_fused(
    root_a, budget, state=mckp.HierState(), fstate=fstate
)
assert sa is not None, fstate.stats["fallback_reason"]
sb = mckp.solve_hierarchical_fused(
    root_b, budget, state=mckp.HierState(), fstate=fstate
)
assert sb is not None, fstate.stats["fallback_reason"]
assert fstate.stats["fallbacks"] == 0, fstate.stats
assert fstate.stats["rebuilds"] == 1, fstate.stats  # cold start only
assert fstate.stats["compactions"] >= 1, fstate.stats
# the resident banks really spread leaf-wise over all four devices (after
# a cold upload, a compaction and row patches) instead of piling up on one
for bank in (fstate.kb_dev, fstate.vb_dev):
    shards_ = bank.addressable_shards
    assert len({sh.device for sh in shards_}) == 4, bank.sharding
    assert all(
        sh.data.shape[1] * 4 == bank.shape[1] for sh in shards_
    ), [sh.data.shape for sh in shards_]
hb = mckp.solve_hierarchical(root_b, budget)
assert sb.picks == hb.picks
assert sb.total_value == hb.total_value
assert sb.spent == hb.spent
assert sb.domain_spent == hb.domain_spent

print("SHARDED_PARITY_OK")
"""


def test_sharded_leaf_dps_bitwise_match_single_device():
    env = dict(os.environ)
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(_ROOT / "src") + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    env.pop("REPRO_FUSED_SHARDS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env=env,
        cwd=_ROOT,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "SHARDED_PARITY_OK" in out.stdout
