"""Compile-only checks of the fused round's kernels for a TPU v5e.

Nothing here runs on a chip: each test lowers with ``interpret=False``
and compiles against a *described* v5e (``v5e:2x2``), so Mosaic refuses
here what the chip would refuse — misaligned tiles, unaligned dynamic
slices, too much fast memory — at no chip time.  Shapes are the real
widths of the 10k-node 16-rack deployment ``chip_smoke.py`` drives: 16
leaves, 40 stages, 64 options on a 64-point leaf grid, and a 256-point
frontier tree grid.

The topology is described inside a module fixture, never at import:
only the test worker that runs this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import mckp
from repro.kernels import mckp_dp
from repro.kernels import ops

#: fused-pipeline layout of the 10k-node hier-16 10%-churn scenario
L, S, K, NB, NBT = 16, 40, 64, 64, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles cannot be read back from a cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *avals) -> str:
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text  # a Mosaic kernel, not an interpreter
    return text


def _kernel_names(text: str) -> list[str]:
    return re.findall(
        r'^\s*%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text, re.M
    )


@pytest.mark.parametrize(
    "rows,nb,k",
    [(L, NB, K), (8, NBT, NBT), (16, 256, 256), (16, 256, 128), (4, 256, 256)],
)
def test_stage_kernel_compiles(one_chip, rows, nb, k):
    """The leaf DP stage at the real leaf widths, at the frontier tree's
    grid width, at both benchmark cells' leaf batch (16 rows on a
    256-point grid, K = 256 and 128) and at its four-chip share per
    device (4 rows): one Mosaic kernel a stage, under its wrapper's
    name."""
    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compiled_text(
        lambda dp, kb, vb: mckp_dp.maxplus_stage_pallas_batched(
            dp, kb, vb, interpret=False
        ),
        a((rows, nb), jnp.float32), a((rows, k), jnp.int32),
        a((rows, k), jnp.float32),
    )
    kernels = _kernel_names(text)
    assert len(kernels) == 1
    assert re.fullmatch(r"maxplus_\w*pallas\w*\.\d+", kernels[0])


@pytest.mark.parametrize("rows,nb,k", [(8, NBT, 121), (8, 4096, 2041), (1, 4096, 256)])
def test_dense_kernels_compile(one_chip, rows, nb, k):
    """The row-batched dense (max,+) convolution: the frontier combine of
    the fused round, also on the benchmark cells' 4,096-point tree grid
    (the widest wave's and a single pair's shift counts)."""
    f32 = jnp.float32
    text = _compiled_text(
        lambda dp, f: mckp_dp.maxplus_conv_pallas_batched(
            dp, f, interpret=False
        ),
        jax.ShapeDtypeStruct((rows, nb), f32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows, k), f32, sharding=one_chip),
    )
    kernels = _kernel_names(text)
    assert len(kernels) == 1
    assert re.fullmatch(r"maxplus_\w*pallas\w*\.\d+", kernels[0])


def _pipeline_avals(kb_sh, vb_sh, leaf_sh, rep_sh, lp, n_doms):
    return (
        jax.ShapeDtypeStruct((S, lp, K), jnp.int32, sharding=kb_sh),
        jax.ShapeDtypeStruct((S, lp, K), jnp.float32, sharding=vb_sh),
        jax.ShapeDtypeStruct((lp,), jnp.int32, sharding=leaf_sh),
        jax.ShapeDtypeStruct((n_doms,), jnp.int32, sharding=rep_sh),
    )


def _site_tree(lp):
    ops_, depths, under, dom_rows = mckp._tree_ops(("d", 0, tuple(range(L))), lp)
    return mckp._tree_waves(ops_, depths, under, NB, NBT), dom_rows


@pytest.mark.parametrize("shards", [1, 4])
def test_fused_pipeline_compiles(topo, monkeypatch, shards):
    """The whole jitted fused round (leaf scan, frontier waves, root
    argmax, backtracks) in float32 for the 16-rack layout — on one chip,
    and with the leaf DPs sharded over the four chips of a v5e host."""
    tree = _site_tree(L)
    if shards == 1:
        sh = SingleDeviceSharding(topo.devices[0])
        avals = _pipeline_avals(sh, sh, sh, sh, L, len(tree[1]))
    else:
        mesh = Mesh(np.asarray(topo.devices[:4]), ("leaves",))
        monkeypatch.setattr(ops, "leaf_shard_mesh", lambda n: mesh)
        bank = NamedSharding(mesh, P(None, "leaves", None))
        avals = _pipeline_avals(
            bank, bank, NamedSharding(mesh, P("leaves")),
            NamedSharding(mesh, P()), L, len(tree[1]),
        )
    # bypass _fused_pipeline_fn's cache: these pipelines belong to no real device
    run = mckp._fused_pipeline_fn.__wrapped__(
        tree, L, L, S, K, NB, NBT, shards, False
    )
    compiled = run.lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_pipeline_keeps_its_names(topo):
    """What the trace reduction reads by name survives the TPU compiler:
    the Pallas custom calls keep their wrappers' names
    (``maxplus_*_pallas_batched.N``), and the option mapping of the stage
    wrapper is dense compare-and-reduce: no scatter or gather is left in
    the ``option_scatter`` scope, and the fusions the compiler makes of it
    keep the scope in their ``op_name``, so ``option_scatter_ms`` cannot
    silently read nothing (the compiler drops the metadata of ops it
    rewrites, which then count under the enclosing ``while``)."""
    tree = _site_tree(L)
    sh = SingleDeviceSharding(topo.devices[0])
    run = mckp._fused_pipeline_fn.__wrapped__(
        tree, L, L, S, K, NB, NBT, 1, False
    )
    text = run.lower(*_pipeline_avals(sh, sh, sh, sh, L, len(tree[1]))).compile().as_text()
    kernels = _kernel_names(text)
    assert len(kernels) == 1 + len(tree[0])  # the leaf stage, one per wave
    assert all(re.fullmatch(r"maxplus_\w*pallas\w*\.\d+", k) for k in kernels)
    # every instruction, fused or not, with its op name
    named = re.findall(
        r'^\s*(?:ROOT )?%\S+ = ([^\n]*?), metadata=\{[^\n]*?op_name="([^"]*)"',
        text, re.M,
    )
    mapping = [(ins, p) for ins, p in named if "/option_scatter/" in p]
    assert mapping
    for ins, p in mapping:
        assert not re.search(r"\s(scatter|gather)\(", ins), ins
        assert not re.match(r"(scatter|gather)", p.rsplit("/", 1)[1]), p
    assert any(re.search(r"\sfusion\(", ins) for ins, _ in mapping)
