"""jit'd public wrappers for the Pallas kernels.

Every op runs its kernel in interpret mode off a TPU (interpret mode
executes the kernel bodies with JAX semantics on the CPU) and compiles it
natively with Mosaic on a TPU — :func:`on_tpu` is the one place that
choice is made.  Reference semantics live in ``repro.kernels.ref``.
"""

from __future__ import annotations

import contextlib
import functools
import os

import jax
import numpy as np

from repro.kernels import mckp_dp as _mckp_dp


@functools.cache
def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU.

    The device choice of every kernel wrapper: Pallas kernels compile
    natively there and run interpreted everywhere else."""
    return jax.default_backend() == "tpu"


def device_value_dtype() -> type:
    """Dtype of the float values the device-resident paths keep.

    float64 where the backend provides it (the CPU: the fused round is
    then bit-for-bit the host's float64 DP); float32 on a TPU, whose
    kernels take no float64 (DESIGN.md §14 states the bound that holds
    there)."""
    return np.float32 if on_tpu() else np.float64


def device_value_scope():
    """Context for building and running the device-resident paths: x64
    on where values ride float64, and no x64 scope at all on a TPU."""
    if device_value_dtype() == np.float64:
        return jax.enable_x64(True)
    return contextlib.nullcontext()


#: fixed persistent-compile-cache directory (git-ignored) at the checkout root
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points (``chip_smoke.py``, the benchmarks, the tools and the
    examples) call this before their first compile.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and nothing
    is changed; otherwise the cache lives in one fixed directory at the
    checkout root, so every process of a checkout shares it (a moving
    path would never hit)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@functools.cache
def leaf_shard_mesh(n_devices: int):
    """1-D device mesh (axis ``"leaves"``) over the first ``n_devices``
    local devices.

    The fused round's batched leaf DPs ``shard_map`` over this axis: each
    [L, NB] DP row is independent, so splitting the [S, L, K] option
    banks leaf-wise across devices is bitwise-neutral — every device runs
    the identical per-row kernel and the frontier aggregation tree then
    reduces the gathered per-device partials (DESIGN.md §16).  Multi-host
    CPU smoke rides ``XLA_FLAGS=--xla_force_host_platform_device_count``.
    """
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:n_devices]), ("leaves",))


@functools.partial(jax.jit, static_argnames=("k_pad",))
def bank_compact(kb_old, vb_old, src_s, src_l, *, k_pad: int):
    """Device-side compaction of the fused round's resident option banks.

    When the bank *layout* changes (leaf set, padded dims, topology — see
    DESIGN.md §17) the surviving rows are repacked on device instead of
    rebuilt on the host: ``src_s``/``src_l`` are ``[S_new, L_new]`` int32
    gather maps into the old ``[S_old, L_old, K_old]`` banks (-1 marks a
    row with no clean source — it is initialized to the identity row
    ``kb = 0 / vb = [0, -inf, ...]`` and, if it carries real content, the
    caller scatters it afterwards via the donated row patch).  The option
    axis pads (or truncates) to ``k_pad``; a clean row's tail beyond its
    own option count is identity padding by construction, so both
    directions are exact.  Returns the new ``[S_new, L_new, k_pad]``
    (kb, vb) banks.  Pure gather/select — no values are recomputed, so a
    gathered row is bitwise the row a host rebuild would upload.
    """
    import jax.numpy as jnp

    valid = src_s >= 0
    ss = jnp.where(valid, src_s, 0)
    ll = jnp.where(valid, src_l, 0)
    kb_g = kb_old[ss, ll]  # [S_new, L_new, K_old]
    vb_g = vb_old[ss, ll]
    k_old = kb_old.shape[-1]
    if k_pad > k_old:
        pad = ((0, 0), (0, 0), (0, k_pad - k_old))
        kb_g = jnp.pad(kb_g, pad)
        vb_g = jnp.pad(vb_g, pad, constant_values=-jnp.inf)
    elif k_pad < k_old:
        kb_g = kb_g[..., :k_pad]
        vb_g = vb_g[..., :k_pad]
    kb_id = jnp.zeros_like(kb_g)
    vb_id = jnp.full_like(vb_g, -jnp.inf).at[..., 0].set(0.0)
    m = valid[..., None]
    return jnp.where(m, kb_g, kb_id), jnp.where(m, vb_g, vb_id)


def maxplus_stage_batched(dp, kb, vb):
    """Sparse-option (max,+) stage with backpointer output.

    dp: [R, NB]; kb: [R, K] int32 descending spend offsets; vb: [R, K]
    option values.  Returns (out [R, NB], arg [R, NB]) where ``arg`` is
    the first maximizing option index — the backpointer table the fused
    device-resident round backtracks through with device gathers.
    Dtype-preserving (float64 in interpret mode for the bit-for-bit
    fused solver path).
    """
    return _mckp_dp.maxplus_stage_pallas_batched(
        dp, kb, vb, interpret=not on_tpu()
    )


def flash_attention(q, k, v, **kw):
    """Fused GQA attention (train/prefill).  See flash_attention.py."""
    from repro.kernels import flash_attention as _fa

    return _fa.flash_attention(q, k, v, interpret=not on_tpu(), **kw)


def decode_attention(q, k_cache, v_cache, lengths, **kw):
    """Flash-decode GQA attention over a KV cache."""
    from repro.kernels import decode_attention as _da

    return _da.decode_attention(
        q, k_cache, v_cache, lengths, interpret=not on_tpu(), **kw
    )


def rmsnorm(x, scale, *, eps: float = 1e-6):
    """Fused RMSNorm."""
    from repro.kernels import rmsnorm as _rn

    return _rn.rmsnorm(x, scale, eps=eps, interpret=not on_tpu())
