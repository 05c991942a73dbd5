"""Pure-jnp oracles for every Pallas kernel in this package.

Each function here is the semantic ground truth: kernels are validated
against these with ``assert_allclose`` over shape/dtype sweeps
(tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# (max,+) convolution — the EcoShift cluster-DP stage (paper §3.2.2, Eq. 2)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk",))
def maxplus_conv(dp: jax.Array, f: jax.Array, chunk: int = 512):
    """Tropical-semiring convolution.

    out[b] = max_{0<=k<=b} dp[b-k] + f[k]
    arg[b] = the smallest maximizing k.

    dp, f: [NB] float arrays.  Evaluated in b-chunks so the [chunk, NB]
    candidate tile bounds the memory footprint (the Pallas kernel tiles the
    same way in VMEM).
    """
    nb = dp.shape[0]
    ks = jnp.arange(nb)
    neg = jnp.asarray(-jnp.inf, dp.dtype)

    def one_chunk(b0):
        b = b0 + jnp.arange(chunk)  # [chunk]
        idx = b[:, None] - ks[None, :]  # [chunk, nb]
        valid = (idx >= 0) & (b[:, None] < nb)
        cand = jnp.where(valid, dp[jnp.clip(idx, 0, nb - 1)], neg) + f[None, :]
        cand = jnp.where(valid, cand, neg)
        arg = jnp.argmax(cand, axis=1)
        out = jnp.take_along_axis(cand, arg[:, None], axis=1)[:, 0]
        return out, arg

    nchunks = -(-nb // chunk)
    starts = jnp.arange(nchunks) * chunk
    outs, args = jax.lax.map(one_chunk, starts)
    return outs.reshape(-1)[:nb], args.reshape(-1)[:nb].astype(jnp.int32)


# ---------------------------------------------------------------------------
# RMSNorm (+ optional residual add) — memory-bound fusion exemplar
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm over the trailing axis, fp32 statistics."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention references (used by flash_attention / decode_attention kernels)
# ---------------------------------------------------------------------------


def mha_reference(
    q: jax.Array,  # [B, Tq, Hq, D]
    k: jax.Array,  # [B, Tk, Hkv, D]
    v: jax.Array,  # [B, Tk, Hkv, D]
    *,
    causal: bool = True,
    window: int | None = None,
    logit_softcap: float | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """Grouped-query attention oracle, fp32 softmax.

    ``window`` enables sliding-window masking (each query attends to at most
    the previous ``window`` keys). ``q_offset`` places the query block at
    absolute positions [q_offset, q_offset+Tq) against keys [0, Tk).
    """
    b, tq, hq, d = q.shape
    _, tk, hkv, _ = k.shape
    assert hq % hkv == 0
    groups = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, tq, hkv, groups, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) / jnp.sqrt(d).astype(jnp.float32)
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    qpos = q_offset + jnp.arange(tq)
    kpos = jnp.arange(tk)
    mask = jnp.ones((tq, tk), dtype=bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= qpos[:, None] - kpos[None, :] < window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
    return out.reshape(b, tq, hq, d).astype(q.dtype)


def decode_attention_reference(
    q: jax.Array,  # [B, Hq, D] one new token per sequence
    k_cache: jax.Array,  # [B, S, Hkv, D]
    v_cache: jax.Array,  # [B, S, Hkv, D]
    lengths: jax.Array,  # [B] valid KV lengths
) -> jax.Array:
    """Single-token GQA decode oracle with per-sequence lengths."""
    b, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    groups = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, groups, d)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    logits = jnp.einsum("bhgd,bshd->bhgs", qf, kf) / jnp.sqrt(d).astype(jnp.float32)
    mask = jnp.arange(s)[None, :] < lengths[:, None]  # [B, S]
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, vf)
    return out.reshape(b, hq, d).astype(q.dtype)
