"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as dk
from repro.kernels import flash_attention as fk
from repro.kernels import mckp_dp
from repro.kernels import ref
from repro.kernels import rmsnorm as rk


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# (max,+) convolution
# ---------------------------------------------------------------------------


def _largest_argmax(dp, f):
    """Largest maximizing shift of out[b] = max_{k<=b} dp[b-k] + f[k] in
    float32 (0 where every candidate is -inf): the kernel's tie rule."""
    dp = np.asarray(dp, np.float32)
    f = np.asarray(f, np.float32)
    idx = np.arange(len(dp))[:, None] - np.arange(len(f))[None, :]
    cand = np.where(
        idx >= 0, dp[np.clip(idx, 0, None)] + f[None, :], np.float32(-np.inf)
    )
    best = cand.max(axis=1)
    last = len(f) - 1 - np.argmax((cand == best[:, None])[:, ::-1], axis=1)
    return np.where(np.isneginf(best), 0, last)


class TestMaxPlus:
    @pytest.mark.parametrize("nb", [17, 64, 200, 513])
    @pytest.mark.parametrize("rows", [1, 11])
    def test_matches_ref(self, nb, rows):
        """One row, and every row of a batch spanning two 8-row tiles,
        against the jnp oracle; ties go to the largest shift."""
        rng = np.random.default_rng(nb + rows)
        dp = np.maximum.accumulate(rng.uniform(0, 1, (rows, nb)), axis=1)
        f = np.maximum.accumulate(rng.uniform(0, 1, (rows, nb)), axis=1)
        dp, f = jnp.asarray(dp, jnp.float32), jnp.asarray(f, jnp.float32)
        out_p, arg_p = mckp_dp.maxplus_conv_pallas_batched(dp, f)
        for r in range(rows):
            out_r, _ = ref.maxplus_conv(dp[r], f[r])
            np.testing.assert_allclose(out_p[r], out_r, rtol=1e-6)
            np.testing.assert_array_equal(
                np.asarray(arg_p[r]), _largest_argmax(dp[r], f[r])
            )

    def test_monotone_inputs_monotone_output(self):
        rng = np.random.default_rng(0)
        dp = jnp.asarray(np.maximum.accumulate(rng.uniform(0, 1, 128)), jnp.float32)
        f = jnp.asarray(np.maximum.accumulate(rng.uniform(0, 1, 128)), jnp.float32)
        out, _ = mckp_dp.maxplus_conv_pallas_batched(dp[None], f[None])
        assert np.all(np.diff(np.asarray(out)[0]) >= -1e-6)

    def test_planted_ties_resolve_to_largest_shift(self):
        """Equal candidates at several shifts: the kernel keeps the largest
        (the frontier combine's smallest-left-spend tie-break), where the
        oracle keeps the smallest; values agree bitwise."""
        dp = jnp.zeros((1, 4), jnp.float32)
        f = jnp.ones((1, 3), jnp.float32)
        out, arg = mckp_dp.maxplus_conv_pallas_batched(dp, f)
        np.testing.assert_array_equal(np.asarray(out)[0], [1, 1, 1, 1])
        np.testing.assert_array_equal(np.asarray(arg)[0], [0, 1, 2, 2])

        rng = np.random.default_rng(5)
        rows, nb, k = 9, 150, 70
        dp = rng.integers(0, 4, (rows, nb)).astype(np.float32)
        f = rng.integers(0, 4, (rows, k)).astype(np.float32)
        dp[:, :3] = -np.inf  # unreachable low grid points
        out, arg = mckp_dp.maxplus_conv_pallas_batched(
            jnp.asarray(dp), jnp.asarray(f)
        )
        f_full = np.full((rows, nb), -np.inf, np.float32)
        f_full[:, :k] = f
        differs = 0
        for r in range(rows):
            out_r, arg_r = ref.maxplus_conv(
                jnp.asarray(dp[r]), jnp.asarray(f_full[r])
            )
            np.testing.assert_array_equal(np.asarray(out)[r], np.asarray(out_r))
            want = _largest_argmax(dp[r], f[r])
            np.testing.assert_array_equal(np.asarray(arg)[r], want)
            differs += int(np.sum(want != np.asarray(arg_r)))
        assert differs > 0  # the ties were real


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,sq,skv,hq,hkv,d",
        [
            (2, 128, 128, 4, 4, 64),  # MHA
            (1, 128, 128, 8, 2, 64),  # GQA 4x
            (2, 96, 160, 4, 1, 32),  # MQA, ragged block tails
        ],
    )
    def test_causal_matches_ref(self, dtype, b, sq, skv, hq, hkv, d):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
        k = jax.random.normal(ks[1], (b, skv, hkv, d), dtype)
        v = jax.random.normal(ks[2], (b, skv, hkv, d), dtype)
        out = fk.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        want = ref.mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
        )

    @pytest.mark.parametrize("window", [32, 64])
    def test_sliding_window(self, window):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 128, 4, 32), jnp.float32)
        k = jax.random.normal(ks[1], (1, 128, 4, 32), jnp.float32)
        v = jax.random.normal(ks[2], (1, 128, 4, 32), jnp.float32)
        out = fk.flash_attention(
            q, k, v, causal=True, window=window, block_q=32, block_k=32
        )
        want = ref.mha_reference(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)

    def test_bidirectional_and_softcap(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (2, 64, 2, 32), jnp.float32)
        k = jax.random.normal(ks[1], (2, 64, 2, 32), jnp.float32)
        v = jax.random.normal(ks[2], (2, 64, 2, 32), jnp.float32)
        out = fk.flash_attention(
            q, k, v, causal=False, softcap=30.0, block_q=32, block_k=32
        )
        want = ref.mha_reference(q, k, v, causal=False, logit_softcap=30.0)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)

    def test_matches_blocked_jax_path(self):
        """The pure-jax blocked attention (model default) == kernel == ref."""
        from repro.models import blocks as mblocks

        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (2, 128, 8, 32), jnp.float32)
        k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
        v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
        out_jax = mblocks.blocked_attention(q, k, v, causal=True)
        out_ker = fk.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        want = ref.mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out_jax, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(out_ker, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Decode attention
# ---------------------------------------------------------------------------


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "b,s,hq,hkv,d", [(4, 256, 8, 2, 64), (2, 200, 4, 4, 32), (3, 512, 16, 8, 64)]
    )
    def test_matches_ref(self, dtype, b, s, hq, hkv, d):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (b, hq, d), dtype)
        kc = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
        vc = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
        lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
        out = dk.decode_attention(q, kc, vc, lengths, block_k=64)
        want = ref.decode_attention_reference(q, kc, vc, lengths)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
        )

    def test_length_one(self):
        """Degenerate cache with a single valid entry."""
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (2, 4, 32), jnp.float32)
        kc = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
        vc = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
        lengths = jnp.array([1, 1], jnp.int32)
        out = dk.decode_attention(q, kc, vc, lengths, block_k=64)
        want = ref.decode_attention_reference(q, kc, vc, lengths)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


class TestRMSNorm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [(4, 64, 256), (3, 100, 128), (1, 1, 512)])
    def test_matches_ref(self, dtype, shape):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        x = jax.random.normal(ks[0], shape, dtype)
        scale = 0.1 * jax.random.normal(ks[1], shape[-1:], jnp.float32)
        out = rk.rmsnorm(x, scale, block_rows=32)
        want = ref.rmsnorm(x, scale)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
        )


# ---------------------------------------------------------------------------
# Sparse-option (max,+) stage with backpointers (fused-round kernel)
# ---------------------------------------------------------------------------


def _stage_ref_np(dp, kb, vb):
    """Scalar oracle of maxplus_stage_pallas_batched (first-max in j)."""
    r, nb = dp.shape
    out = np.full((r, nb), -np.inf, dtype=dp.dtype)
    arg = np.zeros((r, nb), dtype=np.int32)
    for ri in range(r):
        for b in range(nb):
            best, bj = -np.inf, 0
            for j in range(kb.shape[1]):
                k = kb[ri, j]
                cand = dp[ri, b - k] + vb[ri, j] if b - k >= 0 else -np.inf
                if cand > best:
                    best, bj = cand, j
            out[ri, b] = best
            arg[ri, b] = bj
    return out, arg


def _stage_inputs(rng, r, nb, k, dtype):
    dp = np.maximum.accumulate(rng.uniform(0, 1, (r, nb)), axis=1).astype(dtype)
    dp[:, 1:][rng.uniform(size=(r, nb - 1)) < 0.2] = -np.inf
    kb = np.sort(rng.integers(0, nb + 1, (r, k)), axis=1)[:, ::-1].astype(np.int32)
    vb = np.sort(rng.uniform(0, 0.5, (r, k)), axis=1).astype(dtype)
    # pad-style tail options: spend 0, value -inf (as the fused banks emit)
    vb[:, -1] = -np.inf
    kb[:, -1] = 0
    return dp, kb, vb


class TestMaxPlusStageBatched:
    @pytest.mark.parametrize("r,nb,k", [(1, 16, 3), (4, 64, 8), (3, 200, 21)])
    @pytest.mark.parametrize("tiles", [1, 3])
    def test_matches_scalar_ref(self, r, nb, k, tiles):
        """``tiles=3`` stacks rows past one 8-row kernel tile."""
        r = r * tiles
        rng = np.random.default_rng(r * 1000 + nb + k)
        dp, kb, vb = _stage_inputs(rng, r, nb, k, np.float32)
        out, arg = mckp_dp.maxplus_stage_pallas_batched(
            jnp.asarray(dp), jnp.asarray(kb), jnp.asarray(vb),
        )
        out_r, arg_r = _stage_ref_np(dp, kb, vb)
        np.testing.assert_array_equal(np.asarray(out), out_r)
        np.testing.assert_array_equal(np.asarray(arg), arg_r)

    def test_float64_bitwise(self):
        """f64 inputs (the fused solver path) reproduce the host adds
        bit-for-bit — same IEEE ops in the same order."""
        rng = np.random.default_rng(7)
        with jax.enable_x64(True):
            dp, kb, vb = _stage_inputs(rng, 5, 96, 12, np.float64)
            out, arg = mckp_dp.maxplus_stage_pallas_batched(
                jnp.asarray(dp), jnp.asarray(kb), jnp.asarray(vb),
            )
            assert out.dtype == jnp.float64
            out_r, arg_r = _stage_ref_np(dp, kb, vb)
            np.testing.assert_array_equal(np.asarray(out), out_r)
            np.testing.assert_array_equal(np.asarray(arg), arg_r)

    @pytest.mark.parametrize(
        "case,r,nb,k",
        [
            ("shared_spends", 3, 32, 24),
            ("past_grid", 3, 16, 12),
            ("all_pads", 4, 16, 8),
            ("k_over_nb", 2, 8, 40),
            ("cell_k256", 2, 256, 256),
            ("cell_k128", 2, 256, 128),
        ],
    )
    def test_option_mapping_bitwise(self, case, r, nb, k):
        """The one-hot option mapping in float64, bit-for-bit against the
        option-order scan: options sharing a spend, equal values among
        them (the first of maximal value wins), spends past the grid,
        rows of pads only, more options than grid points, and the
        benchmark cells' (K, NB) on a few rows."""
        rng = np.random.default_rng(sum(map(ord, case)))
        with jax.enable_x64(True):
            dp, kb, vb = _stage_inputs(rng, r, nb, k, np.float64)
            if case == "shared_spends":
                # few spends and few values: runs of equal (spend, value)
                kb = np.sort(rng.integers(0, 6, (r, k)), axis=1)[:, ::-1]
                vb = rng.choice([0.125, 0.25, 0.375], (r, k))
            elif case == "past_grid":
                kb = np.sort(rng.integers(0, 2 * nb, (r, k)), axis=1)[:, ::-1]
            elif case == "all_pads":
                vb[1::2], kb[1::2] = -np.inf, 0
            elif case == "k_over_nb":
                vb = np.round(vb, 1)
            kb = np.ascontiguousarray(kb, dtype=np.int32)
            out, arg = mckp_dp.maxplus_stage_pallas_batched(
                jnp.asarray(dp), jnp.asarray(kb), jnp.asarray(vb),
            )
            out_r, arg_r = _stage_ref_np(dp, kb, vb)
            np.testing.assert_array_equal(np.asarray(out), out_r)
            np.testing.assert_array_equal(np.asarray(arg), arg_r)

    def test_direct_vs_jitted_lowering(self):
        """Interpret-mode kernel: the direct call (primitive impl) and an
        explicit outer-jit XLA lowering produce identical bits.
        (jax.disable_jit() is off-limits: pallas_call's impl re-binds the
        primitive under jit and would recurse forever without it.)"""
        rng = np.random.default_rng(11)
        dp, kb, vb = _stage_inputs(rng, 4, 80, 9, np.float32)
        args = (jnp.asarray(dp), jnp.asarray(kb), jnp.asarray(vb))
        out_d, arg_d = mckp_dp.maxplus_stage_pallas_batched(*args)
        jitted = jax.jit(mckp_dp.maxplus_stage_pallas_batched)
        out_j, arg_j = jitted(*args)
        np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_j))
        np.testing.assert_array_equal(np.asarray(arg_d), np.asarray(arg_j))

    def test_backpointers_are_first_max(self):
        """Duplicate options tie: the backpointer is the first maximizer
        in option order (the sparse dict-DP largest-spend tie-break)."""
        dp = jnp.asarray(np.zeros((1, 8), np.float32))
        kb = jnp.asarray(np.array([[2, 2, 0]], np.int32))
        vb = jnp.asarray(np.array([[0.5, 0.5, 0.1]], np.float32))
        out, arg = mckp_dp.maxplus_stage_pallas_batched(dp, kb, vb)
        np.testing.assert_array_equal(
            np.asarray(arg)[0], [2, 2, 0, 0, 0, 0, 0, 0]
        )
        np.testing.assert_allclose(
            np.asarray(out)[0], [0.1, 0.1, 0.5, 0.5, 0.5, 0.5, 0.5, 0.5]
        )

    def test_ops_wrapper_matches(self):
        from repro.kernels import ops

        rng = np.random.default_rng(3)
        dp, kb, vb = _stage_inputs(rng, 2, 48, 5, np.float32)
        out_w, arg_w = ops.maxplus_stage_batched(
            jnp.asarray(dp), jnp.asarray(kb), jnp.asarray(vb)
        )
        out_k, arg_k = mckp_dp.maxplus_stage_pallas_batched(
            jnp.asarray(dp), jnp.asarray(kb), jnp.asarray(vb)
        )
        np.testing.assert_array_equal(np.asarray(out_w), np.asarray(out_k))
        np.testing.assert_array_equal(np.asarray(arg_w), np.asarray(arg_k))


# ---------------------------------------------------------------------------
# Hypothesis sweep on the maxplus kernel (system invariant)
# ---------------------------------------------------------------------------

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # image without hypothesis: property tests skip
    from _hypothesis_stub import hypothesis, st


@hypothesis.given(
    nb=st.integers(2, 96),
    seed=st.integers(0, 2**31 - 1),
)
@hypothesis.settings(max_examples=20, deadline=None)
def test_maxplus_property(nb, seed):
    """out[b] >= dp[b] + f[0] and out[b] >= dp[0] + f[b] (feasible picks)."""
    rng = np.random.default_rng(seed)
    dp = jnp.asarray(np.maximum.accumulate(rng.uniform(0, 1, nb)), jnp.float32)
    f = jnp.asarray(np.maximum.accumulate(rng.uniform(0, 1, nb)), jnp.float32)
    out, arg = ref.maxplus_conv(dp, f)
    out = np.asarray(out)
    dp_n, f_n = np.asarray(dp), np.asarray(f)
    assert np.all(out >= dp_n + f_n[0] - 1e-6)
    assert np.all(out >= dp_n[0] + f_n[np.arange(nb)] - 1e-6)
    # argmax is a real maximizer
    ks = np.asarray(arg)
    bs = np.arange(nb)
    np.testing.assert_allclose(out, dp_n[bs - ks] + f_n[ks], rtol=1e-6)


# ---------------------------------------------------------------------------
# Persistent compile cache placement (entry points call use_compile_cache)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` wins untouched; otherwise one fixed,
    git-ignored directory at the checkout root."""
    from pathlib import Path

    from repro.kernels import ops

    root = Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = ops.use_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir is None:
        assert got == str(root / ".jax_cache") == now
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    else:
        assert got == env_dir and now == before
