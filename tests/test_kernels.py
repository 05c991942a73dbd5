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
    @pytest.mark.parametrize("nb", [17, 64, 200, 513, 2041])
    @pytest.mark.parametrize("rows", [1, 11, 16, 40])
    def test_matches_ref(self, nb, rows):
        """One row, one whole-batch block, several blocks and padded rows,
        on shift counts that are not a multiple of the loop's unroll,
        bitwise against the jnp oracle; ties go to the largest shift.
        Row 0 of a batch is all -inf: -1 from the kernel, 0 from the
        wrapper."""
        rng = np.random.default_rng(nb + rows)
        dp = np.maximum.accumulate(rng.uniform(0, 1, (rows, nb)), axis=1)
        f = np.maximum.accumulate(rng.uniform(0, 1, (rows, nb)), axis=1)
        if rows > 1:
            dp[0] = -np.inf
        dp, f = jnp.asarray(dp, jnp.float32), jnp.asarray(f, jnp.float32)
        out_p, arg_p = mckp_dp.maxplus_conv_pallas_batched(dp, f)
        for r in range(rows):
            out_r, _ = ref.maxplus_conv(dp[r], f[r])
            np.testing.assert_array_equal(np.asarray(out_p[r]), np.asarray(out_r))
            np.testing.assert_array_equal(
                np.asarray(arg_p[r]), _largest_argmax(dp[r], f[r])
            )
        if rows > 1:
            _, arg_k = mckp_dp._maxplus_tiles(dp, f, interpret=True)
            np.testing.assert_array_equal(np.asarray(arg_k[0]), -1)
            assert np.all(np.asarray(arg_k[1:]) >= 0)

    @pytest.mark.parametrize(
        "rows,lanes,want",
        [
            (16, 256, (16, 4)),  # both cells' leaf stage: one block
            (4, 256, (8, 8)),  # its share per chip of a four-chip scan
            (8, 4096, (8, 1)),  # the frontier's widest wave
            (1, 4096, (8, 1)),  # its last pair
            (40, 256, (24, 2)),  # past one block: two, padded to 48 rows
        ],
    )
    def test_loop_shape_follows_the_tile(self, rows, lanes, want):
        """Row block and shifts a trip come from the static shape: whole
        small batches, the partial accumulators within half the
        register file, one shift a trip on the frontier's wide tiles."""
        block, unroll = mckp_dp._loop_shape(rows, lanes)
        assert (block, unroll) == want
        regs = block * lanes // 1024
        assert block % 8 == 0 and 128 % unroll == 0
        assert unroll == 1 or 2 * unroll * regs <= 32
        assert block == 8 or regs <= 8

    def test_monotone_inputs_monotone_output(self):
        rng = np.random.default_rng(0)
        dp = jnp.asarray(np.maximum.accumulate(rng.uniform(0, 1, 128)), jnp.float32)
        f = jnp.asarray(np.maximum.accumulate(rng.uniform(0, 1, 128)), jnp.float32)
        out, _ = mckp_dp.maxplus_conv_pallas_batched(dp[None], f[None])
        assert np.all(np.diff(np.asarray(out)[0]) >= -1e-6)

    @pytest.mark.parametrize("rows,nb,k", [(9, 150, 70), (16, 256, 256), (40, 200, 131)])
    def test_planted_ties_resolve_to_largest_shift(self, rows, nb, k):
        """Equal candidates at several shifts: the kernel keeps the largest
        (the frontier combine's smallest-left-spend tie-break), where the
        oracle keeps the smallest; values agree bitwise.  Ties planted in
        every residue of the loop's unroll land in different partial
        accumulators, and their merge still keeps the largest shift."""
        dp = jnp.zeros((1, 4), jnp.float32)
        f = jnp.ones((1, 3), jnp.float32)
        out, arg = mckp_dp.maxplus_conv_pallas_batched(dp, f)
        np.testing.assert_array_equal(np.asarray(out)[0], [1, 1, 1, 1])
        np.testing.assert_array_equal(np.asarray(arg)[0], [0, 1, 2, 2])

        _, unroll = mckp_dp._loop_shape(rows, -(-nb // 128) * 128)
        rng = np.random.default_rng(rows + nb)
        dp = rng.integers(0, 4, (rows, nb)).astype(np.float32)
        f = rng.integers(0, 4, (rows, k)).astype(np.float32)
        dp[:, :3] = -np.inf  # unreachable low grid points
        # row 0: a flat dp and one tied best value at a few shifts spread
        # over the residues mod the unroll, so each winner's rival sits
        # in another partial accumulator (an earlier trip, too)
        dp[0, 3:] = 0.0
        f[0] = 0.0
        plant = np.arange(3, k, max(1, unroll + 1))
        f[0, plant] = 1.0
        if unroll > 1:
            assert len(set(plant % unroll)) > 1
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
        b = np.arange(nb)
        best = np.array([plant[plant <= x - 3].max(initial=-1) for x in b])
        np.testing.assert_array_equal(
            np.asarray(arg)[0][best >= 0], best[best >= 0]
        )


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
    """Scalar oracle of maxplus_stage_pallas_batched (first-max in j):
    the option loop, each step one IEEE add per (row, grid point)."""
    r, nb = dp.shape
    rows, b = np.arange(r)[:, None], np.arange(nb)[None, :]
    out = np.full((r, nb), -np.inf, dtype=dp.dtype)
    arg = np.zeros((r, nb), dtype=np.int32)
    for j in range(kb.shape[1]):
        src = b - kb[:, j : j + 1]
        cand = np.where(
            src >= 0, dp[rows, np.clip(src, 0, None)] + vb[:, j : j + 1], -np.inf
        )
        better = cand > out
        out = np.where(better, cand, out)
        arg = np.where(better, j, arg)
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
    @pytest.mark.parametrize(
        "r,nb,k",
        [(1, 16, 3), (4, 64, 8), (3, 200, 21), (11, 17, 5), (16, 256, 256),
         (40, 200, 21)],
    )
    @pytest.mark.parametrize("tiles", [1, 3])
    def test_matches_scalar_ref(self, r, nb, k, tiles):
        """``tiles=3`` stacks rows past one 8-row tile and, from 16 rows,
        past one row block; grid widths not a multiple of the unroll."""
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
            ("rows11", 11, 17, 5),
            ("cell_rows16_k256", 16, 256, 256),
            ("rows40", 40, 200, 21),
        ],
    )
    def test_option_mapping_bitwise(self, case, r, nb, k):
        """The one-hot option mapping in float64, bit-for-bit against the
        option-order scan: options sharing a spend, equal values among
        them (the first of maximal value wins), spends past the grid,
        rows of pads only, more options than grid points, the
        benchmark cells' (K, NB) on a few rows and on a whole leaf batch,
        and row counts of one, several and padded row blocks."""
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
