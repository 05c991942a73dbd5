"""Property tests for the (max,+) algebra underpinning every DP solver.

The tropical semiring facts the solvers rely on (DESIGN.md §8, §11, §12):

 * (max,+) convolution is **commutative** and **associative** — the
   binary-split self-convolution and the hierarchical frontier convolution
   are only correct because operand order/grouping cannot change values;
 * the row-batched Pallas kernel ``maxplus_conv_pallas_batched`` computes
   every row's convolution as the jnp oracle does, bitwise, with a
   maximizing shift as its argmax;
 * ``aggregate_curve``'s binary-split m-fold self-convolution equals the
   naive m-fold left fold on randomized option tables.

Exactness notes: convolution *values* are two-operand sums, so
commutativity is exact in floats.  Associativity regroups three-operand
sums, and the m-fold tests regroup up to m of them — those use dyadic
(k/64) values, for which float64 addition is exact, so equality asserts
are bitwise rather than approximate.
"""

import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # image without hypothesis: property tests skip
    from _hypothesis_stub import hypothesis, st

from repro.core import curves, mckp
from repro.kernels import mckp_dp
from repro.kernels import ref as kref


def _conv(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out, _ = kref.maxplus_conv(a, b)
    return np.asarray(out)


def _rand_curve(rng: np.random.Generator, nb: int, dyadic: bool) -> np.ndarray:
    """A monotone-ish curve with f[0] = 0 (a valid DP stage operand)."""
    if dyadic:
        f = rng.integers(0, 64, size=nb).astype(np.float64) / 64.0
    else:
        f = rng.uniform(0.0, 1.0, size=nb)
    f[0] = 0.0
    return f


# ---------------------------------------------------------------------------
# Commutativity / associativity of maxplus_conv
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(8))
def test_maxplus_conv_commutative(seed):
    rng = np.random.default_rng(seed)
    nb = int(rng.integers(4, 40))
    a = _rand_curve(rng, nb, dyadic=False)
    b = _rand_curve(rng, nb, dyadic=False)
    np.testing.assert_array_equal(_conv(a, b), _conv(b, a))


@pytest.mark.parametrize("seed", range(8))
def test_maxplus_conv_associative(seed):
    """Exact on dyadic values (regrouped 3-operand sums stay bitwise)."""
    rng = np.random.default_rng(100 + seed)
    nb = int(rng.integers(4, 32))
    a, b, c = (_rand_curve(rng, nb, dyadic=True) for _ in range(3))
    np.testing.assert_array_equal(
        _conv(_conv(a, b), c), _conv(a, _conv(b, c))
    )


@hypothesis.given(seed=st.integers(0, 2**31 - 1))
@hypothesis.settings(max_examples=25, deadline=None)
def test_maxplus_conv_algebra_property(seed):
    rng = np.random.default_rng(seed)
    nb = int(rng.integers(2, 24))
    a, b, c = (_rand_curve(rng, nb, dyadic=True) for _ in range(3))
    np.testing.assert_array_equal(_conv(a, b), _conv(b, a))
    np.testing.assert_array_equal(
        _conv(_conv(a, b), c), _conv(a, _conv(b, c))
    )


def test_maxplus_conv_identity():
    """[0, -inf, ...] is the (max,+) identity — the padding row of the
    batched solver and the empty-domain frontier."""
    rng = np.random.default_rng(3)
    f = _rand_curve(rng, 17, dyadic=False)
    e = np.full(17, -np.inf)
    e[0] = 0.0
    # the jax reference computes in float32: compare at kernel precision
    f32 = f.astype(np.float32)
    np.testing.assert_array_equal(_conv(f, e), f32)
    np.testing.assert_array_equal(_conv(e, f), f32)


# ---------------------------------------------------------------------------
# The row-batched kernel == the oracle, row by row
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(3))
def test_maxplus_conv_batched_matches_ref(seed):
    """Every row of one batched launch (rows spanning two 8-row tiles,
    fewer shifts than grid points) is the oracle's convolution, bitwise,
    and its argmax realizes the value at a shift no smaller than the
    oracle's smallest maximizer (Pallas path, interpret mode on CPU)."""
    rng = np.random.default_rng(200 + seed)
    rows, nb = 11, int(rng.integers(20, 90))
    k = int(rng.integers(1, nb + 1))
    dp = np.stack([_rand_curve(rng, nb, dyadic=False) for _ in range(rows)])
    f = np.stack([_rand_curve(rng, k, dyadic=False) for _ in range(rows)])
    dp, f = dp.astype(np.float32), f.astype(np.float32)
    out, arg = mckp_dp.maxplus_conv_pallas_batched(dp, f)
    out, arg = np.asarray(out), np.asarray(arg)
    f_full = np.full((rows, nb), -np.inf, np.float32)
    f_full[:, :k] = f
    bs = np.arange(nb)
    for r in range(rows):
        out_r, arg_r = kref.maxplus_conv(dp[r], f_full[r])
        np.testing.assert_array_equal(out[r], np.asarray(out_r))
        assert np.all((arg[r] >= 0) & (arg[r] < k) & (arg[r] <= bs))
        np.testing.assert_array_equal(dp[r][bs - arg[r]] + f[r][arg[r]], out[r])
        assert np.all(arg[r] >= np.asarray(arg_r))


# ---------------------------------------------------------------------------
# Binary-split self-convolution == naive m-fold convolution
# ---------------------------------------------------------------------------


def _rand_table(rng: np.random.Generator, budget: float) -> curves.OptionTable:
    """Random option table with dyadic values (exact regrouped sums)."""
    k = int(rng.integers(1, 6))
    costs = np.unique(
        rng.integers(1, max(2, int(budget / 25)), size=k)
    ).astype(np.float64) * 25.0
    values = np.sort(rng.integers(1, 64, size=len(costs))).astype(np.float64)
    values /= 64.0
    caps = np.stack([100.0 + costs, np.full_like(costs, 100.0)], axis=-1)
    return curves.OptionTable(
        name="t",
        costs=np.concatenate([[0.0], costs]),
        values=np.concatenate([[0.0], values]),
        caps=np.concatenate([[[100.0, 100.0]], caps], axis=0),
    )


def _naive_aggregate(table, m: int, budget: float):
    """Left-fold m leaf curves — the O(m)-convolutions reference."""
    acc = mckp._AggCurve.leaf(table, budget)
    for _ in range(m - 1):
        acc = mckp._AggCurve.combine(
            acc, mckp._AggCurve.leaf(table, budget), budget
        )
    return acc


@pytest.mark.parametrize("seed", range(10))
def test_binary_split_equals_naive_mfold(seed):
    rng = np.random.default_rng(300 + seed)
    budget = float(rng.integers(4, 20)) * 25.0
    table = _rand_table(rng, budget)
    m = int(rng.integers(1, 11))
    fast = mckp.aggregate_curve(table, m, budget)
    slow = _naive_aggregate(table, m, budget)
    np.testing.assert_array_equal(fast.keys, slow.keys)
    np.testing.assert_array_equal(fast.vals, slow.vals)
    # both unwind to option multisets with identical cost/value totals
    for spend in fast.keys:
        ja, jb = [], []
        fast.unwind(float(spend), ja)
        slow.unwind(float(spend), jb)
        assert sorted(ja) == sorted(jb) or (
            np.isclose(sum(table.values[j] for j in ja),
                       sum(table.values[j] for j in jb))
            and np.isclose(sum(table.costs[j] for j in ja),
                           sum(table.costs[j] for j in jb))
        )


@hypothesis.given(
    seed=st.integers(0, 2**31 - 1), m=st.integers(1, 12)
)
@hypothesis.settings(max_examples=25, deadline=None)
def test_binary_split_property(seed, m):
    rng = np.random.default_rng(seed)
    budget = float(rng.integers(3, 16)) * 25.0
    table = _rand_table(rng, budget)
    fast = mckp.aggregate_curve(table, m, budget)
    slow = _naive_aggregate(table, m, budget)
    np.testing.assert_array_equal(fast.keys, slow.keys)
    np.testing.assert_array_equal(fast.vals, slow.vals)
