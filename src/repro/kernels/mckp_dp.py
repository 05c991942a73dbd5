"""Pallas TPU kernel for the EcoShift cluster-DP stage (paper §3.2.2).

One DP stage is a tropical ((max,+)-semiring) convolution over the budget
grid:

    out[b] = max_{0<=k<=b} dp[b-k] + f[k],        b, k in [0, NB)

with NB the points of the budget grid.  The fused round runs it for every
leaf class super-stage and for every frontier combine of its aggregation
tree — promoted here from the paper's host-Python loop to an accelerator
kernel, see DESIGN.md §8.1 and §14.

TPU mapping
-----------
(max,+) cannot use the MXU (no tropical matmul), so this is a VPU kernel
over (8, 128)-aligned tiles:

 * Rows (independent DPs) ride the sublanes, the budget grid the lanes:
   each grid step holds one ``[block, NBp]`` row block (NBp = NB rounded
   up to 128 lanes, ``block`` a multiple of 8) of ``dp`` and ``f`` in
   VMEM.  A small batch runs whole in one block (a leaf stage's 16 rows
   on 256 points: ``[16, 256]``); a batch past ``_BLOCK_REGS`` registers
   splits into the fewest blocks that fit.
 * For each shift ``k`` every row of the block needs ``dp[b - k]`` — one
   uniform lane rotation (``pltpu.roll``) with the wrapped-around lanes
   ``b < k`` masked to -inf, so no load is ever lane-unaligned.
 * The per-row scalar ``f[k]`` comes from the 128-lane-aligned block that
   holds lane ``k``, rotated so that lane lands at 0 and broadcast along
   the lanes.
 * A trip of the shift loop takes ``U`` shifts, one to each of ``U``
   partial ``(value, shift)`` accumulators, so their rotations and adds
   are independent work the scheduler overlaps: a trip's cost is latency,
   not data.  ``U`` (a power of two up to 8) and the block come from the
   static shape alone (``_loop_shape``): the partials' registers stay
   within half the register file, so a ``[16, 256]`` block (4 registers)
   takes 4 shifts a trip and a frontier combine's ``[8, 4096]`` tile (32
   registers) one: a plain one-shift loop.  Shifts past the count, up to a multiple
   of ``U``, read ``f = -inf`` and never win.
 * Argmax is tracked alongside in int32: each partial walks its shifts in
   descending order and keeps the first maximizer, and the partials merge
   by "larger value wins; on equal values, the larger shift", so ties
   resolve to the largest shift overall, the values are the same single
   IEEE adds ``dp[b - k] + f[k]``, and both outputs are bitwise those of
   one descending scan; ``-1`` where nothing beats -inf, mapped to 0 by
   callers.

Every index is explicit int32, so the kernel lowers the same under x64
(CPU interpret mode, float64 values) and on the chip (float32 values).
The sparse-option stage of the fused round reuses the same kernel: its
options are mapped onto a dense per-row curve first, and the kernel's
winning shifts back to option indices after, each by a broadcast compare
of the option/grid one-hot and a max or min along the option axis, which
XLA fuses into vector code (no TPU scatter or gather).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: (sublane, lane) tile of one 32-bit vector register
_ROWS = 8
_LANES = 128
_VREG = _ROWS * _LANES
#: vector registers one row block may span, so a small batch runs whole
_BLOCK_REGS = 8
#: vector registers the loop's partial (value, shift) accumulators may
#: hold (half of the 64-register file; the rest for dp and temporaries)
_CARRY_REGS = 32
#: most shifts a trip unrolls; a power of two, so it divides 128
_MAX_UNROLL = 8


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _loop_shape(rows: int, lanes: int) -> tuple[int, int]:
    """``(rows per block, shifts per trip)`` for ``rows`` DPs on ``lanes``
    (a multiple of 128) grid points, from the shape alone.

    Rows go into as few blocks as keep a block within ``_BLOCK_REGS``
    registers (at least one 8-row tile).  Each trip then takes the largest
    power-of-two count of shifts whose partial accumulators, a value and a
    shift register set each, stay within ``_CARRY_REGS``: a leaf stage's
    ``[16, 256]`` block (4 registers) takes 4 shifts a trip, a frontier
    combine's ``[8, 4096]`` tile (32 registers) one.
    """
    rows8 = _round_up(rows, _ROWS)
    cap = max(_ROWS, _BLOCK_REGS * _VREG // lanes // _ROWS * _ROWS)
    n_blocks = -(-rows8 // cap)
    block = _round_up(-(-rows8 // n_blocks), _ROWS)
    regs = block * lanes // _VREG
    unroll = 1
    while unroll < _MAX_UNROLL and 2 * (2 * unroll) * regs <= _CARRY_REGS:
        unroll *= 2
    return block, unroll


def _merge(a, b):
    """The better of two partial ``(value, shift)`` maxima: the larger
    value, and on equal values the larger shift."""
    take = (b[0] > a[0]) | ((b[0] == a[0]) & (b[1] > a[1]))
    return jnp.where(take, b[0], a[0]), jnp.where(take, b[1], a[1])


def _maxplus_kernel(dp_ref, f_ref, out_ref, arg_ref, *, n_trips, unroll):
    dp = dp_ref[...]
    neg = jnp.asarray(-jnp.inf, dp.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, dp.shape, 1)
    top = jnp.int32((n_trips - 1) * unroll)

    def body(i, parts):
        # shifts lo .. lo + unroll - 1, one to each partial accumulator;
        # lo is a multiple of unroll, which divides 128, so all of them
        # lie in the one aligned 128-lane block of f at base
        lo = top - i * unroll
        base = pl.multiple_of((lo // _LANES) * _LANES, _LANES)
        fblk = f_ref[:, pl.ds(base, _LANES)]
        new = []
        for u, (acc, arg) in enumerate(parts):
            k = lo + u
            # f[:, k] as a [rows, 1] column: lane k % 128 rotated to lane 0
            fk = pltpu.roll(fblk, (_LANES - k % _LANES) % _LANES, 1)[:, :1]
            # dp[b - k]: uniform rotation, wrapped lanes b < k read -inf
            col = jnp.where(lane >= k, pltpu.roll(dp, k, 1), neg)
            cand = col + fk
            better = cand > acc
            new.append((jnp.where(better, cand, acc), jnp.where(better, k, arg)))
        return tuple(new)

    part0 = (jnp.full(dp.shape, neg), jnp.full(dp.shape, -1, jnp.int32))
    parts = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(n_trips), body, (part0,) * unroll
    )
    acc, arg = functools.reduce(_merge, parts)
    out_ref[...] = acc
    arg_ref[...] = arg


def _maxplus_tiles(dp, f, *, interpret: bool):
    """Row-batched dense (max,+) convolution over aligned row blocks.

    dp: [R, NB], f: [R, K] with K <= NB.  Returns ``(out [R, NB],
    arg [R, NB])`` with ``out[r, b] = max_{k < K, k <= b} dp[r, b-k] +
    f[r, k]`` and ``arg`` the largest maximizing shift, -1 where every
    candidate is -inf.  The block and the shifts a trip takes come from
    the static shape (``_loop_shape``); shifts past K, up to the trips'
    multiple of the unroll, read ``f = -inf`` and never win.
    """
    r, nb = dp.shape
    n_shifts = f.shape[1]
    nbp = _round_up(nb, _LANES)
    block, unroll = _loop_shape(r, nbp)
    rp = _round_up(r, block)
    neg = jnp.asarray(-jnp.inf, dp.dtype)
    dp_p = jnp.pad(dp, ((0, rp - r), (0, nbp - nb)), constant_values=neg)
    f_p = jnp.pad(
        f.astype(dp.dtype), ((0, rp - r), (0, nbp - n_shifts)),
        constant_values=neg,
    )
    tile = pl.BlockSpec((block, nbp), lambda i: (i, 0))
    kernel = functools.partial(
        _maxplus_kernel, n_trips=-(-n_shifts // unroll), unroll=unroll
    )
    out, arg = pl.pallas_call(
        kernel,
        grid=(rp // block,),
        in_specs=[tile, tile],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((rp, nbp), dp.dtype),
            jax.ShapeDtypeStruct((rp, nbp), jnp.int32),
        ],
        interpret=interpret,
    )(dp_p, f_p)
    return out[:r, :nb], arg[:r, :nb]


@functools.partial(jax.jit, static_argnames=("interpret",))
def maxplus_stage_pallas_batched(
    dp: jax.Array,
    kb: jax.Array,
    vb: jax.Array,
    *,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Row-batched sparse-option (max,+) stage with backpointers.

    dp: [R, NB] float; kb: [R, K] int32 spend offsets in [0, NB]
    (non-increasing per row); vb: [R, K] option values (pad options with
    ``vb = -inf``, ``kb = 0``).  Returns

        out[r, b] = max_j dp[r, b - kb[r, j]] + vb[r, j]
        arg[r, b] = first maximizing j (int32; 0 where out is -inf)

    with out-of-range gathers (kb[j] > b) reading -inf.  The first
    maximizer in option order is the largest spend among ties — the
    sparse solvers' dict-DP tie-break and the backpointer table the fused
    device-resident round backtracks through (DESIGN.md §14).  Options
    sharing one spend resolve to the first of maximal value.

    The options are mapped onto a dense per-row curve through their
    one-hot ``kb[r, j] == b``: ``f[r, b]`` is the max value of the options
    spending ``b`` (spends >= NB land nowhere).  The curve runs through the
    dense kernel in descending shift order, and each winning shift maps
    back to the first option of maximal value at that spend, a min over
    option indices.  Max and min are exact in any order and ties are
    resolved by index, so the mapping adds no rounding; every candidate
    is the same single IEEE add ``dp + vb`` as the option loop, so
    results are bitwise the option-order scan.  Keeps the input dtype
    (float64 under x64 for the bit-for-bit CPU contract; float32 on the
    chip).
    """
    if dp.ndim != 2 or kb.shape != vb.shape or kb.shape[0] != dp.shape[0]:
        raise ValueError(
            f"bad shapes dp={dp.shape} kb={kb.shape} vb={vb.shape}"
        )
    nb = dp.shape[1]
    k_opts = kb.shape[1]
    vb = vb.astype(dp.dtype)
    kb = kb.astype(jnp.int32)
    neg = jnp.asarray(-jnp.inf, dp.dtype)
    # the option mapping onto the dense curve and back, named for the
    # trace: broadcast compares of the option/grid one-hot reduced along
    # the option axis (axis 1, so each result keeps the grid on the lanes)
    with jax.named_scope("option_scatter"):
        # option j lands on grid point b iff kb[j] == b: spends >= NB land
        # nowhere.  f[r, b] = max value of the options spending b
        lands = kb[:, :, None] == jnp.arange(nb, dtype=jnp.int32)
        f = jnp.max(jnp.where(lands, vb[:, :, None], neg), axis=1)
        # top[r, j]: option j holds the maximal value of its spend, read
        # against the options sharing that spend (== f[r, kb[r, j]])
        same = kb[:, :, None] == kb[:, None, :]
        top = vb == jnp.max(jnp.where(same, vb[:, :, None], neg), axis=1)
    out, arg_k = _maxplus_tiles(dp, f, interpret=interpret)
    with jax.named_scope("option_scatter"):
        # back to option indices: the first top option whose spend is the
        # winning shift (none where arg_k < 0, which maps to 0)
        hit = top[:, :, None] & (kb[:, :, None] == arg_k[:, None, :])
        opt = jnp.arange(k_opts, dtype=jnp.int32)[None, :, None]
        arg = jnp.min(jnp.where(hit, opt, k_opts), axis=1)
        return out, jnp.where(arg_k < 0, 0, arg)


@functools.partial(jax.jit, static_argnames=("interpret",))
def maxplus_conv_pallas_batched(
    dp: jax.Array,
    f: jax.Array,
    *,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Row-batched (max,+) convolution: one kernel launch for R rows.

    dp: [R, NB], f: [R, K] with K <= NB.  out[r, b] = max_{k<=b, k<K}
    dp[r, b-k] + f[r, k], plus the per-row argmax k — the largest
    maximizing k (0 where out is -inf), the fused round's frontier
    combine's tie-break.  Rows are independent DP stages (e.g. the pairs
    of one frontier wave) sharing a single dispatch.  Keeps a float64
    input float64 (the fused round's frontier combine under x64);
    anything else runs in float32.
    """
    if dp.ndim != 2 or f.ndim != 2 or f.shape[0] != dp.shape[0]:
        raise ValueError(f"dp/f must be 2D with equal rows, got {dp.shape} {f.shape}")
    if f.shape[1] > dp.shape[1]:
        raise ValueError(f"more shifts than grid points: {f.shape} > {dp.shape}")
    dtype = jnp.float64 if dp.dtype == jnp.float64 else jnp.float32
    out, arg = _maxplus_tiles(
        dp.astype(dtype), f.astype(dtype), interpret=interpret
    )
    return out, jnp.maximum(arg, 0)
