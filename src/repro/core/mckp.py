"""Multiple-choice-knapsack solvers for reclaimed-power distribution (§3.2.2).

Three roles, each certified against the others in the tests:

 * the **host reference DP** — ``solve_sparse`` (faithful Algorithm 1: a
   dict-keyed sparse DP over the distinct per-app extra-power levels,
   O(B * Σ K_i)), its group-collapsed form ``solve_sparse_grouped`` and
   the topology-aware ``solve_hierarchical``;
 * the **device pipeline** — ``solve_grouped_fused`` and
   ``solve_hierarchical_fused``: the leaf DPs, the frontier tree and the
   backtrack resident on the accelerator (DESIGN.md §14/§16/§17),
   bit-for-bit the host DP's decisions with float64 values (the CPU) and
   within DESIGN.md §14's value bound with float32 values (a TPU); each
   returns None where the round must fall back to the host DP;
 * ``brute_force`` — exhaustive search, the oracle for small instances.

All solvers return allocations in *watts spent per receiver* plus the cap
pair realizing it, and they all respect the monotone-upgrade model: a
receiver may always take the zero-cost baseline option.

**Group-collapsed solving** (DESIGN.md §11): real clusters replicate a small
number of behaviour classes across thousands of nodes, so receivers sharing
one option table collapse into a :class:`GroupedOptions` with multiplicity
``m``.  ``solve_sparse_grouped`` solves the bounded MCKP: each group's
m-fold aggregate curve is built by binary-split (max,+) self-convolution
(O(log m) convolutions), then one sparse DP runs over the ~G group
super-stages instead of the N receivers — bit-for-bit equal to
``solve_sparse`` on the name-sorted ungrouped expansion.

**Hierarchical solving** (DESIGN.md §12): facilities cascade caps down a
site → rack/PDU tree, so :func:`solve_hierarchical` turns each domain's
group-collapsed aggregates into a *capped value-vs-spend frontier* and an
upper-level DP convolves sibling frontiers to split every parent budget
subject to each domain's local cap.  A single root domain with cap >= the
cluster budget reproduces the flat grouped solve bit-for-bit.

Determinism contract: receivers with *byte-identical* option tables are
interchangeable, so every optimum is degenerate under permutations of their
picks.  ``solve_sparse`` canonicalizes — identical-table stages exchange
their chosen options so costs ascend in stage order, and ``total_value`` /
``spent`` are re-accumulated in stage order — which is exactly the form the
group-collapsed solver reproduces.  (Parity assumes option costs are well
above the 1e-6 W state-merge tolerance; true for watt-granular cap grids.)
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from collections import OrderedDict
from typing import MutableMapping, Sequence

import numpy as np

from repro.core.curves import OptionTable
from repro.core.spans import Span


class LRUCache(MutableMapping):
    """Bounded mapping with least-recently-used eviction.

    Drop-in for the plain-dict warm caches (aggregate curves, frontiers,
    pick multisets): ``get``/``[]`` refresh recency, inserts beyond
    ``maxsize`` evict the coldest entry.  Keeps long scenarios from growing
    warm state without bound across distinct budgets/digests.
    """

    def __init__(self, maxsize: int = 512):
        if maxsize < 1:
            raise ValueError("maxsize must be >= 1")
        self.maxsize = maxsize
        self._d: OrderedDict = OrderedDict()

    def __getitem__(self, key):
        val = self._d[key]
        self._d.move_to_end(key)
        return val

    def get(self, key, default=None):
        try:
            return self[key]
        except KeyError:
            return default

    def __setitem__(self, key, val):
        self._d[key] = val
        self._d.move_to_end(key)
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)

    def __delitem__(self, key):
        del self._d[key]

    def __iter__(self):
        return iter(self._d)

    def __len__(self):
        return len(self._d)

    def clear(self):
        self._d.clear()

    def resize(self, maxsize: int) -> None:
        """Shrink or grow the bound in place, evicting coldest entries as
        needed.  In-place matters: solver state (e.g. ``HierState``) holds
        references to the same cache objects, so resizing must not rebind."""
        if maxsize < 1:
            raise ValueError("maxsize must be >= 1")
        self.maxsize = maxsize
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)


@dataclasses.dataclass
class MCKPSolution:
    """Solution of one distribution round."""

    total_value: float  # Σ_i I_i  (N * average improvement)
    spent: float  # watts used out of the budget
    #: per-receiver picks: name -> (cost_watts, value, (c, g))
    picks: dict[str, tuple[float, float, tuple[float, float]]]
    #: hierarchical solves only: domain name -> watts spent inside it
    domain_spent: dict[str, float] | None = None

    def average_improvement(self) -> float:
        n = len(self.picks)
        return self.total_value / n if n else 0.0


# ---------------------------------------------------------------------------
# Faithful Algorithm 1 (sparse dict DP)
# ---------------------------------------------------------------------------


def _qkey(u: float) -> float:
    """State key: costs within 1e-6 W merge into one DP state.

    Defined as floor(u * 1e6 + 0.5) * 1e-6 so the scalar form and the
    vectorized :func:`_qkey_np` are bitwise identical (same float64 ops) —
    the grouped solver's array DP and the ungrouped dict DP must agree on
    every state key.  For grid-exact watt costs the key equals the sum
    itself, so per-step rounding order cannot diverge between the two.
    """
    return math.floor(u * 1e6 + 0.5) * 1e-6


def _qkey_np(u: np.ndarray) -> np.ndarray:
    """Vectorized :func:`_qkey` (bitwise-identical float64 pipeline)."""
    return np.floor(u * 1e6 + 0.5) * 1e-6


def table_digest(opt: OptionTable) -> tuple:
    """Content identity of an option table (costs, values, caps bytes).

    Receivers whose tables digest equally are *interchangeable* in any MCKP
    — permuting their picks preserves value and feasibility.  This is the
    group key of the collapsed solvers, and the equivalence class within
    which ``solve_sparse`` canonicalizes its assignment.  Note a
    multiplicatively-slowed straggler digests equally to its healthy peers:
    relative improvements are invariant under constant slowdown.

    Memoized on the (frozen, content-immutable) table instance so warm
    controllers pay the bytes conversion once per table, not once per round.
    """
    d = opt.__dict__.get("_digest")
    if d is None:
        d = (opt.costs.tobytes(), opt.values.tobytes(), opt.caps.tobytes())
        object.__setattr__(opt, "_digest", d)
    return d


def _pick_tuples(opt: OptionTable) -> list:
    """Per-option ``(cost, value, (c, g))`` pick tuples, memoized on the
    table — the one representation every solver's ``picks`` dict uses."""
    pt = opt.__dict__.get("_pick_tuples")
    if pt is None:
        pt = [
            (float(c), float(v), (float(cc[0]), float(cc[1])))
            for c, v, cc in zip(opt.costs, opt.values, opt.caps)
        ]
        object.__setattr__(opt, "_pick_tuples", pt)
    return pt


_group_counter = itertools.count(1)


def _group_token(g: "GroupedOptions") -> int:
    """Process-unique identity token of one (immutable) GroupedOptions.

    Incremental controllers reuse group objects across rounds while their
    membership is unchanged, so token tuples are cheap round-over-round
    cache keys for merged-class plans (unlike ``id()``, tokens are never
    reused after garbage collection)."""
    t = g.__dict__.get("_token")
    if t is None:
        t = next(_group_counter)
        object.__setattr__(g, "_token", t)
    return t


def _canonical_solution(
    options: Sequence[OptionTable], js: list[int]
) -> MCKPSolution:
    """Assemble a solution from per-stage option choices in canonical form.

    Identical-table stages (same :func:`table_digest`) exchange their
    chosen options so option indices ascend in stage order, and
    ``total_value`` / ``spent`` are accumulated stage by stage — the one
    deterministic representative of the optimum's permutation class, and
    exactly what :func:`solve_sparse_grouped` reconstructs.
    """
    by_digest: dict[tuple, list[int]] = {}
    for i, opt in enumerate(options):
        by_digest.setdefault(table_digest(opt), []).append(i)
    for idxs in by_digest.values():
        if len(idxs) > 1:
            for i, j in zip(idxs, sorted(js[i] for i in idxs)):
                js[i] = j
    picks: dict[str, tuple[float, float, tuple[float, float]]] = {}
    total = 0.0
    spent = 0.0
    for i, opt in enumerate(options):
        j = js[i]
        picks[opt.name] = (
            float(opt.costs[j]),
            float(opt.values[j]),
            (float(opt.caps[j, 0]), float(opt.caps[j, 1])),
        )
        total += float(opt.values[j])
        spent += float(opt.costs[j])
    return MCKPSolution(total_value=total, spent=spent, picks=picks)


def solve_sparse(options: Sequence[OptionTable], budget: float) -> MCKPSolution:
    """Paper Algorithm 1 with parent-pointer backtracking.

    States are keyed by *used power* (floats straight from the option
    tables — no budget discretization), exactly like the pseudo-code's
    ``DP`` dict.  Costs within 1e-6 W are merged to keep the state count
    equal to the number of distinct achievable sums.  The returned solution
    is canonicalized (see :func:`_canonical_solution`) so interchangeable
    receivers always get their picks in ascending-cost stage order.
    """
    qkey = _qkey
    # DP: used -> (score, parent_used, option_index)
    dp: dict[float, tuple[float, float, int]] = {0.0: (0.0, -1.0, -1)}
    stages: list[dict[float, tuple[float, float, int]]] = []
    for opt in options:
        ndp: dict[float, tuple[float, float, int]] = {}
        for u, (score, _, _) in dp.items():
            for j in range(opt.k):
                e = float(opt.costs[j])
                if u + e > budget + 1e-9:
                    continue
                key = qkey(u + e)
                s = score + float(opt.values[j])
                cur = ndp.get(key)
                if cur is None or s > cur[0]:
                    ndp[key] = (s, u, j)
        stages.append(ndp)
        dp = ndp

    # best end state, then walk parents backwards
    best_u = max(dp, key=lambda u: dp[u][0])
    js: list[int] = [0] * len(options)
    u = best_u
    for i in range(len(options) - 1, -1, -1):
        _, parent, j = stages[i][qkey(u)]
        js[i] = j
        u = parent
    return _canonical_solution(options, js)


# ---------------------------------------------------------------------------
# Group-collapsed sparse DP (bounded MCKP via binary-split multiplicity)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GroupedOptions:
    """One behaviour class: a shared option table with its member receivers.

    All members share the table (same surface identity, baseline and
    slowdown class), so the group acts as a bounded multiple-choice item
    with multiplicity ``m = len(members)``.
    """

    table: OptionTable
    members: tuple[str, ...]

    @property
    def m(self) -> int:
        return len(self.members)


def expand_groups(groups: Sequence[GroupedOptions]) -> list[OptionTable]:
    """Ungrouped, name-sorted expansion (the parity reference ordering)."""
    out = [
        dataclasses.replace(g.table, name=name)
        for g in groups
        for name in g.members
    ]
    out.sort(key=lambda o: o.name)
    return out


def collapse_receivers(
    names: Sequence[str],
    surfaces: Sequence,
    baselines: Sequence[tuple[float, float]],
    build_table,
) -> list[GroupedOptions]:
    """Collapse aligned receiver columns into behaviour-class groups.

    Receivers sharing (surface identity, baseline) form one class;
    ``build_table(surface, baseline)`` is called once per class (a warm
    cache lookup on the controller path, a fresh ``curves.build_options``
    on the pure-policy path).
    """
    classes: dict[tuple, list] = {}
    for name, surf, base in zip(names, surfaces, baselines):
        key = (id(surf), base[0], base[1])
        slot = classes.get(key)
        if slot is None:
            classes[key] = [surf, (float(base[0]), float(base[1])), [name]]
        else:
            slot[2].append(name)
    return [
        GroupedOptions(
            table=build_table(surf, base), members=tuple(sorted(members))
        )
        for surf, base, members in classes.values()
    ]


def _dedupe_first_max(
    keys: np.ndarray, vals: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Per distinct key keep the max value — first occurrence on ties.

    Mirrors the dict DP's ``cur is None or s > cur[0]`` update over the
    candidates in array order.  Returns (sorted unique keys, selector into
    the input arrays).
    """
    order = np.lexsort((np.arange(len(keys)), -vals, keys))
    k_sorted = keys[order]
    first = np.ones(len(order), dtype=bool)
    first[1:] = k_sorted[1:] != k_sorted[:-1]
    sel = order[first]
    return keys[sel], sel


def _micro_int(keys: np.ndarray) -> np.ndarray | None:
    """Exact micro-watt integers of quantized spend keys, or None.

    Every spend key in the sparse solvers is a :func:`_qkey` multiple of
    1e-6, i.e. ``float64(n) * 1e-6`` for an integer ``n`` — so ``n`` is
    recoverable exactly and ``float64(n) * 1e-6`` reproduces the key
    *bitwise*.  Returns None when any key fails the round-trip (non-qkey
    floats), which routes the caller to the generic lexsort path.
    """
    ints = np.round(keys * 1e6).astype(np.int64)
    recon = ints.astype(np.float64) * 1e-6
    if recon.tobytes() != keys.tobytes():
        return None
    return ints


#: int-lattice fast path bound: skip when the dense spend grid would exceed
#: this many states (degenerate tiny-gcd key sets fall back to lexsort)
_INT_LATTICE_MAX_STATES = 1 << 21

#: spend-grid chunk for the [K, chunk] candidate tile of the int path
_INT_LATTICE_CHUNK = 1 << 14


def _maxplus_pair(
    a_keys: np.ndarray,
    a_vals: np.ndarray,
    b_keys: np.ndarray,
    b_vals: np.ndarray,
    budget: float,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(max,+)-convolve two sparse value-vs-spend curves under ``budget``.

    Returns ``(keys, vals, left_keys, right_keys)``: the deduped combined
    curve (ascending quantized spends, best value each) plus, per state,
    the (a, b) spend split realizing it.  Tie-breaking is the scalar dict
    DP's: among equal (key, value) candidates the smallest a-spend wins
    (first occurrence in (a index, b index) order).

    This is the one convolution primitive behind ``_AggCurve.combine``,
    the super-stage DP and the hierarchical frontier tree.  When both key
    sets sit on a common integer watt lattice (grid-aligned costs — the
    production case) the outer-product + lexsort dedupe collapses to a
    dense gather + argmax over the integer spend grid, bitwise identical
    and ~10x faster; otherwise the generic lexsort path runs.
    """
    if len(a_keys) * len(b_keys) > 2048:
        # the int-lattice setup only pays off past a few thousand candidates
        ia = _micro_int(a_keys)
        ib = _micro_int(b_keys) if ia is not None else None
        if ib is not None and len(ia) and len(ib):
            out = _maxplus_pair_int(
                ia, a_keys, a_vals, ib, b_keys, b_vals, budget
            )
            if out is not None:
                return out
    # generic path: full outer product, feasibility prune, first-max dedupe
    raw = (a_keys[:, None] + b_keys[None, :]).ravel()
    vals = (a_vals[:, None] + b_vals[None, :]).ravel()
    feas = np.flatnonzero(raw <= budget + 1e-9)
    keys, sel = _dedupe_first_max(_qkey_np(raw[feas]), vals[feas])
    sel = feas[sel]
    nb = len(b_keys)
    return keys, vals[sel], a_keys[sel // nb], b_keys[sel % nb]


def _maxplus_pair_int(
    ia: np.ndarray,
    a_keys: np.ndarray,
    a_vals: np.ndarray,
    ib: np.ndarray,
    b_keys: np.ndarray,
    b_vals: np.ndarray,
    budget: float,
) -> tuple | None:
    """Integer-lattice (max,+) pair convolution (see :func:`_maxplus_pair`).

    Spends become indices on the gcd-pitch grid; each output state gathers
    its candidates as ``a_dense[t - b] + b_val`` and an argmax with
    last-maximizer tie-breaking reproduces the dict DP's first-max over
    (a asc, b asc) candidate order (for a fixed sum, ascending a-spend is
    descending b-spend).  Returns None when the grid would be too large.
    """
    g = int(np.gcd(np.gcd.reduce(ia), np.gcd.reduce(ib)))
    if g <= 0:
        # all spends are zero: single state (0, best value pair)
        g = 1
    # largest feasible grid index (micro-watt bound mirrors `<= budget+1e-9`)
    bound = np.floor((budget + 1e-9) * 1e6 / g)
    if not np.isfinite(bound):
        return None
    tmax = min(int(bound), int(ia.max() // g + ib.max() // g))
    if tmax < 0:
        # no feasible state at all (negative budget cannot happen upstream,
        # but keep the generic path authoritative for it)
        return None
    if tmax + 1 > _INT_LATTICE_MAX_STATES:
        return None
    nb = tmax + 1
    iag = ia // g
    ibg = ib // g
    keep_a = np.flatnonzero(iag <= tmax)
    keep_b = np.flatnonzero(ibg <= tmax)
    if not len(keep_a) or not len(keep_b):
        return None
    kmax = int(ibg[keep_b].max())
    # a side densified on the grid, left-padded by kmax so every gather
    # index t - kb + kmax is in-bounds (holes and padding are -inf)
    a_pad = np.full(nb + kmax, -np.inf)
    a_pos = np.zeros(nb, dtype=np.int64)
    a_pad[iag[keep_a] + kmax] = a_vals[keep_a]
    a_pos[iag[keep_a]] = keep_a
    # b options in descending-spend order: a plain row argmax then picks,
    # among ties, the largest b spend == the smallest a spend — the dict
    # DP's first max in (a asc, b asc) candidate order
    kbr = ibg[keep_b][::-1].copy()
    vbr = b_vals[keep_b][::-1].copy()
    k = len(kbr)

    out_vals = np.empty(nb, dtype=np.float64)
    out_jr = np.empty(nb, dtype=np.int64)
    for t0 in range(0, nb, _INT_LATTICE_CHUNK):
        t = np.arange(t0, min(t0 + _INT_LATTICE_CHUNK, nb))
        idx = t[:, None] - kbr[None, :] + kmax  # [chunk, K], all in-bounds
        cand = a_pad[idx]
        cand += vbr[None, :]
        jr = np.argmax(cand, axis=1)
        out_jr[t] = jr
        out_vals[t] = cand[np.arange(len(t)), jr]

    feas = np.flatnonzero(out_vals > -np.inf)
    jr = out_jr[feas]
    ta = feas - kbr[jr]
    keys = ((feas * g).astype(np.float64)) * 1e-6
    return (
        keys,
        out_vals[feas],
        a_keys[a_pos[ta]],
        b_keys[keep_b[k - 1 - jr]],
    )


class _AggCurve:
    """Sparse aggregate curve of ``t`` copies of one option table.

    Columns over the curve's states (ascending spend key): ``keys`` are
    quantized spends, ``vals`` the best achievable value at each.  For a
    leaf curve (t == 1) ``back`` holds option indices; for a combined curve
    ``back_left`` / ``back_right`` hold the (left, right) spend split, so
    :meth:`unwind` can walk the binary-split tree back down to the multiset
    of single-receiver picks.  All convolutions are vectorized outer
    (max,+) products deduped by :func:`_dedupe_first_max` — the same
    candidate order and tie-breaking as the scalar dict DP.
    """

    __slots__ = ("keys", "vals", "back", "back_left", "back_right", "left", "right")

    def __init__(self, keys, vals, back=None, back_left=None, back_right=None,
                 left=None, right=None):
        self.keys: np.ndarray = keys
        self.vals: np.ndarray = vals
        self.back = back
        self.back_left = back_left
        self.back_right = back_right
        self.left: _AggCurve | None = left
        self.right: _AggCurve | None = right

    @staticmethod
    def leaf(table: OptionTable, budget: float) -> "_AggCurve":
        feas = np.flatnonzero(table.costs <= budget + 1e-9)
        keys = _qkey_np(table.costs[feas])
        _, sel = _dedupe_first_max(keys, table.values[feas])
        return _AggCurve(
            keys=keys[sel], vals=table.values[feas][sel], back=feas[sel]
        )

    @staticmethod
    def combine(a: "_AggCurve", b: "_AggCurve", budget: float) -> "_AggCurve":
        keys, vals, left, right = _maxplus_pair(
            a.keys, a.vals, b.keys, b.vals, budget
        )
        return _AggCurve(
            keys=keys,
            vals=vals,
            back_left=left,
            back_right=right,
            left=a,
            right=b,
        )

    def _at(self, spend: float) -> int:
        i = int(np.searchsorted(self.keys, spend))
        if i >= len(self.keys) or self.keys[i] != spend:
            raise KeyError(f"aggregate curve has no state at {spend!r}")
        return i

    def unwind(self, spend: float, out: list[int]) -> None:
        """Collect the option-index multiset realizing ``spend``."""
        i = self._at(spend)
        if self.left is None:
            out.append(int(self.back[i]))
        else:
            self.left.unwind(float(self.back_left[i]), out)
            self.right.unwind(float(self.back_right[i]), out)


def aggregate_curve(
    table: OptionTable, m: int, budget: float,
    chain: list[_AggCurve] | None = None,
) -> _AggCurve:
    """m-fold (max,+) self-convolution of a table's sparse staircase.

    Binary split: O(log m) pairwise convolutions build the doubling chain
    P_1, P_2, P_4, ... and the set bits of ``m`` combine into the final
    curve.  State count stays bounded by the distinct achievable sums
    <= budget, so each convolution is one small vectorized outer product.

    ``chain`` optionally persists the doubling chain across calls (keyed by
    (digest, budget) in ``_class_curves``): the powers are multiplicity-
    independent, so when membership churn shifts a class from m to m', only
    the popcount(m') set-bit combines rerun — not the whole chain.
    """
    if chain is None:
        chain = []
    if not chain:
        chain.append(_AggCurve.leaf(table, budget))
    acc: _AggCurve | None = None
    bit = m
    i = 0
    while bit:
        if i >= len(chain):
            chain.append(_AggCurve.combine(chain[-1], chain[-1], budget))
        if bit & 1:
            acc = (
                chain[i] if acc is None
                else _AggCurve.combine(acc, chain[i], budget)
            )
        bit >>= 1
        i += 1
    assert acc is not None
    return acc


def _merge_classes(groups: Sequence[GroupedOptions]) -> list[list]:
    """Merge interchangeable groups (equal table content) into classes.

    Returns ``[table, members, digest]`` triples sorted by min member name —
    the deterministic class order every grouped/hierarchical solver shares.
    """
    merged: dict[tuple, list] = {}
    for g in groups:
        d = table_digest(g.table)
        slot = merged.get(d)
        if slot is None:
            merged[d] = [g.table, list(g.members), d]
        else:
            slot[1].extend(g.members)
    return sorted(merged.values(), key=lambda s: min(s[1]))


class _LeafPlan:
    """Merged-class layout of one behaviour-class set.

    Precomputes everything about the *stage structure* that is independent
    of budget and spends: the digest-merged classes in canonical order
    (sorted by min member name, members name-sorted within each class), the
    ``layout`` content key of the frontier caches, and the permutation
    taking class-concatenated members to the globally name-sorted order the
    canonical assembly uses.  Plans are cached by the group-token tuple so
    incremental controllers reusing unchanged ``GroupedOptions`` objects
    skip the per-round merge + sorts entirely.
    """

    __slots__ = ("classes", "layout", "names_sorted", "order", "key")

    def __init__(self, classes, layout, names_sorted, order, key):
        self.classes: list[list] = classes
        self.layout: tuple = layout
        self.names_sorted: list[str] = names_sorted
        self.order: np.ndarray = order
        #: group-token tuple when plan-cached (None on ephemeral plans)
        self.key: tuple | None = key


def _leaf_plan(
    groups: Sequence[GroupedOptions],
    plan_cache: MutableMapping | None = None,
) -> _LeafPlan:
    """Build (or fetch) the :class:`_LeafPlan` of a behaviour-class set."""
    key = None
    if plan_cache is not None:
        key = tuple(sorted(_group_token(g) for g in groups))
        hit = plan_cache.get(key)
        if hit is not None:
            return hit
    classes = _merge_classes(groups)
    for slot in classes:
        slot[1].sort()
    concat = [nm for _, members, _ in classes for nm in members]
    if concat:
        arr = np.asarray(concat)
        order = np.argsort(arr, kind="stable")
        names_sorted = arr[order].tolist()
    else:
        order = np.empty(0, dtype=np.int64)
        names_sorted = []
    plan = _LeafPlan(
        classes=classes,
        layout=tuple((d, len(m)) for _, m, d in classes),
        names_sorted=names_sorted,
        order=order,
        key=key,
    )
    if plan_cache is not None:
        plan_cache[key] = plan
    return plan


def _curve_cutoff(budget: float) -> float:
    """Canonical aggregate-curve cutoff: the smallest power-of-two multiple
    of 64 W at or above ``budget``.

    Aggregate curves truncated to any cutoff >= the DP budget produce the
    *same* feasible states, values and backtracked multisets (costs are
    non-negative, so an over-cutoff state can never parent a feasible one,
    and dropping it changes no candidate order among survivors).  Keying
    curves and chains by this quantized cutoff instead of the raw budget
    keeps them warm while per-domain headroom drifts watt-by-watt under
    failures and deratings — the curve caches then miss only on genuine
    class changes, not on accounting noise.
    """
    b = 64.0
    while b < budget:
        b *= 2.0
    return b


def _class_curves(
    classes: Sequence[list],
    budget: float,
    curve_cache: MutableMapping | None,
    chain_cache: MutableMapping | None = None,
) -> tuple[list[_AggCurve], list[tuple]]:
    """m-fold aggregate curve per class, memoized by (digest, m, budget).

    ``chain_cache`` persists the multiplicity-independent doubling chains
    by (digest, budget) — kept apart from ``curve_cache`` because churny
    (digest, m) keys would otherwise evict the far-more-valuable chains.
    Returns the curves plus their content cache keys (the pick-multiset
    cache reuses them)."""
    if chain_cache is None:
        chain_cache = curve_cache
    cutoff = _curve_cutoff(budget)
    qc = _qkey(cutoff)
    curves_: list[_AggCurve] = []
    keys: list[tuple] = []
    for table, members, d in classes:
        key = (d, len(members), qc)
        curve = curve_cache.get(key) if curve_cache is not None else None
        if curve is None:
            chain = None
            if chain_cache is not None:
                # membership churn (m -> m') then reruns only the set-bit
                # combines, never the whole chain
                ckey = (d, "powers", qc)
                chain = chain_cache.get(ckey)
                if chain is None:
                    chain = []
                    chain_cache[ckey] = chain  # type: ignore[index]
            curve = aggregate_curve(table, len(members), cutoff, chain=chain)
            if curve_cache is not None:
                curve_cache[key] = curve  # type: ignore[index]
        curves_.append(curve)
        keys.append(key)
    return curves_, keys


def _superstage_dp(
    stage_curves: Sequence[tuple[np.ndarray, np.ndarray]], budget: float
) -> tuple[np.ndarray, np.ndarray, list]:
    """Sparse DP over (keys, vals) super-stages under ``budget``.

    Each stage is one vectorized outer (max,+) product over
    [states x stage spends].  Stages may be class aggregate curves (grouped
    solve) or whole domain frontiers (hierarchical solve).  Returns the
    final ``(dp_keys, dp_vals, stages)`` where each backtracking stage is a
    (keys, parent spend, stage spend) triple.
    """
    dp_keys = np.zeros(1, dtype=np.float64)
    dp_vals = np.zeros(1, dtype=np.float64)
    stages: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    for c_keys, c_vals in stage_curves:
        # keys come back ascending from the dedupe, so the stage arrays
        # are searchsorted-ready as-is
        keys, vals, parents, spends = _maxplus_pair(
            dp_keys, dp_vals, c_keys, c_vals, budget
        )
        stages.append((keys, parents, spends))
        dp_keys = keys
        dp_vals = vals
    return dp_keys, dp_vals, stages


class _IntStages:
    """Backtracking record of one leaf solved by the *batched* integer-
    lattice super-stage DP (:func:`_superstage_dp_batch`).

    Holds, per stage, the dense winner table over the leaf's spend grid
    plus the descending-spend stage key arrays; :meth:`backtrack` walks
    them exactly like :func:`_backtrack_superstages` walks sparse stage
    tuples — same states, same spends, bitwise.
    """

    __slots__ = ("g", "win", "kb_desc", "keys_desc", "nstages")

    def __init__(self, g, win, kb_desc, keys_desc, nstages):
        self.g = g
        self.win = win
        self.kb_desc = kb_desc
        self.keys_desc = keys_desc
        self.nstages = nstages

    def backtrack(self, u: float) -> list[float]:
        t = int(round(u * 1e6)) // self.g
        spends = [0.0] * self.nstages
        for s in range(self.nstages - 1, -1, -1):
            j = int(self.win[s][t])
            spends[s] = float(self.keys_desc[s][j])
            t -= int(self.kb_desc[s][j])
        return spends


def _backtrack_superstages(stages, u: float) -> list[float]:
    """Walk the super-stage DP backwards from end state ``u``: the per-stage
    spends realizing it (stage order)."""
    if isinstance(stages, _IntStages):
        return stages.backtrack(u)
    spends: list[float] = [0.0] * len(stages)
    for i in range(len(stages) - 1, -1, -1):
        keys, parents, spends_stage = stages[i]
        pos = int(np.searchsorted(keys, u))
        spends[i] = float(spends_stage[pos])
        u = float(parents[pos])
    return spends


def _superstage_dp_batch(
    jobs: Sequence[tuple[Sequence[tuple[np.ndarray, np.ndarray]], float]],
) -> list[tuple[np.ndarray, np.ndarray, _IntStages]] | None:
    """Solve many leaves' super-stage DPs in one vectorized pass.

    ``jobs`` is a list of (stage curves, eff budget) pairs — one per dirty
    leaf.  All leaves advance through their stages *together*: stage ``s``
    of every leaf is a single [L, K, NB] gather + argmax on the per-leaf
    integer spend lattice, replacing L x S per-leaf convolution calls with
    S batched numpy ops (the host analogue of the fused pipeline's
    ``maxplus_stage_pallas_batched`` leaf scan).  Per-leaf results —
    frontier keys, values and backtracking stages — are **bitwise
    identical** to running :func:`_superstage_dp` on each leaf alone: the
    candidate sets, float64 adds and (value desc, a-spend asc)
    tie-breaking are data-parallel across leaves, padding rows are exact
    identities (+0.0), and per-leaf feasibility masks mirror the
    per-stage pruning.  Returns None when any leaf's keys leave the
    integer lattice or the padded grid would be degenerate — callers then
    fall back to the per-leaf path.
    """
    L = len(jobs)
    per_leaf = []
    nb_max = 1
    s_max = 1
    k_max = 1
    for stage_curves, eff in jobs:
        ints = []
        g = 0
        for ck, cv in stage_curves:
            ia = _micro_int(ck)
            if ia is None or not len(ia):
                return None
            ints.append(ia)
            g = int(np.gcd(g, np.gcd.reduce(ia)))
        if g <= 0:
            g = 1
        bound = np.floor((eff + 1e-9) * 1e6 / g)
        if not np.isfinite(bound) or bound < 0:
            return None
        tmax = int(bound)
        if tmax + 1 > _INT_LATTICE_MAX_STATES // max(1, L):
            return None
        nb_max = max(nb_max, tmax + 1)
        s_max = max(s_max, len(stage_curves))
        stages_desc = []
        for ia, (ck, cv) in zip(ints, stage_curves):
            keep = np.flatnonzero(ia // g <= tmax)
            if not len(keep):
                return None
            kb = (ia[keep] // g)[::-1].copy()
            stages_desc.append(
                (kb, cv[keep][::-1].copy(), ck[keep][::-1].copy())
            )
            k_max = max(k_max, len(kb))
        per_leaf.append((g, tmax, stages_desc))

    kmax_glob = 0
    for g, tmax, stages_desc in per_leaf:
        for kb, _, _ in stages_desc:
            kmax_glob = max(kmax_glob, int(kb[0]) if len(kb) else 0)
    if L * nb_max * k_max > _INT_LATTICE_MAX_STATES * 8:
        # the per-stage [L, NB, K] candidate tile would be huge; the
        # per-leaf path (chunked _maxplus_pair_int) handles such grids
        return None

    dp = np.full((L, kmax_glob + nb_max), -np.inf)
    dp[:, kmax_glob] = 0.0
    t_grid = np.arange(nb_max)
    leaf_idx = np.arange(L)[:, None, None]
    results_win: list[np.ndarray] = []
    for s in range(s_max):
        kbr = np.zeros((L, k_max), dtype=np.int64)
        vbr = np.full((L, k_max), -np.inf)
        for li, (g, tmax, stages_desc) in enumerate(per_leaf):
            if s < len(stages_desc):
                kb, vb, _ = stages_desc[s]
                kbr[li, : len(kb)] = kb
                vbr[li, : len(vb)] = vb
            else:
                vbr[li, 0] = 0.0  # identity stage: spend 0, value +0.0
        # [L, NB, K] layout: the options axis is contiguous, so the
        # tie-breaking argmax (first max over descending spends) is a
        # cache-friendly row reduction
        idx = t_grid[None, :, None] - kbr[:, None, :] + kmax_glob
        cand = dp[leaf_idx, idx]
        cand += vbr[:, None, :]
        jr = np.argmax(cand, axis=2)
        out = np.take_along_axis(cand, jr[:, :, None], axis=2)[:, :, 0]
        for li, (g, tmax, _) in enumerate(per_leaf):
            if tmax + 1 < nb_max:
                out[li, tmax + 1 :] = -np.inf
        dp[:, kmax_glob:] = out
        results_win.append(jr.astype(np.int32))

    out_final = dp[:, kmax_glob:]
    results = []
    for li, (g, tmax, stages_desc) in enumerate(per_leaf):
        feas = np.flatnonzero(out_final[li, : tmax + 1] > -np.inf)
        dp_keys = (feas * g).astype(np.float64) * 1e-6
        dp_vals = out_final[li, feas].copy()
        stages = _IntStages(
            g=g,
            win=[results_win[s][li] for s in range(len(stages_desc))],
            kb_desc=[kb for kb, _, _ in stages_desc],
            keys_desc=[ks for _, _, ks in stages_desc],
            nstages=len(stages_desc),
        )
        results.append((dp_keys, dp_vals, stages))
    return results


def _class_picks(
    table: OptionTable,
    curve: _AggCurve,
    curve_key: tuple,
    spend: float,
    pick_cache: MutableMapping | None,
) -> tuple[list, np.ndarray, np.ndarray]:
    """One class's canonical pick column at ``spend``: name-sorted members
    get the option multiset in ascending-cost order.  Returns (pick tuples,
    costs, values) aligned with the class's sorted members — memoized by
    (curve content key, quantized spend) so unchanged classes skip the
    binary-split unwind entirely on warm rounds."""
    pkey = (curve_key, _qkey(spend))
    hit = pick_cache.get(pkey) if pick_cache is not None else None
    if hit is None:
        js: list[int] = []
        curve.unwind(spend, js)
        js.sort()
        pt = _pick_tuples(table)
        hit = ([pt[j] for j in js], table.costs[js], table.values[js])
        if pick_cache is not None:
            pick_cache[pkey] = hit
    return hit


def _assemble_plan(
    plan: _LeafPlan,
    curve_keys: Sequence[tuple],
    curves_: Sequence[_AggCurve],
    spends: Sequence[float],
    pick_cache: MutableMapping | None,
) -> tuple[dict, float, float]:
    """Canonical assembly of one plan's solution: picks dict over the
    name-sorted members plus (total_value, spent) accumulated in that same
    order — bit-for-bit the ungrouped ``solve_sparse`` form (sequential
    float64 adds via cumsum == the scalar left fold)."""
    if not plan.names_sorted:
        return {}, 0.0, 0.0
    tuples_parts: list[list] = []
    costs_parts: list[np.ndarray] = []
    vals_parts: list[np.ndarray] = []
    for (table, _, _), ckey, curve, spend in zip(
        plan.classes, curve_keys, curves_, spends
    ):
        tups, costs, vals = _class_picks(table, curve, ckey, spend, pick_cache)
        tuples_parts.append(tups)
        costs_parts.append(costs)
        vals_parts.append(vals)
    flat_tuples = [t for part in tuples_parts for t in part]
    order = plan.order
    picks = dict(zip(plan.names_sorted, (flat_tuples[i] for i in order)))
    costs = np.concatenate(costs_parts)[order]
    vals = np.concatenate(vals_parts)[order]
    total = float(np.cumsum(vals)[-1])
    spent = float(np.cumsum(costs)[-1])
    return picks, total, spent


def solve_sparse_grouped(
    groups: Sequence[GroupedOptions],
    budget: float,
    *,
    curve_cache: MutableMapping | None = None,
    pick_cache: MutableMapping | None = None,
    plan_cache: MutableMapping | None = None,
    chain_cache: MutableMapping | None = None,
) -> MCKPSolution:
    """Group-collapsed Algorithm 1: one DP super-stage per behaviour class.

    Equivalent to — and bit-for-bit equal with — ``solve_sparse`` on the
    name-sorted ungrouped expansion: groups digesting equally merge first
    (their members are interchangeable), each merged group contributes its
    m-fold aggregate curve as a single DP stage, and the backtracked
    per-group spends unwind into option multisets assigned to name-sorted
    members in ascending-cost order (the sparse solver's canonical form).

    All three caches are optional warm state (mutable mappings, e.g. a
    controller's LRU dicts): ``curve_cache`` memoizes aggregate curves by
    (digest, m, quantized budget), ``pick_cache`` memoizes unwound pick
    multisets by (curve key, quantized spend), and ``plan_cache`` memoizes
    merged-class layouts by group-token tuple — together they make a
    steady-state re-solve cost O(changed classes), not O(cluster).
    """
    plan = _leaf_plan(groups, plan_cache)
    curves_, curve_keys = _class_curves(
        plan.classes, budget, curve_cache, chain_cache
    )
    dp_keys, dp_vals, stages = _superstage_dp(
        [(c.keys, c.vals) for c in curves_], budget
    )
    u = float(dp_keys[int(np.argmax(dp_vals))])
    spends = _backtrack_superstages(stages, u)
    picks, total, spent = _assemble_plan(
        plan, curve_keys, curves_, spends, pick_cache
    )
    return MCKPSolution(total_value=total, spent=spent, picks=picks)


# ---------------------------------------------------------------------------
# Hierarchical (two-level) solve over a power-domain tree (DESIGN.md §12)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DomainGroups:
    """One power domain's slice of an allocation round.

    ``cap`` is the domain's *extra-power headroom* in watts — its physical
    cap net of the draw already committed under it (baselines of member
    receivers, natural draw of member donors; the engine does that
    accounting).  A leaf carries the behaviour-class ``groups`` of its
    member receivers (possibly empty); an internal domain carries
    ``children``.
    """

    name: str
    cap: float
    groups: tuple[GroupedOptions, ...] = ()
    children: tuple["DomainGroups", ...] = ()

    def __post_init__(self):
        if self.groups and self.children:
            raise ValueError(
                f"domain {self.name!r}: groups and children are exclusive"
            )


class HierState:
    """Persistent warm state for (incremental) hierarchical sparse solving.

    Every cache is *content-keyed* — digests + multiplicities + quantized
    budgets for curves/frontiers, content tokens for the aggregation-tree
    combines, group-identity tokens for plans and leaf solutions — so a
    warm re-solve is **bit-for-bit** the from-scratch solve: a cache entry
    is only ever reused for inputs under which it would be recomputed
    identically.  A steady-state round therefore costs O(what changed):

     * an unchanged leaf reuses its frontier DP and its assembled solution;
     * a changed leaf re-runs its class super-stages and re-aggregates
       through the balanced frontier **aggregation tree**, recombining only
       the O(log n_leaves) tree nodes on its root path;
     * unchanged classes inside a dirty leaf still reuse their aggregate
       curves and unwound pick multisets.

    All caches are LRU-bounded so long scenarios with drifting budgets or
    digests cannot grow warm state without bound.
    """

    def __init__(
        self,
        curve_cache: MutableMapping | None = None,
        frontier_cache: MutableMapping | None = None,
        *,
        chain_cache: MutableMapping | None = None,
        pick_cache: MutableMapping | None = None,
        plan_cache: MutableMapping | None = None,
        max_curves: int = 1024,
        max_frontiers: int = 512,
        max_picks: int = 8192,
        max_leaf_solutions: int = 128,
        max_plans: int = 256,
    ):
        self.curve_cache: MutableMapping = (
            LRUCache(max_curves) if curve_cache is None else curve_cache
        )
        #: (digest, budget) -> doubling chain, shielded from (d, m) churn
        self.chain_cache: MutableMapping = (
            LRUCache(512) if chain_cache is None else chain_cache
        )
        self.frontier_cache: MutableMapping = (
            LRUCache(max_frontiers) if frontier_cache is None else frontier_cache
        )
        #: (left token, right token, quantized cap) -> combined frontier
        self.comb_cache: MutableMapping = LRUCache(max_frontiers)
        self.pick_cache: MutableMapping = (
            LRUCache(max_picks) if pick_cache is None else pick_cache
        )
        #: (leaf token, plan key, spends) -> (picks, total, spent)
        self.leaf_sol_cache: MutableMapping = LRUCache(max_leaf_solutions)
        self.plan_cache: MutableMapping = (
            LRUCache(max_plans) if plan_cache is None else plan_cache
        )
        self._tokens: dict = {}
        self._next_token = itertools.count(1)

    def token(self, content) -> int:
        """Intern hashable content to a small process-unique int.

        Tokens are never reused (the counter outlives table resets), so a
        stale cache entry keyed by an old token can never collide with new
        content — it just ages out of its LRU."""
        t = self._tokens.get(content)
        if t is None:
            if len(self._tokens) > (1 << 20):
                self._tokens.clear()
            t = next(self._next_token)
            self._tokens[content] = t
        return t

    def cache_sizes(self) -> dict[str, int]:
        return {
            "curves": len(self.curve_cache),
            "frontiers": len(self.frontier_cache),
            "combines": len(self.comb_cache),
            "picks": len(self.pick_cache),
            "leaf_solutions": len(self.leaf_sol_cache),
            "plans": len(self.plan_cache),
        }

    def clear(self) -> None:
        for c in (
            self.curve_cache,
            self.chain_cache,
            self.frontier_cache,
            self.comb_cache,
            self.pick_cache,
            self.leaf_sol_cache,
            self.plan_cache,
        ):
            c.clear()
        self._tokens.clear()


class _CombNode:
    """One node of the balanced frontier aggregation tree.

    Wrapper nodes (``leaf`` set) adapt a child domain's frontier; internal
    nodes hold a (max,+)-combined frontier with per-state (left, right)
    spend splits for backtracking.  The tree shape is a deterministic
    function of the child count (adjacent pairs, odd tail carried up), so
    content-addressed memoization of each combine makes replacing one
    dirty child cost O(log n_children) convolutions.
    """

    __slots__ = ("keys", "vals", "back_left", "back_right", "left", "right", "leaf")

    def __init__(self, keys, vals, back_left=None, back_right=None,
                 left=None, right=None, leaf=None):
        self.keys: np.ndarray = keys
        self.vals: np.ndarray = vals
        self.back_left = back_left
        self.back_right = back_right
        self.left: _CombNode | None = left
        self.right: _CombNode | None = right
        self.leaf: "_SparseFrontier | None" = leaf


class _SparseFrontier:
    """A domain's value-vs-spend frontier with backtracking state.

    ``keys``/``vals`` are the capped frontier (ascending quantized spends,
    best value at each).  Leaves keep their plan/curves/stages for
    unwinding; internal domains keep their children plus the aggregation
    tree (``comb``) that combined them.  ``token`` is the content token
    the parent's combine cache keys on.
    """

    __slots__ = (
        "dom", "keys", "vals", "stages", "plan", "curves", "curve_keys",
        "token", "comb", "children",
    )

    def __init__(self, dom, keys, vals, *, stages=None, plan=None,
                 curves=None, curve_keys=None, token=None, comb=None,
                 children=None):
        self.dom: DomainGroups = dom
        self.keys: np.ndarray = keys
        self.vals: np.ndarray = vals
        self.stages: list | None = stages
        self.plan: _LeafPlan | None = plan
        self.curves = curves
        self.curve_keys = curve_keys
        self.token: int | None = token
        self.comb: _CombNode | None = comb
        self.children: list["_SparseFrontier"] | None = children


def _combine_frontiers(
    subs: Sequence[_SparseFrontier], eff: float, state: HierState
) -> tuple[_CombNode, int]:
    """Fold child frontiers through the balanced aggregation tree under
    cap ``eff``.  Returns the root node and its content token."""
    nodes = [
        _CombNode(keys=f.keys, vals=f.vals, leaf=f) for f in subs
    ]
    tokens = [f.token for f in subs]
    effk = _qkey(eff)
    while len(nodes) > 1:
        nxt: list[_CombNode] = []
        ntok: list[int] = []
        for i in range(0, len(nodes) - 1, 2):
            key = (tokens[i], tokens[i + 1], effk)
            hit = state.comb_cache.get(key)
            if hit is None:
                hit = _maxplus_pair(
                    nodes[i].keys, nodes[i].vals,
                    nodes[i + 1].keys, nodes[i + 1].vals, eff,
                )
                state.comb_cache[key] = hit
            nxt.append(
                _CombNode(
                    keys=hit[0], vals=hit[1], back_left=hit[2],
                    back_right=hit[3], left=nodes[i], right=nodes[i + 1],
                )
            )
            ntok.append(state.token(("comb",) + key))
        if len(nodes) % 2:
            nxt.append(nodes[-1])
            ntok.append(tokens[-1])
        nodes, tokens = nxt, ntok
    return nodes[0], tokens[0]


def _comb_spends(
    node: _CombNode, u: float, out: list[tuple[_SparseFrontier, float]]
) -> None:
    """Split a chosen spend ``u`` down the aggregation tree into per-child
    (frontier, spend) pairs in original child order."""
    if node.leaf is not None:
        out.append((node.leaf, u))
        return
    i = int(np.searchsorted(node.keys, u))
    _comb_spends(node.left, float(node.back_left[i]), out)
    _comb_spends(node.right, float(node.back_right[i]), out)


def _domain_eff(dom: DomainGroups, budget: float) -> float:
    """Effective spend cap of a domain under its parent's budget — the one
    clamping rule shared by the frontier builders and the batched-leaf
    pre-walks (divergence here would silently misalign their grids)."""
    eff = min(float(dom.cap), float(budget))
    return eff if eff > 0.0 else 0.0


def _prime_leaf_frontiers(
    root: DomainGroups, budget: float, state: HierState
) -> None:
    """Batched single-dispatch solve of every *dirty* leaf DP.

    Walks the domain tree computing each leaf's effective cap, collects
    the leaves whose frontier isn't cached, and solves them all through
    :func:`_superstage_dp_batch` — priming the frontier cache so the
    subsequent recursive build is all hits.  A steady-state round with k
    dirty leaves pays one batched dispatch instead of k per-leaf stage
    loops.  No-op (falling back to the per-leaf path) on non-lattice
    instances.
    """
    jobs: list[tuple[_LeafPlan, float, tuple]] = []
    seen: set = set()

    def walk(dom: DomainGroups, b: float) -> None:
        eff = _domain_eff(dom, b)
        if dom.children:
            for c in dom.children:
                walk(c, eff)
            return
        if not dom.groups:
            return
        plan = _leaf_plan(dom.groups, state.plan_cache)
        key = (plan.layout, _qkey(eff))
        if key in seen or state.frontier_cache.get(key) is not None:
            return
        seen.add(key)
        jobs.append((plan, eff, key))

    walk(root, float(budget))
    if len(jobs) < 2:
        return
    prepared = []
    for plan, eff, key in jobs:
        curves_, curve_keys = _class_curves(
            plan.classes, eff, state.curve_cache, state.chain_cache
        )
        prepared.append((plan, eff, key, curves_, curve_keys))
    batch = _superstage_dp_batch(
        [
            ([(c.keys, c.vals) for c in curves_], eff)
            for _, eff, _, curves_, _ in prepared
        ]
    )
    if batch is None:
        return
    for (plan, eff, key, curves_, curve_keys), (dp_keys, dp_vals, stages) in zip(
        prepared, batch
    ):
        state.frontier_cache[key] = (curves_, curve_keys, dp_keys, dp_vals, stages)


def _sparse_frontier(
    dom: DomainGroups, budget: float, state: HierState
) -> _SparseFrontier:
    """Capped frontier of one domain: its best-value-per-spend staircase,
    restricted to spends <= min(domain cap, parent budget).

    A leaf's frontier is the class super-stage DP of its groups — the same
    arrays ``solve_sparse_grouped`` ends on, so a single root domain with
    cap >= budget reproduces the flat grouped solve bit-for-bit.  An
    internal domain folds its children's frontiers through the balanced
    aggregation tree under its own cap (the "upper-level DP").  Leaf DPs
    memoize by (per-class digest+multiplicity layout, quantized budget);
    tree combines by the child content tokens — both in ``state``.
    """
    eff = _domain_eff(dom, budget)
    if dom.children:
        subs = [_sparse_frontier(c, eff, state) for c in dom.children]
        comb, token = _combine_frontiers(subs, eff, state)
        return _SparseFrontier(
            dom, comb.keys, comb.vals, token=token, comb=comb, children=subs
        )
    plan = _leaf_plan(dom.groups, state.plan_cache)
    key = (plan.layout, _qkey(eff))
    hit = state.frontier_cache.get(key)
    if hit is None:
        curves_, curve_keys = _class_curves(
            plan.classes, eff, state.curve_cache, state.chain_cache
        )
        dp_keys, dp_vals, stages = _superstage_dp(
            [(c.keys, c.vals) for c in curves_], eff
        )
        hit = (curves_, curve_keys, dp_keys, dp_vals, stages)
        state.frontier_cache[key] = hit  # type: ignore[index]
    curves_, curve_keys, dp_keys, dp_vals, stages = hit
    return _SparseFrontier(
        dom, dp_keys, dp_vals, stages=stages, plan=plan, curves=curves_,
        curve_keys=curve_keys, token=state.token(("leaf", key)),
    )


def _backtrack_frontier(
    f: _SparseFrontier,
    u: float,
    state: HierState,
    picks: dict[str, tuple[float, float, tuple[float, float]]],
    domain_spent: dict[str, float],
    leaf_totals: list[tuple[float, float]],
) -> None:
    """Walk a chosen spend ``u`` down the frontier tree to receiver picks.

    Leaf solutions (picks + canonically-accumulated totals) memoize by
    (leaf content token, membership plan key, per-class spends): an
    unchanged leaf whose budget share didn't move contributes its cached
    dict without re-unwinding a single class.
    """
    domain_spent[f.dom.name] = u
    if f.children is not None:
        pairs: list[tuple[_SparseFrontier, float]] = []
        _comb_spends(f.comb, u, pairs)
        for sub, s in pairs:
            _backtrack_frontier(sub, s, state, picks, domain_spent, leaf_totals)
        return
    spends = _backtrack_superstages(f.stages, u)
    skey = None
    if f.plan.key is not None:
        skey = (f.token, f.plan.key, tuple(spends))
        hit = state.leaf_sol_cache.get(skey)
        if hit is not None:
            picks.update(hit[0])
            leaf_totals.append((hit[1], hit[2]))
            return
    lp, lt, ls = _assemble_plan(
        f.plan, f.curve_keys, f.curves, spends, state.pick_cache
    )
    if skey is not None:
        state.leaf_sol_cache[skey] = (lp, lt, ls)
    picks.update(lp)
    leaf_totals.append((lt, ls))


def solve_hierarchical(
    root: DomainGroups,
    budget: float,
    *,
    curve_cache: MutableMapping | None = None,
    frontier_cache: MutableMapping | None = None,
    state: HierState | None = None,
) -> MCKPSolution:
    """Topology-aware MCKP over an arbitrary-depth power-domain tree.

    Per-domain group-collapsed aggregate tables become capped value-vs-spend
    frontiers; the upper-level DP folds sibling frontiers through a
    balanced aggregation tree *recursively at every internal domain* to
    split each parent's budget subject to every domain's local cap (site
    → row → PDU → ... → leaf), then backtracks down to the per-receiver
    picks.  Every domain's spend is <= its cap by construction, and with a
    single root domain whose cap >= the cluster budget the result is
    **bit-for-bit** ``solve_sparse_grouped`` — certified by
    tests/test_hier_alloc.py.

    Passing a persistent :class:`HierState` makes warm re-solves
    incremental (O(what changed) — see the class docstring) while staying
    bit-for-bit equal to a from-scratch call; ``curve_cache`` /
    ``frontier_cache`` remain accepted as standalone warm mappings.

    Returns a solution whose ``domain_spent`` maps each domain name to the
    watts spent inside it.
    """
    st = state if state is not None else HierState(curve_cache, frontier_cache)
    _prime_leaf_frontiers(root, float(budget), st)
    f = _sparse_frontier(root, float(budget), st)
    u = float(f.keys[int(np.argmax(f.vals))])
    picks: dict[str, tuple[float, float, tuple[float, float]]] = {}
    domain_spent: dict[str, float] = {}
    leaf_totals: list[tuple[float, float]] = []
    _backtrack_frontier(f, u, st, picks, domain_spent, leaf_totals)
    total = 0.0
    spent = 0.0
    for lt, ls in leaf_totals:
        total += lt
        spent += ls
    return MCKPSolution(
        total_value=total, spent=spent, picks=picks,
        domain_spent=domain_spent,
    )


# ---------------------------------------------------------------------------
# Receding-horizon (MPC) spend planning over cached frontiers (DESIGN.md §15)
# ---------------------------------------------------------------------------


def grouped_frontier(
    groups: Sequence[GroupedOptions],
    budget: float,
    *,
    curve_cache: MutableMapping | None = None,
    plan_cache: MutableMapping | None = None,
    chain_cache: MutableMapping | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """The flat cluster's value-vs-spend frontier under ``budget``: the
    final ``(dp_keys, dp_vals)`` arrays of the grouped super-stage DP —
    exactly the states ``solve_sparse_grouped`` ends on, built from the
    same warm class-curve caches (so a planning call right before the
    round's solve costs one super-stage scan, not a re-aggregation)."""
    plan = _leaf_plan(groups, plan_cache)
    curves_, _ = _class_curves(plan.classes, budget, curve_cache, chain_cache)
    dp_keys, dp_vals, _ = _superstage_dp(
        [(c.keys, c.vals) for c in curves_], budget
    )
    return dp_keys, dp_vals


def hierarchical_frontier(
    root: DomainGroups, budget: float, state: HierState | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """The root domain's capped value-vs-spend frontier under ``budget``
    (PR-5 frontier aggregation tree, warm through ``state``) — the
    hierarchical analogue of :func:`grouped_frontier`."""
    st = state if state is not None else HierState()
    _prime_leaf_frontiers(root, float(budget), st)
    f = _sparse_frontier(root, float(budget), st)
    return f.keys, f.vals


def frontier_records(
    keys: np.ndarray, vals: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Monotone record points of a frontier: the (spend, value) states
    where the running-max value strictly increases.

    Every record point is an *achievable* DP state (never an
    interpolation), and the smallest-spend argmax under any cap ``c`` is
    the last record point with spend <= ``c`` — the same state
    ``np.argmax`` (first max) picks in the myopic solvers, so planning on
    records commits only spends the real solve would also choose.
    """
    if len(keys) == 0:
        return keys, vals
    run = np.maximum.accumulate(vals)
    rec = np.ones(len(vals), dtype=bool)
    rec[1:] = vals[1:] > run[:-1]
    return keys[rec], vals[rec]


def plan_horizon(
    keys: np.ndarray,
    vals: np.ndarray,
    caps: Sequence[float],
    weights: Sequence[float] | None = None,
    *,
    eco_factor: float = 1.0,
    levels: int = 64,
    grid: int = 2048,
) -> list[float] | None:
    """Receding-horizon spend plan over one value-vs-spend frontier.

    Given the cluster frontier ``(keys, vals)`` (spends ascending, best
    value per spend) and an H-round cap forecast, choose per-round spends
    ``s_i`` maximizing ``sum_i value(s_i)`` subject to

     * ``s_i <= caps[i]`` — the instantaneous budget is *never* exceeded
       (the committed round-0 spend is a cap on that round's solve);
     * ``sum_i weights[i] * s_i <= eco_factor * sum_i weights[i] * umax_i``
       — the horizon's weighted spend (CO2 grams, dollars) may use at
       most an ``eco_factor`` fraction of what the myopic cap-riding
       controller would emit (``umax_i`` = the myopic best spend under
       ``caps[i]``).

    The temporal coupling is entirely in the weighted allowance: with
    ``eco_factor >= 1`` the per-round maxima are jointly feasible, the DP
    returns them, and the plan never restricts anything — so the function
    returns **None** ("don't touch the budget") and the caller takes the
    *literally unchanged* myopic code path, which is what certifies H=1
    and eco-off parity bit-for-bit.  With ``eco_factor < 1`` the DP banks
    spend away from dirty/expensive rounds (high weight) and rounds of
    diminishing marginal value, and toward clean rounds and upcoming
    deratings.

    Implementation: per-round candidates are the frontier's record points
    under that round's cap, subsampled to <= ``levels`` spends (the cap
    state and zero state always kept); the DP runs on an integer
    allowance lattice of ``grid`` cells with *ceil* cost rounding — so a
    returned plan's true weighted spend is <= the allowance, never over
    (conservative by construction).  Cost is O(H * levels * grid) numpy
    ops, independent of cluster size — the frontier did the heavy
    lifting.  Returns the planned spends (round order) or None when the
    plan would not restrict round 0.
    """
    H = len(caps)
    if H <= 1 or eco_factor >= 1.0 or len(keys) == 0:
        return None
    rk, rv = frontier_records(np.asarray(keys), np.asarray(vals))
    if len(rk) == 0 or rk[-1] <= 0.0:
        return None
    w = (
        np.ones(H, dtype=np.float64)
        if weights is None
        else np.clip(np.asarray(weights, dtype=np.float64), 0.0, None)
    )
    # per-round candidate spends/values + the myopic optimum under each cap
    cand_k: list[np.ndarray] = []
    cand_v: list[np.ndarray] = []
    umax = np.empty(H, dtype=np.float64)
    for i in range(H):
        hi = int(np.searchsorted(rk, float(caps[i]) + 1e-9))
        if hi == 0:
            # no positive-spend state fits: the only choice is state 0
            hi = 1
        umax[i] = rk[hi - 1]
        if hi > levels:
            idx = np.unique(
                np.round(np.linspace(0, hi - 1, levels)).astype(np.int64)
            )
        else:
            idx = np.arange(hi)
        cand_k.append(rk[idx])
        cand_v.append(rv[idx])
    allowance = float(eco_factor) * float(np.dot(w, umax))
    if allowance <= 0.0:
        plan = [float(k[0]) for k in cand_k]
        return None if plan[0] >= umax[0] - 1e-9 else plan
    q = allowance / float(grid)
    # integer ceil costs: sum(cost_cells) <= grid  =>  weighted spend <=
    # allowance exactly (each candidate's cells over-cover its true cost)
    costs = [
        np.ceil(w[i] * cand_k[i] / q - 1e-9).astype(np.int64)
        for i in range(H)
    ]
    neg = -np.inf
    dp = np.zeros(grid + 1, dtype=np.float64)
    wins: list[np.ndarray] = []
    t_axis = np.arange(grid + 1)
    for i in range(H):
        c, v = costs[i], cand_v[i]
        feas = c <= grid
        if not feas.any():
            return None
        c, v = c[feas], v[feas]
        # cand[j, t] = dp[t - c_j] + v_j where feasible
        shifted = np.full((len(c), grid + 1), neg)
        for j in range(len(c)):
            cj = int(c[j])
            shifted[j, cj:] = dp[: grid + 1 - cj] + v[j]
        win = np.argmax(shifted, axis=0)
        dp = shifted[win, t_axis]
        # record the candidate index in the unfiltered array for backtrack
        wins.append((np.flatnonzero(feas)[win], np.asarray(c)[win]))
    if not np.isfinite(dp[grid]):
        return None
    plan = [0.0] * H
    t = grid
    for i in range(H - 1, -1, -1):
        jfull, cwin = wins[i]
        j = int(jfull[t])
        plan[i] = float(cand_k[i][j])
        t -= int(cwin[t])
    return None if plan[0] >= umax[0] - 1e-9 else plan


# ---------------------------------------------------------------------------
# Fused device-resident sparse solve (DESIGN.md §14)
# ---------------------------------------------------------------------------

#: fused-path grid bound: fall back to host when the padded global spend
#: grid would exceed this many states (churn storms with tiny gcd pitches)
_FUSED_MAX_NB = 4096

#: per-stage option-count bound for the padded [S, L, K] device banks
_FUSED_MAX_OPTS = 1024


def _pow2_at_least(n: int, floor: int) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


class FusedState:
    """Device-resident warm state for the fused steady-state round.

    Holds the padded ``[S, L, K]`` option banks (int32 spend offsets on
    the shared integer micro-watt lattice + values in
    ``ops.device_value_dtype()``: float64 on the CPU, float32 on a TPU)
    as *resident jax device arrays*, the host-side per-row content signatures that drive
    delta patching, and the reversed per-stage key arrays the host
    assembly maps device backpointers through.  Banks use
    **capacity-slack layouts** (DESIGN.md §17): padded dims are quantized
    tiers (pow2 options/grids, identity-row stage padding) that only ever
    grow, so membership/structure churn inside the slack is pure row
    content.  The churn-boundary contract (DESIGN.md §14/§17):

     * same layout + same row signatures  -> zero upload, straight to the
       jitted pipeline;
     * same layout, k rows changed        -> one donated scatter of the k
       rebuilt rows (O(churn) upload) — this now includes class
       add/remove/split/merge, pitch changes and leaf-name permutations
       that used to be shape changes;
     * layout changed (leaf set, pad tier growth, topology edit) ->
       **device-side compaction**: a jitted gather repacks every clean
       row into the new geometry and only dirty rows upload; the round
       still runs fused (O(churn), same round — no host fallback).

    Only the cold start (no resident banks) builds banks on the host and
    uploads them whole (``stats['rebuilds']``).  ``last_key``/
    ``last_solution`` short-circuit the host assembly when the device
    decision vector is unchanged round-over-round.
    """

    def __init__(self):
        self.shape: tuple | None = None  # capacity-slack layout signature
        self.names: tuple | None = None  # per-leaf names (compaction map)
        self.row_sigs: list | None = None  # [L][S] per-row content sigs
        self.kb_dev = None  # [S, L, K] int32 device bank (global lattice)
        self.vb_dev = None  # [S, L, K] device-value-dtype bank
        self.keys_desc: list | None = None  # [L][S] host reversed key arrays
        self.g: int = 0  # global micro-watt lattice pitch
        self.last_key: tuple | None = None
        self.last_solution: MCKPSolution | None = None
        #: (curve key tuple) -> (leaf gcd pitch, per-class micro ints)
        self._leaf_ints: dict = {}
        #: row sig -> (kb_glob desc, vals desc, keys desc)
        self._row_cache: dict = {}
        #: last round's seconds per ``fused.*`` span: prep/patch/compact/
        #: dispatch (launch + wait)/backtrack/assembly (``fused_segments``)
        self.last_segments: dict = {}
        self.stats: dict = {
            "rounds": 0,
            "fallbacks": 0,
            "rebuilds": 0,
            "compactions": 0,
            "row_uploads": 0,
            "short_circuits": 0,
            "slack_utilization": 0.0,
            "fallback_reason": "",
            "value_bound": 0.0,
        }

    def clear(self) -> None:
        self.shape = None
        self.names = None
        self.row_sigs = None
        self.kb_dev = None
        self.vb_dev = None
        self.keys_desc = None
        self.g = 0
        self.last_key = None
        self.last_solution = None
        self._leaf_ints.clear()
        self._row_cache.clear()
        self.last_segments = {}


@functools.cache
def _fused_patch_fn():
    """Donated row scatter: patch changed (stage, leaf) rows of a resident
    bank in place (the donation reuses the device buffer, so steady-state
    churn uploads only the dirty rows, never the whole bank)."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def patch(bank, s_idx, l_idx, rows):
        return bank.at[s_idx, l_idx].set(rows)

    return patch


@functools.cache
def _fused_shards() -> int:
    """Device count the fused leaf scan shards over.

    Defaults to every visible device (1 on a single-device host — the
    transparent unsharded path); ``REPRO_FUSED_SHARDS`` overrides, so a
    multi-device process can also compile the single-device pipeline and
    certify the sharded one bitwise against it.
    """
    import os

    import jax

    env = os.environ.get("REPRO_FUSED_SHARDS")
    n = int(env) if env else jax.device_count()
    return max(1, min(n, jax.device_count()))


@functools.cache
def _tree_ops(
    tree_sig: tuple | int, first_out: int
) -> tuple[tuple, dict, dict, tuple]:
    """Lower a nested domain signature to its static combine-op list.

    ``tree_sig``: leaf = spec row index; internal domain =
    ``("d", dom_idx, (child_sigs...))`` with ``dom_idx`` post-order.
    Rows ``0..L-1`` are the DFS leaves; each pairwise combine allocates
    the next row id from ``first_out``.  Per domain the ops replay
    ``_combine_frontiers``' balanced order exactly (adjacent pairs, odd
    tail carried up; a single-child domain emits no op — its cap already
    flows through the child's cascaded eff).  Returns ``(ops, depth,
    leaves_under, dom_rows)``: ops as ``(left_row, right_row, out_row,
    dom_idx)`` in topological order, per-row combine depth and leaf
    count, and each internal domain's result row.
    """
    ops: list[tuple[int, int, int, int]] = []
    depth: dict[int, int] = {}
    leaves_under: dict[int, int] = {}
    nxt = [first_out]
    dom_rows: dict[int, int] = {}

    def build(sig):
        if isinstance(sig, int):
            depth.setdefault(sig, 0)
            leaves_under.setdefault(sig, 1)
            return sig
        _tag, dom_idx, children = sig
        rows = [build(c) for c in children]
        while len(rows) > 1:
            merged = []
            for i in range(0, len(rows) - 1, 2):
                left, right = rows[i], rows[i + 1]
                out = nxt[0]
                nxt[0] += 1
                depth[out] = 1 + max(depth[left], depth[right])
                leaves_under[out] = leaves_under[left] + leaves_under[right]
                ops.append((left, right, out, dom_idx))
                merged.append(out)
            if len(rows) % 2:
                merged.append(rows[-1])
            rows = merged
        dom_rows[dom_idx] = rows[0]
        return rows[0]

    build(tree_sig)
    # renumber output rows into wave (depth) order: the pipeline buffer
    # appends each wave's outputs contiguously, so a row's id must equal
    # its append position — creation order interleaves domains and would
    # not (stable sort keeps within-depth creation order)
    order = sorted(range(len(ops)), key=lambda i: depth[ops[i][2]])
    remap = {
        ops[i][2]: first_out + pos for pos, i in enumerate(order)
    }
    ops_w = tuple(
        (
            remap.get(ops[i][0], ops[i][0]),
            remap.get(ops[i][1], ops[i][1]),
            remap[ops[i][2]],
            ops[i][3],
        )
        for i in order
    )
    return (
        ops_w,
        {remap.get(r, r): d for r, d in depth.items()},
        {remap.get(r, r): v for r, v in leaves_under.items()},
        tuple(
            remap.get(dom_rows[i], dom_rows[i]) for i in range(len(dom_rows))
        ),
    )


def _tree_waves(
    ops: tuple, depth: dict, leaves_under: dict, nb: int, nbt: int
) -> tuple:
    """Group combine ops into depth waves for batched kernel launches.

    Ops at the same combine depth are independent (inputs come from
    strictly shallower rows), so each wave is one row-batched (max,+)
    dispatch.  Per wave, the enumerated right-offset count is the static
    support bound of its right inputs: ``min(nbt, max_right_leaves *
    (nb - 1) + 1)`` — offsets beyond a subtree's reachable spend are
    provably ``-inf`` and dropping them is bitwise-neutral.
    """
    by_depth: dict[int, list] = {}
    for op in ops:
        by_depth.setdefault(depth[op[2]], []).append(op)
    return tuple(
        (
            min(nbt, max(leaves_under[op[1]] for op in wave) * (nb - 1) + 1),
            tuple(wave),
        )
        for _d, wave in sorted(by_depth.items())
    )


@functools.cache
def _fused_pipeline_fn(
    tree: tuple | None, L: int, Lp: int, S: int, K: int, NB: int, NBT: int,
    shards: int, interpret: bool,
):
    """Build the jitted fused round for one static shape.

    One XLA program: batched leaf super-stage DPs (Pallas sparse-option
    (max,+) stages with backpointer outputs), the depth-wave frontier
    aggregation schedule of an arbitrary-depth domain tree (the dense
    (max,+) kernel in descending shift order, masked at each owning
    domain's cap cut), the root argmax, and the index-based backtrack —
    device gathers through the recorded backpointer tables instead of a
    host Python unwind.  Mirrors ``_superstage_dp_batch`` +
    ``_combine_frontiers`` + ``_backtrack_superstages`` op for op
    (first-max argmax, per-stage feasibility masks, per-pair cap
    pruning), so with float64 values its decisions are bit-for-bit the
    sparse host path's at any tree depth; with float32 values (a TPU)
    they hold the DESIGN.md §14 bound.  Indices are int32 throughout.

    ``tree`` is the static ``(waves, dom_rows)`` schedule from
    ``_tree_ops``/``_tree_waves`` (None for flat/leaf-root rounds);
    ``Lp >= L`` is the leaf row count padded to a multiple of
    ``shards`` — with ``shards > 1`` the leaf scan runs under
    ``shard_map`` over the leaf axis (rows are independent, so the
    sharded scan is bitwise the single-device one; DESIGN.md §16), and
    the aggregation waves tree-reduce the gathered per-device frontier
    partials.

    Two lattice grids keep the work proportional to the *support*: leaf
    DPs and backtracking run on the per-leaf grid ``NB`` (max leaf spend
    + 1), the aggregation waves on ``NBT >= NB`` (cap-cut/support-sum
    bound), and each wave enumerates only ``K_level`` right-spend
    offsets — the static support bound of its right inputs.  Dropped
    grid tails and offsets are provably ``-inf`` (beyond every reachable
    spend sum), so values, first-max winners and backpointers of every
    reachable state are bitwise unchanged versus the single-grid form.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import mckp_dp as _mk

    waves, dom_rows = tree if tree is not None else ((), ())
    root_row = dom_rows[-1] if dom_rows else 0

    # named device scopes (leaf_scan, option_scatter in the stage kernel's
    # wrapper, stage_mask, frontier_wave<i>, root_argmax, tree_backtrack,
    # leaf_backtrack) label the ops of the compiled program, so a trace
    # reduction finds each part of the pipeline by name
    @jax.named_scope("leaf_scan")
    def leaf_scan(kb, vb, tmax_leaf):
        t_idx = jnp.arange(NB, dtype=jnp.int32)
        neg = jnp.asarray(-jnp.inf, vb.dtype)
        dp0 = jnp.full((kb.shape[1], NB), neg).at[:, 0].set(0.0)

        def stage(dp, skv):
            kb_s, vb_s = skv
            out, arg = _mk.maxplus_stage_pallas_batched(
                dp, kb_s, vb_s, interpret=interpret
            )
            # per-leaf feasibility mask after every stage == the host
            # batch's out[li, tmax+1:] = -inf
            with jax.named_scope("stage_mask"):
                out = jnp.where(t_idx[None, :] > tmax_leaf[:, None], neg, out)
            return out, arg

        return jax.lax.scan(stage, dp0, (kb, vb))

    def rows(ids):
        return jnp.asarray(ids, dtype=jnp.int32)

    def tree_solve(dp, tcuts):
        # frontier aggregation: depth waves of pairwise combines, each one
        # dense (max,+) convolution of the left frontiers with the first
        # k_level points of the right ones, first max in *descending*
        # right spend (== the dict DP's smallest-left-spend tie-break),
        # masked at the owning domain's cap cut — the device image of
        # _combine_frontiers applying _maxplus_pair(..., eff) at every pair
        neg = jnp.asarray(-jnp.inf, dp.dtype)
        t_idx_tree = jnp.arange(NBT, dtype=jnp.int32)
        buf = (
            jnp.concatenate([dp, jnp.full((Lp, NBT - NB), neg)], axis=1)
            if NBT > NB
            else dp
        )
        wins_tree = []  # per wave: [ops, NBT] winning right spends
        for w, (k_level, wave) in enumerate(waves):
            with jax.named_scope(f"frontier_wave{w}"):
                left = buf[rows([op[0] for op in wave])]
                right = buf[rows([op[1] for op in wave]), :k_level]
                out, t_right = _mk.maxplus_conv_pallas_batched(
                    left, right, interpret=interpret
                )
                tc = tcuts[rows([op[3] for op in wave])]
                out = jnp.where(t_idx_tree[None, :] > tc[:, None], neg, out)
                wins_tree.append(t_right)
                buf = jnp.concatenate([buf, out], axis=0)

        with jax.named_scope("root_argmax"):
            root_vec = buf[root_row]
            t_root = jnp.argmax(root_vec).astype(jnp.int32)  # first max
            root_val = root_vec[t_root]

        # tree backtrack: split t down the static schedule via gathers,
        # in reverse wave order (an op's output t is known before its
        # inputs are needed — the schedule is topological)
        with jax.named_scope("tree_backtrack"):
            t_of = {root_row: t_root}
            for (_k, wave), win in zip(reversed(waves), reversed(wins_tree)):
                for i in range(len(wave) - 1, -1, -1):
                    l_row, r_row, o_row, _d = wave[i]
                    t_out = t_of[o_row]
                    t_r = win[i, t_out]
                    t_of[r_row] = t_r
                    t_of[l_row] = t_out - t_r
            t_leaf = jnp.stack([t_of[i] for i in range(L)]).astype(jnp.int32)
            t_dom = (
                jnp.stack([t_of[r] for r in dom_rows]).astype(jnp.int32)
                if dom_rows
                else jnp.zeros((0,), jnp.int32)
            )
        return t_root, root_val, t_leaf, t_dom

    if shards > 1:
        from jax.sharding import PartitionSpec as P

        from repro.kernels import ops as _kops

        mesh = _kops.leaf_shard_mesh(shards)
        leaf_fn = jax.shard_map(
            leaf_scan,
            mesh=mesh,
            in_specs=(
                P(None, "leaves", None),
                P(None, "leaves", None),
                P("leaves"),
            ),
            out_specs=(P("leaves", None), P(None, "leaves", None)),
            check_vma=False,  # pallas_call carries no replication rule
        )
        # the frontier tree is small: every device combines the gathered
        # leaf frontiers redundantly (a Mosaic kernel in a multi-device
        # program must sit inside a shard_map)
        tree_fn = jax.shard_map(
            tree_solve,
            mesh=mesh,
            in_specs=(P(), P()),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )
    else:
        leaf_fn, tree_fn = leaf_scan, tree_solve

    @jax.jit
    def run(kb, vb, tmax_leaf, tcuts):
        rows_i = jnp.arange(L, dtype=jnp.int32)
        dp, wins = leaf_fn(kb, vb, tmax_leaf)  # dp: [Lp, NB]; wins: [S, Lp, NB]
        t_root, root_val, t_leaf, t_dom = tree_fn(dp, tcuts)

        # leaf backtrack: walk the backpointer tables stage-by-stage, the
        # device-gather analogue of _IntStages.backtrack
        def bstep(t, skw):
            kb_s, win_s = skw
            j = win_s[rows_i, t]
            return (t - kb_s[rows_i, j]).astype(jnp.int32), j.astype(jnp.int32)

        with jax.named_scope("leaf_backtrack"):
            _, js_rev = jax.lax.scan(bstep, t_leaf, (kb[::-1], wins[::-1]))
            js = js_rev[::-1].swapaxes(0, 1)  # [L, S]
        return t_root, t_leaf, js, root_val, t_dom

    return run


def _fused_leaf_rows(
    spec: tuple, fstate: FusedState
) -> tuple[int, int, list] | None:
    """Per-leaf lattice prep, mirroring ``_superstage_dp_batch``'s per-job
    block: micro-int class keys, the leaf gcd pitch, and the per-stage
    descending (offsets, values, keys, sig, max |value|) rows.  None
    routes to host."""
    name, eff, plan, curves_, curve_keys = spec
    lkey = tuple(curve_keys)
    ent = fstate._leaf_ints.get(lkey)
    if ent is None:
        ints = []
        g_l = 0
        for c in curves_:
            ia = _micro_int(c.keys)
            if ia is None or not len(ia):
                return None
            ints.append(ia)
            g_l = int(np.gcd(g_l, np.gcd.reduce(ia)))
        all_zero = g_l == 0  # every class key is 0.0: the leaf can only spend 0
        if g_l <= 0:
            g_l = 1
        if len(fstate._leaf_ints) > 1024:
            fstate._leaf_ints.clear()
        ent = (g_l, ints, all_zero)
        fstate._leaf_ints[lkey] = ent
    g_l, ints, all_zero = ent
    if all_zero:
        tmax_host = 0  # zero-spend leaf: one state, any lattice pitch fits
    else:
        bound = np.floor((eff + 1e-9) * 1e6 / g_l)
        if not np.isfinite(bound) or bound < 0:
            return None
        tmax_host = int(bound)
    rows = []
    for s, (ia, curve, ckey) in enumerate(zip(ints, curves_, curve_keys)):
        sig = (ckey, g_l, tmax_host)
        row = fstate._row_cache.get(sig)
        if row is None:
            keep = np.flatnonzero(ia // g_l <= tmax_host)
            if not len(keep):
                return None
            kb = (ia[keep] // g_l)[::-1].copy()  # leaf-lattice units
            vals = curve.vals[keep][::-1].copy()
            row = (
                kb,
                vals,
                curve.keys[keep][::-1].copy(),
                sig,
                float(np.abs(vals).max()),
            )
            if len(fstate._row_cache) > 4096:
                fstate._row_cache.clear()
            fstate._row_cache[sig] = row
        rows.append(row)
    return g_l, tmax_host, rows, all_zero


def _fused_run(
    specs: list[tuple],
    eff_root: float,
    kind: str,
    tree_sig: tuple | int | None,
    doms: tuple,
    *,
    pick_cache: MutableMapping | None,
    fstate: FusedState,
    st: "HierState | None" = None,
) -> MCKPSolution | None:
    """One fused device round over prepared leaf specs.

    ``specs``: per-leaf (name, eff, plan, curves, curve_keys) in DFS
    order.  ``kind``: 'flat' (grouped solve, no domain accounting),
    'leaf_root' (hierarchical root that is itself a leaf) or 'tree'
    (arbitrary-depth domain tree: ``tree_sig`` is the nested signature
    over spec indices and ``doms`` the post-order (name, eff) list of
    internal domains, root last).

    Structure churn never routes to the host (DESIGN.md §17): content
    changes (class add/remove/split/merge, pitch moves, headroom drift)
    patch rows in place under the unchanged capacity-slack layout, and
    layout changes (leaf set, pad-tier growth, topology edits) repack the
    resident banks by device-side compaction — either way the fused
    pipeline produces this round's allocation.  Returns None only for
    off-lattice keys, oversized grids, empty rounds or an infeasible
    root; ``fstate.stats['fallback_reason']`` records which.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as _kops

    stats = fstate.stats
    seg = fstate.last_segments = {
        "prep_s": 0.0, "patch_s": 0.0, "compact_s": 0.0,
        "dispatch_s": 0.0, "backtrack_s": 0.0, "assembly_s": 0.0,
    }
    with Span("fused.prep") as sp:
        L = len(specs)
        if L == 0:
            stats["fallbacks"] += 1
            stats["fallback_reason"] = "empty"
            return None

        prepped = []
        for spec in specs:
            pr = _fused_leaf_rows(spec, fstate)
            if pr is None:
                stats["fallbacks"] += 1
                stats["fallback_reason"] = "off_lattice"
                return None
            prepped.append(pr)

        g = 0
        for (g_l, _, rows, all_zero) in prepped:
            if rows and not all_zero:
                # zero-spend leaves contribute nothing: their only state (0)
                # sits on every lattice, so they must not shrink the pitch
                g = int(np.gcd(g, g_l))
        if g <= 0:
            g = 1

        shards = _fused_shards()
        Lp = -(-L // shards) * shards  # pad rows are identity leaves

        s_max = 1
        k_max = 1
        nb_needed = 1
        v_abs = 0.0  # sum of every stage's largest |value|: bounds any path
        tmax_dev = np.zeros(Lp, dtype=np.int32)
        for li, (g_l, tmax_host, rows, all_zero) in enumerate(prepped):
            if rows:
                mult = 1 if all_zero else g_l // g
                td = tmax_host * mult
                if td + 1 > _FUSED_MAX_NB:
                    stats["fallbacks"] += 1
                    stats["fallback_reason"] = "grid_overflow"
                    return None
                tmax_dev[li] = td
                nb_needed = max(nb_needed, td + 1)
                s_max = max(s_max, len(rows))
                for row in rows:
                    k_max = max(k_max, len(row[0]))
                    v_abs += row[4]

        use_tree = kind == "tree"
        tcuts = np.zeros(len(doms), dtype=np.int32)
        nbt_needed = nb_needed
        ops: tuple = ()
        depths: dict = {}
        leaves_under: dict = {}
        dom_rows: tuple = ()
        if use_tree:
            # the exact _maxplus_pair prune per internal domain: keep combined
            # states whose reconstructed float64 key is <= eff + 1e-9
            cut_by_eff: dict[float, int] = {}
            for i, (_dn, eff_d) in enumerate(doms):
                c = cut_by_eff.get(eff_d)
                if c is None:
                    ub = int((eff_d + 1e-9) * 1e6 // g) + 1
                    if ub + 1 > 4 * _FUSED_MAX_NB:
                        stats["fallbacks"] += 1
                        stats["fallback_reason"] = "grid_overflow"
                        return None
                    ks = (
                        np.arange(ub + 2, dtype=np.int64) * g
                    ).astype(np.float64) * 1e-6
                    c = int(np.flatnonzero(ks <= eff_d + 1e-9).max())
                    cut_by_eff[eff_d] = c
                tcuts[i] = c
            ops, depths, leaves_under, dom_rows = _tree_ops(tree_sig, Lp)
            # the tree grid only needs the reachable spend-sum support: every
            # state beyond min(cap cut, sum of input supports) is -inf
            support = {li: int(tmax_dev[li]) for li in range(L)}
            for l_row, r_row, o_row, d in ops:
                support[o_row] = min(
                    support[l_row] + support[r_row], int(tcuts[d])
                )
            nbt_needed = max(nb_needed, max(support.values()) + 1)

        if k_max > _FUSED_MAX_OPTS:
            stats["fallbacks"] += 1
            stats["fallback_reason"] = "grid_overflow"
            return None
        nb_pad = _pow2_at_least(nb_needed, 16)
        nbt_pad = _pow2_at_least(nbt_needed, 16) if use_tree else nb_pad
        if max(nb_pad, nbt_pad) > _FUSED_MAX_NB:
            stats["fallbacks"] += 1
            stats["fallback_reason"] = "grid_overflow"
            return None
        s_pad = max(1, -(-s_max // 8) * 8)
        k_pad = _pow2_at_least(max(k_max, 1), 4)

        names = tuple(name for name, *_ in specs)
        dom_names = tuple(dn for dn, _ in doms)
        # sticky pads: padding up is always exact (identity stages, -inf
        # option tails, masked grid tops), so never *shrink* the resident
        # tiers while the solver kind matches — churn across a pow2 boundary
        # must not flap between compactions and recompiles, and keeping tiers
        # across leaf-count changes means compaction never truncates content
        if fstate.shape is not None and fstate.shape[0] == kind:
            _pk, _pL, ps, pkk, pnb, pnbt = fstate.shape[:6]
            s_pad = max(s_pad, ps)
            k_pad = max(k_pad, pkk)
            nb_pad = max(nb_pad, pnb)
            nbt_pad = max(nbt_pad, pnbt) if use_tree else nb_pad
        nbt_pad = max(nbt_pad, nb_pad)
        # capacity-slack layout signature (DESIGN.md §17): only what the
        # jitted pipeline is specialized on — kind, leaf count, padded tiers
        # and the static tree schedule.  Everything else (global pitch g,
        # leaf names, class digests/layouts, option rows) is *content*: the
        # per-row signatures below move it through the delta-patch or
        # compaction path under an unchanged layout, with no re-jit and no
        # host round.  Row signatures fold in the leaf->global lattice
        # multiplier, so a pitch change re-uploads exactly the rows whose
        # device image (kb * mult) it moved.
        layout = (kind, L, s_pad, k_pad, nb_pad, nbt_pad, tree_sig)
        stats["slack_utilization"] = max(
            s_max / s_pad,
            k_max / k_pad,
            nb_needed / nb_pad,
            (nbt_needed / nbt_pad) if use_tree else 0.0,
        )

        # DESIGN.md §14: a device path value is a sum tree over its option
        # values with n = stages + tree depth + 1 roundings per term (the +1
        # rounds each value into the device dtype), so it is within
        # gamma_n * v_abs of the exact sum, and the device's argmax path
        # trails the host optimum by at most twice that: 2 (n + 1) u v_abs
        # with u = eps / 2 (the extra +1 absorbs gamma_n's higher-order term
        # and the float64 host's own sums)
        vdt = _kops.device_value_dtype()
        n_round = s_max + (max(depths.values()) if depths else 0) + 1
        stats["value_bound"] = (n_round + 1) * float(np.finfo(vdt).eps) * v_abs

        bank_shape = (s_pad, Lp, k_pad)
        rebuild = fstate.shape is None
        compact = not rebuild and (
            fstate.shape != layout or tuple(fstate.kb_dev.shape) != bank_shape
        )
        if compact and (
            fstate.shape[0] != kind
            or len(set(names)) != len(names)
            or len(set(fstate.names or ())) != len(fstate.names or ())
        ):
            # unmappable resident state (different solver kind, ambiguous
            # leaf identities): cold host rebuild — still a fused round
            rebuild, compact = True, False

        if shards > 1:
            # resident banks live split leaf-wise over the shard mesh, where
            # the sharded leaf scan reads them
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            bank_sharding = NamedSharding(
                _kops.leaf_shard_mesh(shards), P(None, "leaves", None)
            )

            def place(x):
                return jax.device_put(x, bank_sharding)
        else:
            place = jnp.asarray
    seg["prep_s"] = sp.seconds

    with _kops.device_value_scope():

        def upload_rows(entries):
            # entries: (s, li, kb_glob | None, vb | None); None = identity.
            # The scatter batch pads to a pow2 tier by *repeating the
            # first entry* (duplicate index, identical row: the set is
            # value-deterministic) — the jitted scatter then sees a few
            # quantized shapes instead of recompiling per churn count.
            patch = _fused_patch_fn()
            m = len(entries)
            mp = _pow2_at_least(m, 8)
            s_np = np.empty(mp, dtype=np.int32)
            l_np = np.empty(mp, dtype=np.int32)
            kb_rows = np.zeros((mp, k_pad), dtype=np.int32)
            vb_rows = np.full((mp, k_pad), -np.inf)
            for i, (s, li, kbg, vb) in enumerate(entries):
                s_np[i] = s
                l_np[i] = li
                if kbg is None:
                    vb_rows[i, 0] = 0.0
                else:
                    kb_rows[i, : len(kbg)] = kbg
                    vb_rows[i, : len(vb)] = vb
            s_np[m:] = s_np[0]
            l_np[m:] = l_np[0]
            kb_rows[m:] = kb_rows[0]
            vb_rows[m:] = vb_rows[0]
            si, lj = jnp.asarray(s_np), jnp.asarray(l_np)
            fstate.kb_dev = patch(fstate.kb_dev, si, lj, jnp.asarray(kb_rows))
            fstate.vb_dev = patch(
                fstate.vb_dev, si, lj, jnp.asarray(vb_rows, dtype=vdt)
            )
            stats["row_uploads"] += m
            fstate.last_key = None

        if rebuild:
            with Span("fused.patch") as sp:
                # cold start (or unmappable state): host-built banks, one
                # full upload — the only non-O(churn) sync point left
                kb_np = np.zeros((s_pad, Lp, k_pad), dtype=np.int32)
                vb_np = np.full((s_pad, Lp, k_pad), -np.inf)
                vb_np[:, :, 0] = 0.0  # identity padding stages/rows: spend 0, +0.0
                row_sigs: list[list] = [[None] * s_pad for _ in range(L)]
                keys_desc: list[list] = [[None] * s_pad for _ in range(L)]
                for li, (g_l, tmax_host, rows, all_zero) in enumerate(prepped):
                    mult = 1 if all_zero else g_l // g
                    for s, (kb, vb, keys, sig, _a) in enumerate(rows):
                        n = len(kb)
                        kb_np[s, li, :n] = kb * mult
                        vb_np[s, li, :n] = vb
                        vb_np[s, li, n:] = -np.inf
                        row_sigs[li][s] = (sig, mult)
                        keys_desc[li][s] = keys
                fstate.kb_dev = place(kb_np)
                fstate.vb_dev = place(vb_np.astype(vdt))
                fstate.row_sigs = row_sigs
                fstate.keys_desc = keys_desc
                fstate.shape = layout
                fstate.names = names
                fstate.g = g
                fstate.last_key = None
                fstate.last_solution = None
                stats["rebuilds"] += 1
            seg["patch_s"] += sp.seconds
        elif compact:
            with Span("fused.compact") as sp:
                # device-side compaction (DESIGN.md §17): the layout moved
                # (leaf set / pad tier / topology), so repack every row whose
                # content signature survived via one jitted gather out of the
                # old banks — clean subtrees keep their rows bit-for-bit with
                # zero upload — and scatter only the dirty rows after
                from repro.kernels import ops as _kops

                old_pos = {nm: i for i, nm in enumerate(fstate.names or ())}
                o_s_pad = int(fstate.kb_dev.shape[0])
                src_s = np.full((s_pad, Lp), -1, dtype=np.int32)
                src_l = np.full((s_pad, Lp), -1, dtype=np.int32)
                row_sigs = [[None] * s_pad for _ in range(L)]
                keys_desc = [[None] * s_pad for _ in range(L)]
                dirty: list[tuple] = []
                for li, (g_l, tmax_host, rows, all_zero) in enumerate(prepped):
                    mult = 1 if all_zero else g_l // g
                    oli = old_pos.get(names[li])
                    for s in range(s_pad):
                        if s < len(rows):
                            kb, vb, keys, sig, _a = rows[s]
                            esig = (sig, mult)
                        else:
                            kb = vb = keys = None
                            esig = None
                        row_sigs[li][s] = esig
                        keys_desc[li][s] = keys
                        if esig is None:
                            continue  # identity rows come from the init
                        if (
                            oli is not None
                            and s < o_s_pad
                            and fstate.row_sigs[oli][s] == esig
                        ):
                            src_s[s, li] = s
                            src_l[s, li] = oli
                        else:
                            dirty.append((s, li, kb * mult, vb))
                fstate.kb_dev, fstate.vb_dev = map(place, _kops.bank_compact(
                    fstate.kb_dev, fstate.vb_dev,
                    jnp.asarray(src_s), jnp.asarray(src_l), k_pad=k_pad,
                ))
                fstate.row_sigs = row_sigs
                fstate.keys_desc = keys_desc
                fstate.shape = layout
                fstate.names = names
                fstate.g = g
                fstate.last_key = None
                fstate.last_solution = None
                stats["compactions"] += 1
            seg["compact_s"] += sp.seconds
            with Span("fused.patch") as sp:
                if dirty:
                    upload_rows(dirty)
            seg["patch_s"] += sp.seconds
        else:
            with Span("fused.patch") as sp:
                # delta patch: upload only the rows whose content signature
                # moved (class churn / pitch moves / headroom drift), via
                # donated scatter
                entries: list[tuple] = []
                for li, (g_l, tmax_host, rows, all_zero) in enumerate(prepped):
                    mult = 1 if all_zero else g_l // g
                    for s in range(s_pad):
                        if s < len(rows):
                            kb, vb, keys, sig, _a = rows[s]
                            esig = (sig, mult)
                        else:
                            kb = vb = keys = None
                            esig = None
                        if fstate.row_sigs[li][s] == esig:
                            continue
                        entries.append(
                            (s, li, None if kb is None else kb * mult, vb)
                        )
                        fstate.row_sigs[li][s] = esig
                        fstate.keys_desc[li][s] = keys
                if entries:
                    upload_rows(entries)
                fstate.names = names
                fstate.g = g
            seg["patch_s"] += sp.seconds

        tree_static = None
        if use_tree:
            waves = _tree_waves(ops, depths, leaves_under, nb_pad, nbt_pad)
            tree_static = (waves, dom_rows)
        run = _fused_pipeline_fn(
            tree_static, L, Lp, s_pad, k_pad, nb_pad, nbt_pad,
            shards, not _kops.on_tpu(),
        )
        # launch and wait apart: the call returns once the program is
        # enqueued, block_until_ready waits for the device
        with Span("fused.launch") as sp:
            out = run(
                fstate.kb_dev,
                fstate.vb_dev,
                jnp.asarray(tmax_dev),
                jnp.asarray(tcuts),
            )
        launch_s = sp.seconds
        with Span("fused.wait") as sp:
            out = jax.block_until_ready(out)
        seg["dispatch_s"] += launch_s + sp.seconds
        stats["rounds"] += 1

    with Span("fused.backtrack") as sp:
        if not np.isfinite(float(out[3])):
            # no feasible root state: keep the host path authoritative
            stats["fallbacks"] += 1
            stats["fallback_reason"] = "no_feasible_root"
            return None
        stats["fallback_reason"] = ""
        t_root = int(out[0])
        t_leaf = np.asarray(out[1])
        js = np.asarray(out[2])

        leaf_meta = []
        for name, eff, plan, curves_, curve_keys in specs:
            tok = (
                st.token(("leaf", (plan.layout, _qkey(eff))))
                if st is not None
                else None
            )
            leaf_meta.append((tok, plan.key))

        # layout no longer pins pitch / leaf names / class layouts (they are
        # patchable content now), so the short-circuit key carries them
        # explicitly alongside the row signatures
        dec_key = (
            layout,
            g,
            names,
            dom_names,
            tuple(tuple(rs) for rs in fstate.row_sigs),
            tuple(leaf_meta),
            t_root,
            t_leaf.tobytes(),
            js.tobytes(),
        )
    seg["backtrack_s"] += sp.seconds
    with Span("fused.assembly") as sp:
        if dec_key == fstate.last_key and fstate.last_solution is not None:
            # unchanged device decision vector: the previous solution is the
            # bit-identical answer — skip the host assembly entirely
            fstate.stats["short_circuits"] += 1
            return fstate.last_solution

        picks: dict[str, tuple[float, float, tuple[float, float]]] = {}
        domain_spent: dict[str, float] | None = (
            {} if kind in ("tree", "leaf_root") else None
        )
        if use_tree:
            # per-internal-domain spends off the device backtrack: the
            # float64(t * g) * 1e-6 reconstruction is the host frontier-key
            # round-trip, so the values are bitwise _backtrack_frontier's
            t_dom = np.asarray(out[4])
            for i, (dname, _de) in enumerate(doms):
                domain_spent[dname] = float(
                    np.float64(int(t_dom[i]) * g) * 1e-6
                )
        leaf_totals: list[tuple[float, float]] = []
        for li, ((name, eff, plan, curves_, curve_keys), (tok, _pk)) in enumerate(
            zip(specs, leaf_meta)
        ):
            u = float(np.float64(int(t_leaf[li]) * g) * 1e-6)
            if domain_spent is not None:
                domain_spent[name] = u
            n_stages = len(plan.classes)
            spends = [
                float(fstate.keys_desc[li][s][int(js[li, s])])
                for s in range(n_stages)
            ]
            skey = None
            if st is not None and plan.key is not None:
                skey = (tok, plan.key, tuple(spends))
                hit = st.leaf_sol_cache.get(skey)
                if hit is not None:
                    picks.update(hit[0])
                    leaf_totals.append((hit[1], hit[2]))
                    continue
            lp, lt, ls = _assemble_plan(
                plan, curve_keys, curves_, spends, pick_cache
            )
            if skey is not None:
                st.leaf_sol_cache[skey] = (lp, lt, ls)
            picks.update(lp)
            leaf_totals.append((lt, ls))

        total = 0.0
        spent = 0.0
        for lt, ls in leaf_totals:
            total += lt
            spent += ls
        sol = MCKPSolution(
            total_value=total, spent=spent, picks=picks, domain_spent=domain_spent
        )
        fstate.last_key = dec_key
        fstate.last_solution = sol
    seg["assembly_s"] += sp.seconds
    return sol


def solve_grouped_fused(
    groups: Sequence[GroupedOptions],
    budget: float,
    *,
    fstate: FusedState,
    curve_cache: MutableMapping | None = None,
    pick_cache: MutableMapping | None = None,
    plan_cache: MutableMapping | None = None,
    chain_cache: MutableMapping | None = None,
) -> MCKPSolution | None:
    """Fused device-resident form of :func:`solve_sparse_grouped`.

    Returns the bit-for-bit identical solution, or None to fall back to
    the host path (off-lattice keys, oversized grids, empty rounds,
    infeasible roots).  Group/class churn is *not* a fallback: it
    patches or compacts the resident banks and solves fused in the same
    call (DESIGN.md §17).
    """
    plan = _leaf_plan(groups, plan_cache)
    curves_, curve_keys = _class_curves(
        plan.classes, budget, curve_cache, chain_cache
    )
    eff = float(budget)
    specs = [(None, eff, plan, curves_, curve_keys)]
    sol = _fused_run(
        specs, eff, "flat", None, (), pick_cache=pick_cache, fstate=fstate
    )
    return sol


def solve_hierarchical_fused(
    root: DomainGroups,
    budget: float,
    *,
    state: HierState,
    fstate: FusedState,
) -> MCKPSolution | None:
    """Fused device-resident form of the N-level sparse
    :func:`solve_hierarchical`.

    Walks the arbitrary-depth domain tree on the host exactly like
    ``_sparse_frontier`` (same cascaded effective caps, plans and class
    curves — shared caches), lowering it to a static combine schedule
    plus a dynamic per-domain cap-cut vector, then runs the whole
    decision pipeline on device (DESIGN.md §16).  Returns None to fall
    back to the host path: off-lattice keys, oversized grids, empty
    rounds or an infeasible root — ``fstate.stats['fallback_reason']``
    says which.  Structure changes (new class layouts, membership churn,
    topology edits) are served fused in the same round by row patching
    or device-side compaction of the resident banks (DESIGN.md §17).
    """
    eff_root = _domain_eff(root, float(budget))
    if not root.children:
        plan = _leaf_plan(root.groups, state.plan_cache)
        curves_, curve_keys = _class_curves(
            plan.classes, eff_root, state.curve_cache, state.chain_cache
        )
        specs = [(root.name, eff_root, plan, curves_, curve_keys)]
        return _fused_run(
            specs,
            eff_root,
            "leaf_root",
            None,
            (),
            pick_cache=state.pick_cache,
            fstate=fstate,
            st=state,
        )

    specs = []
    doms: list[tuple[str, float]] = []

    def walk(dom: DomainGroups, b: float):
        eff = _domain_eff(dom, b)
        if dom.children:
            child_sigs = tuple(walk(c, eff) for c in dom.children)
            doms.append((dom.name, eff))
            return ("d", len(doms) - 1, child_sigs)
        plan = _leaf_plan(dom.groups, state.plan_cache)
        curves_, curve_keys = _class_curves(
            plan.classes, eff, state.curve_cache, state.chain_cache
        )
        specs.append((dom.name, eff, plan, curves_, curve_keys))
        return len(specs) - 1

    tree_sig = walk(root, float(budget))
    return _fused_run(
        specs,
        eff_root,
        "tree",
        tree_sig,
        tuple(doms),
        pick_cache=state.pick_cache,
        fstate=fstate,
        st=state,
    )


# ---------------------------------------------------------------------------
# Exhaustive brute force (Oracle ground truth for small cases)
# ---------------------------------------------------------------------------


def brute_force(options: Sequence[OptionTable], budget: float) -> MCKPSolution:
    """Exhaustive DFS over the cross product of option sets.

    Exponential — used for the §6.3 Oracle on <= ~10 apps with pruned
    option sets, and to certify the DP solvers in tests.  A simple
    optimistic bound (sum of per-app max remaining values) prunes branches.
    """
    n = len(options)
    # optimistic suffix bound
    suffix_max = np.zeros(n + 1)
    for i in range(n - 1, -1, -1):
        suffix_max[i] = suffix_max[i + 1] + float(np.max(options[i].values))

    best = {"total": -1.0, "choice": [0] * n}
    choice = [0] * n

    def dfs(i: int, used: float, value: float) -> None:
        if value + suffix_max[i] <= best["total"]:
            return
        if i == n:
            if value > best["total"]:
                best["total"] = value
                best["choice"] = list(choice)
            return
        opt = options[i]
        for j in range(opt.k - 1, -1, -1):
            e = float(opt.costs[j])
            if used + e > budget + 1e-9:
                continue
            choice[i] = j
            dfs(i + 1, used + e, value + float(opt.values[j]))
        choice[i] = 0

    dfs(0, 0.0, 0.0)
    picks: dict[str, tuple[float, float, tuple[float, float]]] = {}
    for i, opt in enumerate(options):
        j = best["choice"][i]
        picks[opt.name] = (
            float(opt.costs[j]),
            float(opt.values[j]),
            (float(opt.caps[j, 0]), float(opt.caps[j, 1])),
        )
    spent = sum(c for c, _, _ in picks.values())
    return MCKPSolution(total_value=best["total"], spent=spent, picks=picks)
