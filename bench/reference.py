"""The plain reference that decides ``correct``.

A straightforward, independent statement of what one EcoShift control
round must produce, computed from the benchmark's own deployment model
(``bench/cluster.py``) and the round's generated inputs, importing
nothing of the program:

* receivers are the alive nodes whose natural draw is not more than 1 W
  below their caps on both components (the rest donate);
* each domain may spend at most its cap minus its committed draw (a
  receiver commits its caps, a donor its natural draw, a dead node
  nothing); the whole cluster at most the round's budget;
* a receiver's options are the grid cap pairs at or above its baseline
  caps; an option costs its extra watts and is worth its relative
  runtime reduction ``(T0 - T(c, g)) / T0``;
* the answer maximises the summed worth over every receiver, subject to
  every domain's headroom and the budget (a multiple-choice knapsack on
  a tree), solved here by a plain per-receiver dynamic programme over
  the grid's watt steps.

``check_round`` holds a round's answer to this: the receiver set, caps
on the grid and at or above the baseline, every domain's spend and the
budget within the configuration's cap tolerance, and both the summed
worth the round reports and the worth of its caps against the optimum.
``control_answer`` is the control: the reference put in the program's
place, its DP in bfloat16 (the precision below the float32 the
configurations state), its caps valued in float64 on the host as the
program values its own.
"""

from __future__ import annotations

import numpy as np

from bench import suite
from bench.cluster import Deployment, NodeState

#: feasibility slack of a spend against a headroom (watts)
FEAS_EPS_W = 1e-9


def option_curves(dep: Deployment):
    """Per app, the best worth at each exact extra-power cost.

    Returns ``(worth [A, U+1], cpu [A, U+1], gpu [A, U+1])`` over cost
    units of one grid step: -inf where no grid pair costs that much,
    worth 0 at cost 0 (the baseline caps)."""
    g = dep.grid
    step = float(g["step"])
    c0, g0 = dep.init_caps
    cl = np.arange(g["cpu_min"], g["cpu_max"] + 0.5 * step, step)
    gl = np.arange(g["gpu_min"], g["gpu_max"] + 0.5 * step, step)
    if not (np.isclose(cl, c0).any() and np.isclose(gl, g0).any()):
        raise ValueError(f"initial caps {dep.init_caps} are off the grid")
    cc, gg = np.meshgrid(cl[cl >= c0 - 1e-9], gl[gl >= g0 - 1e-9], indexing="ij")
    cc, gg = cc.ravel(), gg.ravel()
    units = np.rint((cc - c0 + gg - g0) / step).astype(np.int64)
    n_u = int(units.max()) + 1
    n_a = len(dep.app_names)
    worth = np.full((n_a, n_u), -np.inf)
    cpu = np.zeros((n_a, n_u))
    gpu = np.zeros((n_a, n_u))
    for a, name in enumerate(dep.app_names):
        p = dep.params[name]
        t0 = suite.runtime(p, c0, g0)
        w = (t0 - suite.runtime(p, cc, gg)) / t0
        for j in np.lexsort((-w, units)):  # per cost: the first is the best
            u = units[j]
            if worth[a, u] == -np.inf:
                worth[a, u], cpu[a, u], gpu[a, u] = w[j], cc[j], gg[j]
        worth[a, 0], cpu[a, 0], gpu[a, 0] = 0.0, c0, g0
    return worth, cpu, gpu


class Round:
    """The reference's view of one round's inputs."""

    def __init__(self, dep: Deployment, state: NodeState, budget: float):
        self.dep, self.state, self.budget = dep, state, float(budget)
        tree = dep.tree
        self.recv = np.flatnonzero(state.alive & ~dep.donor_app(state.app))
        committed = tree.aggregate(dep.committed_by_leaf(state))
        self.extra = np.clip(dep.domain_caps - committed, 0.0, None)
        step = float(dep.grid["step"])
        self.units = np.floor((self.extra + FEAS_EPS_W) / step).astype(np.int64)
        self.units[0] = min(
            self.units[0], int(np.floor((self.budget + FEAS_EPS_W) / step))
        )
        self.names = [state.name(int(n), dep.app_names) for n in self.recv]


def solve(rnd: Round, curves, dtype=np.float64) -> tuple[float, np.ndarray]:
    """Optimal summed worth and each receiver's cost units (``rnd.recv``
    order), every addition and comparison in ``dtype``."""
    dep, tree = rnd.dep, rnd.dep.tree
    worth = curves[0].astype(dtype)
    neg = np.asarray(-np.inf, dtype)
    n_leaf = len(tree.leaf_ids)
    leaf_cap = rnd.units[tree.leaf_ids]
    cmax = int(leaf_cap.max())
    leaf_of = rnd.state.leaf[rnd.recv]
    order = np.argsort(leaf_of, kind="stable")
    counts = np.bincount(leaf_of, minlength=n_leaf)
    n_stage = int(counts.max()) if len(order) else 0
    # slot [leaf, stage] -> position in rnd.recv (-1: identity stage)
    slot = np.full((n_leaf, max(n_stage, 1)), -1, dtype=np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    for li in range(n_leaf):
        slot[li, : counts[li]] = order[starts[li]: starts[li] + counts[li]]
    apps = rnd.state.app[rnd.recv]
    n_u = min(worth.shape[1], cmax + 1)
    identity = np.full(n_u, neg, dtype)
    identity[0] = 0
    t_idx = np.arange(cmax + 1)
    over = t_idx[None, :] > leaf_cap[:, None]
    dp = np.full((n_leaf, cmax + 1), neg, dtype)
    dp[:, 0] = 0
    args = np.zeros((n_stage, n_leaf, cmax + 1), dtype=np.int16)
    for s in range(n_stage):
        pos = slot[:, s]
        f = np.where((pos >= 0)[:, None], worth[apps[pos], :n_u], identity[None, :])
        best = np.full_like(dp, neg)
        arg = np.zeros(dp.shape, dtype=np.int16)
        for u in range(n_u):
            cand = np.full_like(dp, neg)
            cand[:, u:] = dp[:, : cmax + 1 - u] + f[:, u: u + 1]
            better = cand > best
            best = np.where(better, cand, best)
            arg = np.where(better, np.int16(u), arg)
        best[over] = neg
        dp, args[s] = best, arg

    frontier: dict[int, np.ndarray] = {
        int(d): dp[k, : leaf_cap[k] + 1] for k, d in enumerate(tree.leaf_ids)
    }
    splits: dict[int, list] = {}
    for d in range(len(tree.names) - 1, -1, -1):
        kids = tree.children(d)
        if not len(kids):
            continue
        cap = int(rnd.units[d])
        acc = frontier[int(kids[0])][: cap + 1]
        sp = []
        for c in kids[1:]:
            acc, win = _maxplus(acc, frontier[int(c)], cap, neg)
            sp.append(win)
        frontier[d], splits[d] = acc, sp
    root = frontier[0]
    t_root = int(np.argmax(root))
    best_val = float(root[t_root])

    # backtrack: domain splits top-down, then each leaf's stages
    t_of = {0: t_root}
    for d in range(len(tree.names)):
        kids = tree.children(d)
        if not len(kids):
            continue
        t = t_of[d]
        for c, win in zip(kids[:0:-1], splits[d][::-1]):
            j = int(win[t])
            t_of[int(c)] = j
            t -= j
        t_of[int(kids[0])] = t
    units = np.zeros(len(rnd.recv), dtype=np.int64)
    for k, d in enumerate(tree.leaf_ids):
        t = t_of[int(d)]
        for s in range(n_stage - 1, -1, -1):
            u = int(args[s, k, t])
            if slot[k, s] >= 0:
                units[slot[k, s]] = u
            t -= u
    return best_val, units


def _maxplus(a: np.ndarray, b: np.ndarray, cap: int, neg):
    """out[t] = max_j a[t - j] + b[j] for t <= cap; first max in
    ascending j; ``win[t]`` the winning j."""
    n = min(cap + 1, len(a) + len(b) - 1)
    out = np.full(n, neg, a.dtype)
    win = np.zeros(n, dtype=np.int64)
    for j in range(min(len(b), n)):
        m = min(len(a), n - j)
        cand = np.full(n, neg, a.dtype)
        cand[j: j + m] = a[:m] + b[j]
        better = cand > out
        out = np.where(better, cand, out)
        win = np.where(better, j, win)
    return out, win


def worth_of(rnd: Round, cpu: np.ndarray, gpu: np.ndarray) -> np.ndarray:
    """Each receiver's worth at caps ``(cpu, gpu)`` (``rnd.recv`` order)."""
    dep = rnd.dep
    c0, g0 = dep.init_caps
    apps = rnd.state.app[rnd.recv]
    out = np.zeros(len(apps))
    for a in np.unique(apps):
        m = apps == a
        p = dep.params[dep.app_names[a]]
        t0 = suite.runtime(p, c0, g0)
        out[m] = (t0 - suite.runtime(p, cpu[m], gpu[m])) / t0
    return out


def caps_from_units(rnd: Round, curves, units: np.ndarray) -> dict:
    apps = rnd.state.app[rnd.recv]
    cpu, gpu = curves[1][apps, units], curves[2][apps, units]
    return {nm: (float(c), float(g)) for nm, c, g in zip(rnd.names, cpu, gpu)}


def control_answer(rnd: Round, curves) -> tuple[dict, float]:
    """The control's answer: (caps, reported worth).  The DP runs in
    bfloat16; its caps are valued in float64, as the program values the
    caps it picks."""
    import ml_dtypes

    _val, units = solve(rnd, curves, ml_dtypes.bfloat16)
    caps = caps_from_units(rnd, curves, units)
    apps = rnd.state.app[rnd.recv]
    cpu, gpu = curves[1][apps, units], curves[2][apps, units]
    return caps, float(worth_of(rnd, cpu, gpu).sum())


def check_round(rnd: Round, curves, caps: dict, reported: float) -> dict:
    """Hold one round's answer to the reference: its caps (name ->
    (cpu, gpu)) and the summed worth it reports for them.

    ``value_err_rel``: the larger gap to the optimum of the reported
    worth and of the worth of the caps, relative to the optimum."""
    dep = rnd.dep
    step = float(dep.grid["step"])
    c0, g0 = dep.init_caps
    want = set(rnd.names)
    mismatch = len(want.symmetric_difference(caps))
    cg = np.array([caps.get(nm, (c0, g0)) for nm in rnd.names], dtype=np.float64)
    cg = cg.reshape(len(rnd.names), 2)
    cpu, gpu = cg[:, 0], cg[:, 1]
    g = dep.grid

    def on_grid(x, lo, base, hi):
        k = (x - lo) / step
        return (np.abs(k - np.rint(k)) < 1e-9) & (x >= base - 1e-9) & (x <= hi + 1e-9)

    off_grid = int(np.count_nonzero(
        ~(on_grid(cpu, g["cpu_min"], c0, g["cpu_max"])
          & on_grid(gpu, g["gpu_min"], g0, g["gpu_max"]))
    ))
    spend_node = (cpu - c0) + (gpu - g0)
    leaf_spend = np.bincount(
        rnd.state.leaf[rnd.recv], weights=spend_node,
        minlength=len(dep.tree.leaf_ids),
    )
    spend = dep.tree.aggregate(leaf_spend)
    over = max(
        float(np.max(spend[1:] - rnd.extra[1:])) if len(spend) > 1 else 0.0,
        float(spend[0] - min(rnd.extra[0], rnd.budget)),
    )
    opt, _ = solve(rnd, curves)
    got = float(worth_of(rnd, cpu, gpu).sum())
    scale = opt if opt > 0 else 1.0
    return {
        "value_err_rel": max(abs(reported - opt), abs(got - opt)) / scale,
        "overdraw_w": max(0.0, over),
        "receiver_mismatch": mismatch,
        "off_grid": off_grid,
        "opt": opt,
        "got": got,
        "spent_w": float(spend[0]),
    }
