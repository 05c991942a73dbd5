"""The benchmark's own model of a deployment: power tree and node state.

Plain data that the traffic generator steps and the reference reads.
The program is handed a copy of it as its inputs (``bench/deploy.py``)
and never shares these objects.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from bench import suite

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_json(rel: str) -> dict:
    with open(os.path.join(BENCH_DIR, rel)) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Tree:
    """A balanced power-domain tree in DFS preorder (root first)."""

    names: tuple[str, ...]
    parent: np.ndarray  # [D] int, -1 for the root
    depth: np.ndarray  # [D] int, root 0
    leaf_ids: np.ndarray  # [n_leaves] preorder ids of the leaves, in order
    leaf_ranges: tuple[tuple[int, int], ...]  # half-open node-id range per leaf

    def aggregate(self, leaf_values: np.ndarray) -> np.ndarray:
        """Per-leaf values (leaf order) summed up to every domain."""
        out = np.zeros(len(self.names), dtype=np.float64)
        out[self.leaf_ids] = leaf_values
        for i in range(len(self.names) - 1, 0, -1):
            out[self.parent[i]] += out[i]
        return out

    def children(self, i: int) -> np.ndarray:
        return np.flatnonzero(self.parent == i)


def build_tree(n_nodes: int, fanouts: list[int], level_names: list[str]) -> Tree:
    """site -> level 1 -> ... -> leaves, leaves owning contiguous
    near-equal node ranges tiling [0, n_nodes)."""
    n_leaves = int(np.prod(fanouts))
    bounds = np.linspace(0, n_nodes, n_leaves + 1).astype(int)
    names, parent, depth, leaf_ids, ranges = [], [], [], [], []
    counters = [0] * len(fanouts)

    def visit(d: int, par: int) -> None:
        me = len(names)
        if d == 0:
            names.append("site")
        else:
            names.append(f"{level_names[d - 1]}{counters[d - 1]}")
            counters[d - 1] += 1
        parent.append(par)
        depth.append(d)
        if d == len(fanouts):
            k = len(leaf_ids)
            leaf_ids.append(me)
            ranges.append((int(bounds[k]), int(bounds[k + 1])))
            return
        for _ in range(fanouts[d]):
            visit(d + 1, me)

    visit(0, -1)
    return Tree(
        names=tuple(names),
        parent=np.asarray(parent, dtype=np.int64),
        depth=np.asarray(depth, dtype=np.int64),
        leaf_ids=np.asarray(leaf_ids, dtype=np.int64),
        leaf_ranges=tuple(ranges),
    )


class Deployment:
    """One configuration file made concrete: suite, grid, tree, caps."""

    def __init__(self, config: dict):
        self.config = config
        sysc = config["system"]
        self.grid = sysc["grid"]
        self.params = suite.paper_suite(sysc)
        self.app_names = tuple(self.params)
        self.app_index = {a: i for i, a in enumerate(self.app_names)}
        self.sclass = np.array([self.params[a]["sclass"] for a in self.app_names])
        #: per-app natural (uncapped) draw, [n_apps, 2]
        self.natural = np.array([self.params[a]["natural"] for a in self.app_names])
        self.init_caps = tuple(float(x) for x in config["initial_caps"])
        self.n_nodes = int(config["n_nodes"])
        topo = config["topology"]
        self.tree = build_tree(self.n_nodes, topo["fanouts"], topo["level_names"])
        bud = config["budget"]
        #: the round budget's top and bottom (W): ``w_per_node`` watts a
        #: node over the whole cluster, down to ``floor_frac`` of that
        top = float(bud["w_per_node"]) * self.n_nodes
        self.envelope = (float(bud["floor_frac"]) * top, top)
        self.cap_tol_w = float(config["cap_tolerance_w"])
        #: receiver apps: nodes of these apps never donate at the initial caps
        self.receiver_apps = np.flatnonzero(
            ~self.donor_app(np.arange(len(self.app_names)))
        )
        self.domain_caps: np.ndarray | None = None

    def donor_app(self, app_idx: np.ndarray) -> np.ndarray:
        """A node donates iff its natural draw sits more than 1 W below
        its caps on both components."""
        nat = self.natural[app_idx]
        c0, g0 = self.init_caps
        return (c0 - nat[:, 0] > 1.0) & (g0 - nat[:, 1] > 1.0)

    def committed_by_leaf(self, state: "NodeState") -> np.ndarray:
        """[n_leaves] committed watts: a receiver commits its caps, a donor
        its natural draw, a dead node nothing."""
        app = state.app
        donor = self.donor_app(app)
        nat = self.natural[app].sum(axis=1)
        per_node = np.where(donor, nat, sum(self.init_caps))
        per_node = np.where(state.alive, per_node, 0.0)
        return np.bincount(
            state.leaf, weights=per_node, minlength=len(self.tree.leaf_ids)
        )

    def set_domain_caps(self, state: "NodeState") -> np.ndarray:
        """Each domain's cap: its committed draw at the start plus its
        level's fraction of its node-proportional share of the budget's
        top; the root is unconstrained (the round budget binds)."""
        topo = self.config["topology"]
        tree = self.tree
        committed = tree.aggregate(self.committed_by_leaf(state))
        counts = tree.aggregate(
            np.array([hi - lo for lo, hi in tree.leaf_ranges], dtype=np.float64)
        )
        caps = np.full(len(tree.names), 1e18)
        fracs = topo["level_fracs"]
        for i in range(1, len(tree.names)):
            frac = fracs[tree.depth[i] - 1]
            caps[i] = float(committed[i]) + frac * self.envelope[1] * (
                counts[i] / self.n_nodes
            )
        self.domain_caps = caps
        return caps


@dataclasses.dataclass
class NodeState:
    """Columnar node state, indexed by node id."""

    app: np.ndarray  # [n] int, current app (phase) index
    name_app: np.ndarray  # [n] int, the app the node was created with
    alive: np.ndarray  # [n] bool
    leaf: np.ndarray  # [n] int, leaf index (leaf order)

    def copy(self) -> "NodeState":
        return NodeState(
            self.app.copy(), self.name_app.copy(), self.alive.copy(),
            self.leaf.copy(),
        )

    def name(self, nid: int, app_names) -> str:
        return f"{app_names[self.name_app[nid]]}#n{nid}"


def initial_state(dep: Deployment, rng: np.random.Generator) -> NodeState:
    """Place the apps by cycling a seeded permutation of the suite over
    the node ids, so every seed holds the same multiset of apps."""
    order = rng.permutation(len(dep.app_names))
    app = order[np.arange(dep.n_nodes) % len(order)]
    leaf = np.empty(dep.n_nodes, dtype=np.int64)
    for k, (lo, hi) in enumerate(dep.tree.leaf_ranges):
        leaf[lo:hi] = k
    return NodeState(
        app=app.astype(np.int64),
        name_app=app.astype(np.int64).copy(),
        alive=np.ones(dep.n_nodes, dtype=bool),
        leaf=leaf,
    )
