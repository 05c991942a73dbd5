"""The one traffic generator: a budget signal and node churn, from data.

A traffic mix is a JSON file under ``bench/traffic/`` read by name:

    {"budget": {"signal": "co2_day", "invert": true, "step": 4, "jitter": 0.02},
     "churn": {"fraction": 0.10, "slowdowns": [1.0, 1.3, 1.7],
               "mix": {"straggler": 0.60, "phase": 0.39, "failure": 0.01}}}

``budget``: each round's cluster budget follows the named day curve
(``bench/signals/<signal>.json``, ``step`` points per round, wrapping,
from an offset drawn from the seed), inverted so that clean power means a high
budget, scaled into the middle ``1 - 2 jitter`` of the configuration's
envelope, plus a seeded uniform jitter of +-``jitter`` of the envelope,
so every budget lies in the envelope and no two rounds share one.

``churn`` (absent or ``fraction`` 0: no node events): each round
``fraction`` of the alive nodes is hit.  Stragglers toggle their
slowdown; phase changes move a receiver to another receiver app (so
every node keeps its donor or receiver role and every domain its
committed draw); a failure is replaced at once by a node of the same app
in the same leaf domain.  Node count, each domain's population and its
headroom therefore stay constant over any window.

Events are plain tuples; ``bench/deploy.py`` turns them into the
program's event objects:

    ("straggler", node_id, slowdown)   ("phase", node_id, app)
    ("failure", node_id)               ("arrival", node_id, app, leaf)
"""

from __future__ import annotations

import numpy as np

from bench.cluster import Deployment, NodeState, load_json

#: independent random streams drawn from one seed
STREAM_POPULATION, STREAM_BUDGET, STREAM_CHURN, STREAM_SAMPLE = range(4)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


class Traffic:
    """Round-by-round budgets and events for one (deployment, mix, seed)."""

    def __init__(self, dep: Deployment, mix: dict, seed: int, state: NodeState):
        self.dep = dep
        self.state = state
        bud = mix["budget"]
        sig = np.asarray(load_json(f"signals/{bud['signal']}.json")["values"], float)
        span = sig.max() - sig.min()
        level = (sig.max() - sig) / span if bud.get("invert") else (sig - sig.min()) / span
        self._level = level
        self._jitter = float(bud.get("jitter", 0.0))
        self._step = int(bud.get("step", 1))
        self._seed = int(seed)
        self._offset = int(rng_for(seed, STREAM_BUDGET).integers(len(level)))
        self._crng = rng_for(seed, STREAM_CHURN)
        churn = mix.get("churn") or {}
        self._frac = float(churn.get("fraction", 0.0))
        self._mix = churn.get("mix", {})
        self._slowdowns = np.asarray(churn.get("slowdowns", [1.0]), float)
        self.round = 0

    def budget(self, r: int) -> float:
        """Round ``r``'s budget (a pure function of the seed and ``r``)."""
        lo, hi = self.dep.envelope
        u = np.random.default_rng(
            np.random.SeedSequence([self._seed, STREAM_BUDGET, r])
        ).uniform(-self._jitter, self._jitter)
        j = self._jitter
        pos = (self._offset + r * self._step) % len(self._level)
        x = j + (1.0 - 2.0 * j) * self._level[pos] + u
        return float(lo + (hi - lo) * x)

    def next_round(self, burst: float = 1.0) -> tuple[int, float, list[tuple]]:
        """Advance one round: (round index, budget, events), with the
        events already applied to ``state``.  ``burst`` scales the
        round's churn (warm-up only)."""
        r = self.round
        self.round += 1
        return r, self.budget(r), self._events(burst)

    def _events(self, burst: float) -> list[tuple]:
        st = self.state
        alive = np.flatnonzero(st.alive)
        k = min(len(alive), int(round(burst * self._frac * len(alive))))
        if k == 0:
            return []
        rng = self._crng
        n_fail = int(round(k * self._mix.get("failure", 0.0)))
        n_phase = int(round(k * self._mix.get("phase", 0.0)))
        victims = rng.choice(alive, size=k, replace=False)
        fails, rest = victims[:n_fail], victims[n_fail:]
        recv = ~self.dep.donor_app(st.app[rest])
        phase_ids = rest[recv][:n_phase]
        strag_ids = np.setdiff1d(rest, phase_ids, assume_unique=True)
        ev: list[tuple] = []
        slow = self._slowdowns[rng.integers(len(self._slowdowns), size=len(strag_ids))]
        for nid, s in zip(strag_ids.tolist(), slow.tolist()):
            ev.append(("straggler", nid, s))
        targets = self.dep.receiver_apps[
            rng.integers(len(self.dep.receiver_apps), size=len(phase_ids))
        ]
        names = self.dep.app_names
        for nid, a in zip(phase_ids.tolist(), targets.tolist()):
            st.app[nid] = a
            ev.append(("phase", nid, names[a]))
        for nid in fails.tolist():
            new = len(st.app)
            a, leaf = int(st.app[nid]), int(st.leaf[nid])
            st.alive[nid] = False
            st.app = np.append(st.app, a)
            st.name_app = np.append(st.name_app, a)
            st.alive = np.append(st.alive, True)
            st.leaf = np.append(st.leaf, leaf)
            ev.append(("failure", nid))
            ev.append(("arrival", new, names[a], leaf))
        return ev
