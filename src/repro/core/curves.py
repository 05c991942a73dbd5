"""Per-application improvement curves F_i(b) (paper §3.2.2, Eq. 1).

For receiver ``i`` with baseline caps ``(c̄, ḡ)`` we enumerate upgraded cap
pairs on the feasible grid, compute the predicted relative improvement
``I_i(c, g)`` and the extra-power cost ``e = (c - c̄) + (g - ḡ)``, and then

 * keep only the best improvement at each distinct cost (Algorithm 1 l.2-18),
 * prune dominated options (an option is dominated if a cheaper-or-equal
   option achieves >= improvement).

The resulting option table is what every solver in ``repro.core.mckp``
consumes: the host DP directly, the device pipeline after mapping it onto
its spend lattice.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.surfaces import PowerSurface
from repro.core.types import CapGrid


@dataclasses.dataclass(frozen=True)
class OptionTable:
    """Pruned options for one receiver, sorted by increasing cost.

    Always contains the zero-cost option (0 extra power, 0 improvement,
    baseline caps) so a receiver may legally receive nothing.
    """

    name: str
    costs: np.ndarray  # [K] float64, strictly increasing, costs[0] == 0
    values: np.ndarray  # [K] float64, strictly increasing after pruning
    caps: np.ndarray  # [K, 2] the (c, g) pair realizing each option

    def __post_init__(self):
        assert self.costs.shape == self.values.shape
        assert self.caps.shape == (len(self.costs), 2)
        assert self.costs[0] == 0.0

    @property
    def k(self) -> int:
        return len(self.costs)


def build_options(
    name: str,
    surface: PowerSurface,
    baseline: tuple[float, float],
    grid: CapGrid,
    budget: float,
) -> OptionTable:
    """Enumerate + prune the upgraded-cap option set for one receiver.

    Matches Algorithm 1 lines 2-18: for every grid pair with
    ``c >= c̄, g >= ḡ`` and cost ``e <= B`` keep the best improvement at each
    distinct ``e``; then drop options dominated by cheaper ones, producing a
    strictly-increasing (cost, value) staircase.
    """
    c0, g0 = baseline
    pairs = grid.pairs()
    keep = (pairs[:, 0] >= c0 - 1e-9) & (pairs[:, 1] >= g0 - 1e-9)
    pairs = pairs[keep]
    cost = (pairs[:, 0] - c0) + (pairs[:, 1] - g0)
    feas = cost <= budget + 1e-9
    pairs, cost = pairs[feas], cost[feas]
    impr = np.asarray(surface.improvement(baseline, pairs[:, 0], pairs[:, 1]))

    # best improvement at each distinct cost
    order = np.lexsort((-impr, cost))
    pairs, cost, impr = pairs[order], cost[order], impr[order]
    first = np.ones(len(cost), dtype=bool)
    first[1:] = cost[1:] > cost[:-1] + 1e-9
    pairs, cost, impr = pairs[first], cost[first], impr[first]

    # ensure the zero-cost baseline option exists with value exactly 0
    if len(cost) == 0 or cost[0] > 1e-9:
        pairs = np.concatenate([[[c0, g0]], pairs], axis=0)
        cost = np.concatenate([[0.0], cost])
        impr = np.concatenate([[0.0], impr])
    else:
        impr[0] = 0.0
        pairs[0] = (c0, g0)

    # prune dominated: keep only strictly-improving staircase
    keep_idx = [0]
    best = impr[0]
    for j in range(1, len(cost)):
        if impr[j] > best + 1e-12:
            keep_idx.append(j)
            best = impr[j]
    sel = np.array(keep_idx)
    return OptionTable(name=name, costs=cost[sel], values=impr[sel], caps=pairs[sel])
