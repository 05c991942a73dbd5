"""Stateful policy controllers for the multi-round cluster engine.

One controller per entry in ``repro.core.policies.POLICIES``.  Each wraps
the existing pure policy function but *carries warm state across rounds*:

 * ``EcoShiftController`` / ``OracleController`` cache per-receiver
   ``OptionTable``s keyed by (instance, baseline, surface identity).  The
   tables are budget-independent (built to the grid's headroom ceiling; all
   MCKP solvers already skip over-budget options), so after a node failure
   only the *pool* changes and re-optimization reuses every surviving
   table — the incremental re-solve the paper's fault-tolerance study
   needs.  Event hooks (``invalidate``) drop entries whose surface or
   baseline changed (stragglers, phase changes).
 * ``EcoShiftOnlineController`` closes the prediction loop: it sources its
   surfaces from a telemetry-driven ``repro.cluster.predictor
   .OnlinePredictor`` instead of a frozen mapping, ingests each round's
   measurements via ``ingest_telemetry``, and invalidates warm option
   tables only for instances whose served surface actually moved beyond
   the predictor's tolerance.
 * ``EcoShiftHierController`` allocates through the topology-aware
   two-level capped-frontier DP (DESIGN.md §12), collapsing behaviour
   classes within each leaf power domain and splitting the cluster budget
   across domains subject to every local cap — with the same warm
   content-keyed caches, plus per-domain frontier memoization.
 * heuristic controllers (uniform / DPS / MixedAdaptive) are stateless
   wrappers, registered for a uniform interface.

Controllers register themselves into ``policies.CONTROLLERS`` so the
registry lives beside ``POLICIES`` (``policies.get_controller``).
Controller-only policies (``ecoshift_online``) have no pure-function
counterpart in ``POLICIES`` — the online phase is inherently stateful.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Mapping, Sequence

import numpy as np

from repro.core import curves, mckp
from repro.core import policies as policies_mod
from repro.core.curves import OptionTable
from repro.core.spans import Span
from repro.core.surfaces import PowerSurface
from repro.core.types import (
    Allocation,
    AppSpec,
    FusedRoundStats,
    ReceiverBatch,
    SystemSpec,
    as_receiver_order,
)


class Controller:
    """Base: a policy with per-round ``allocate`` plus warm-state hooks."""

    #: key into ``POLICIES`` / the legacy ``run_round`` name
    policy: str = ""
    #: True for policies that always see ground-truth surfaces (Oracle)
    sees_truth: bool = False
    #: True when the controller consumes a columnar ``ReceiverBatch`` via
    #: ``allocate_grouped`` (group-collapsed DP controllers)
    supports_grouped: bool = False

    def __init__(self, system: SystemSpec):
        self.system = system

    def allocate(
        self,
        receivers: Sequence[AppSpec],
        baselines: Mapping[str, tuple[float, float]],
        budget: float,
        surfaces: Mapping[str, PowerSurface],
    ) -> Allocation:
        raise NotImplementedError

    # -- warm-state hooks ----------------------------------------------------

    def invalidate(self, names: Sequence[str] | None = None) -> None:
        """Drop cached per-receiver state (``None`` = everything)."""

    def ingest_telemetry(self, records: Sequence) -> None:
        """Consume one round's noisy measurements
        (:class:`repro.cluster.predictor.TelemetryRecord`).  The engine
        calls this after every measured round; predictor-backed
        controllers refresh their surfaces here, everyone else ignores
        it."""

    def reset(self) -> None:
        self.invalidate()

    # -- fault-tolerance hooks (DESIGN.md §18) -------------------------------

    def notify_actuation(self, report) -> None:
        """Engine hook after a faulted round's actuation settles
        (:class:`repro.cluster.faults.ActuationReport`).  DP controllers
        pin NACKed receivers at their last-confirmed caps with bounded
        retry backoff; the base class ignores it."""

    def snapshot(self) -> dict:
        """Serializable warm-state checkpoint (plain python/numpy values).

        The contract (certified by tests/test_faults.py): a controller
        that is ``crash_reset()`` then ``restore(snapshot)``-ed produces
        **bit-for-bit** the allocations of the uninterrupted run.  Warm
        caches are *not* serialized — every incremental/fused path is
        already certified bit-for-bit equal to its from-scratch solve, so
        only state that changes *results* (pins, online-learned predictor
        state) needs to survive; caches and resident banks rebuild cold.
        """
        return {"policy": self.policy}

    def restore(self, state: Mapping) -> None:
        """Adopt a :meth:`snapshot` (see there for the bit-for-bit
        contract).  Drops any warm caches accumulated since — restore is
        self-contained and valid on a warm controller."""
        if state.get("policy") != self.policy:
            raise ValueError(
                f"snapshot of policy {state.get('policy')!r} cannot restore "
                f"a {self.policy!r} controller"
            )

    def crash_reset(self) -> None:
        """Simulate a controller process crash: all warm state is gone.
        (Restore from a snapshot afterwards for checkpointed failover.)"""
        self.reset()


class _StatelessController(Controller):
    """Wraps a pure policy function; nothing carries across rounds."""

    def allocate(self, receivers, baselines, budget, surfaces):
        fn = policies_mod.POLICIES[self.policy]
        return fn(receivers, baselines, budget, self.system, surfaces)


@policies_mod.register_controller("uniform")
class UniformController(_StatelessController):
    policy = "uniform"


@policies_mod.register_controller("dps")
class DPSController(_StatelessController):
    policy = "dps"


@policies_mod.register_controller("mixed_adaptive")
class MixedAdaptiveController(_StatelessController):
    policy = "mixed_adaptive"


@dataclasses.dataclass
class ControllerConfig:
    """One construction config for every EcoShift-family controller.

    The grouping/fusion/predictor knobs grew organically across
    ``EcoShiftController`` / ``EcoShiftHierController`` /
    ``EcoShiftOnlineController`` / ``OracleController``; this dataclass
    folds them into a single object so callers (and
    ``policies.get_controller``) construct any controller as
    ``Ctrl(system, config=ControllerConfig(...))``.  Every historical
    keyword form keeps working as an alias: an explicit keyword passed to
    a controller's ``__init__`` overrides the corresponding config field
    (``merged``), and the defaults here are exactly the historical
    per-controller defaults.

    The receding-horizon fields (DESIGN.md §15): ``horizon`` is how many
    rounds of budget forecast the controller plans over (1 = myopic —
    planning entirely disabled, bit-for-bit today's path); ``eco_factor``
    is the fraction of the myopic controller's weighted (CO2/dollar)
    spend the planner may use (>= 1.0 never restricts, also bit-for-bit);
    ``plan_levels`` / ``plan_grid`` bound the horizon DP's per-round
    candidate count and allowance lattice.
    """

    grouped: bool = True
    incremental: bool = True
    fused: bool = False
    #: optional repro.core.allocator.EcoShiftAllocator (warm NCF handle)
    allocator: object | None = None
    #: optional repro.cluster.predictor.OnlinePredictor (required by the
    #: online controller; optional surface source for the hier controller)
    predictor: object | None = None
    #: optional repro.core.topology.PowerTopology (hier controller)
    topology: object | None = None
    #: Oracle brute-force toggle (None = auto, <= 10 receivers)
    exhaustive: bool | None = None
    #: receding-horizon plan length in rounds (1 = myopic)
    horizon: int = 1
    #: fraction of the myopic weighted spend the planner may use
    eco_factor: float = 1.0
    #: max frontier candidates per horizon step
    plan_levels: int = 64
    #: allowance-lattice cells of the horizon DP
    plan_grid: int = 2048
    #: LRU bounds of the warm caches (None = the class defaults, e.g.
    #: ``_OptionCachingController.MAX_GROUP_TABLES``).  Long-running
    #: serving deployments tune memory here; any bound >= 1 is
    #: bit-for-bit safe — caches are pure accelerators (evictions
    #: re-compute, never change results; tests/test_faults.py certifies
    #: a bound of 1 end-to-end)
    max_group_tables: int | None = None
    max_agg_curves: int | None = None
    max_picks: int | None = None
    max_plans: int | None = None
    max_allocations: int | None = None
    max_frontiers: int | None = None

    def merged(self, **overrides) -> "ControllerConfig":
        """Copy with every non-None override applied — the legacy-kwarg
        alias path (an explicit keyword beats the config field)."""
        changes = {k: v for k, v in overrides.items() if v is not None}
        return dataclasses.replace(self, **changes) if changes else self


def _served_replace(batch: ReceiverBatch, served) -> ReceiverBatch:
    """Swap in predictor-served surfaces and strip the delta sequence.

    Served surfaces move on telemetry, outside the engine's delta bound,
    so the batch must not claim delta continuity (seq=0 routes grouping
    down the from-scratch path).  The one helper both online paths share.
    """
    return dataclasses.replace(
        batch, surfaces=served, seq=0, prev_seq=None, delta=None, removed=()
    )


class _ClassRec:
    """One live behaviour class inside a :class:`_GroupingState` scope."""

    __slots__ = ("surf", "members", "table", "group")

    def __init__(self, surf, table):
        self.surf = surf
        #: name-sorted member list, maintained incrementally
        self.members: list[str] = []
        self.table = table
        #: lazily rebuilt frozen GroupedOptions (None = members moved)
        self.group = None


class _GroupingState:
    """Persistent behaviour-class grouping, updated by batch deltas.

    Mirrors ``mckp.collapse_receivers`` — receivers sharing (surface
    identity, baseline) form one class — but *across rounds*: the engine's
    :class:`~repro.core.types.ReceiverBatch` delta contract names exactly
    the positions whose surface/baseline moved and the receivers that
    left, so a steady-state round updates O(churn) classes instead of
    re-collapsing the whole cluster.  ``scope`` partitions classes (leaf
    power-domain id on the hierarchical path, 0 on the flat path).
    Unchanged scopes keep their frozen ``GroupedOptions`` tuples — object
    identity downstream caches (plans, leaf solutions) key on.
    """

    __slots__ = ("seq", "scopes", "of_name", "_groups_cache")

    def __init__(self):
        #: batch seq this state mirrors (None = never built)
        self.seq: int | None = None
        self.scopes: dict[int, dict[tuple, _ClassRec]] = {}
        self.of_name: dict[str, tuple[int, tuple]] = {}
        self._groups_cache: dict[int, tuple] = {}

    def reset(self) -> None:
        self.seq = None
        self.scopes.clear()
        self.of_name.clear()
        self._groups_cache.clear()

    def sync(self, batch, leaf_ids, table_for) -> None:
        """Bring the grouping in line with ``batch`` (delta or rebuild)."""
        if batch.seq == self.seq and self.seq is not None:
            return
        if (
            batch.prev_seq is not None
            and batch.prev_seq == self.seq
            and batch.delta is not None
        ):
            for name in batch.removed:
                self._remove(name)
            for pos in batch.delta:
                self._place(batch, pos, leaf_ids, table_for)
            self.seq = batch.seq
            return
        self._rebuild(batch, leaf_ids, table_for)
        self.seq = batch.seq

    def _rebuild(self, batch, leaf_ids, table_for) -> None:
        self.scopes.clear()
        self.of_name.clear()
        self._groups_cache.clear()
        scopes = (
            leaf_ids.tolist() if leaf_ids is not None else [0] * len(batch)
        )
        bl = batch.baselines.tolist()
        for name, surf, base, scope in zip(
            batch.names, batch.surfaces, bl, scopes
        ):
            base = (base[0], base[1])
            ckey = (id(surf), base)
            recs = self.scopes.setdefault(scope, {})
            rec = recs.get(ckey)
            if rec is None or rec.surf is not surf:
                rec = _ClassRec(surf, table_for(surf, base))
                recs[ckey] = rec
            rec.members.append(name)
            self.of_name[name] = (scope, ckey)
        for recs in self.scopes.values():
            for rec in recs.values():
                rec.members.sort()

    def _place(self, batch, pos, leaf_ids, table_for) -> None:
        name = batch.names[pos]
        surf = batch.surfaces[pos]
        b = batch.baselines[pos]
        base = (float(b[0]), float(b[1]))
        scope = int(leaf_ids[pos]) if leaf_ids is not None else 0
        ckey = (id(surf), base)
        old = self.of_name.get(name)
        if old is not None:
            oscope, ockey = old
            if oscope == scope and ockey == ckey:
                rec = self.scopes[scope][ckey]
                if rec.surf is surf:
                    return  # nothing actually moved
            self._remove(name)
        recs = self.scopes.setdefault(scope, {})
        rec = recs.get(ckey)
        if rec is None or rec.surf is not surf:
            rec = _ClassRec(surf, table_for(surf, base))
            recs[ckey] = rec
        bisect.insort(rec.members, name)
        rec.group = None
        self.of_name[name] = (scope, ckey)
        self._groups_cache.pop(scope, None)

    def _remove(self, name: str) -> None:
        loc = self.of_name.pop(name, None)
        if loc is None:
            return
        scope, ckey = loc
        rec = self.scopes[scope][ckey]
        i = bisect.bisect_left(rec.members, name)
        if i < len(rec.members) and rec.members[i] == name:
            del rec.members[i]
        rec.group = None
        if not rec.members:
            del self.scopes[scope][ckey]
        self._groups_cache.pop(scope, None)

    def groups(self, scope: int) -> tuple:
        """Frozen GroupedOptions of one scope (tuple reused while clean)."""
        g = self._groups_cache.get(scope)
        if g is None:
            out = []
            for rec in self.scopes.get(scope, {}).values():
                if rec.group is None:
                    rec.group = mckp.GroupedOptions(
                        table=rec.table, members=tuple(rec.members)
                    )
                out.append(rec.group)
            g = tuple(out)
            self._groups_cache[scope] = g
        return g

    def by_scope(self) -> dict[int, tuple]:
        return {scope: self.groups(scope) for scope in self.scopes}


class _OptionCachingController(Controller):
    """Shared warm ``OptionTable`` caches for the DP-based policies.

    Two cache layers:

     * per-instance tables keyed by name (the legacy ungrouped path);
     * **group tables** keyed by (surface identity, baseline) — one table
       per behaviour class, shared by every member, feeding the
       group-collapsed solvers.  Keys are value+identity based, so event
       invalidation is implicit: a straggler/phase-change swaps the
       surface object and the stale entry simply stops matching (stale
       keys are pruned opportunistically).

    Both layers build budget-independent tables (grid headroom ceiling;
    all MCKP solvers skip over-budget options), so after a node failure
    only the *pool* changes and re-optimization reuses every surviving
    table — the incremental re-solve the paper's fault-tolerance study
    needs.
    """

    #: LRU bounds of the warm caches (DESIGN.md §13: warm state must stay
    #: capped over long scenarios with drifting budgets/digests)
    MAX_GROUP_TABLES = 512
    MAX_AGG_CURVES = 8192
    MAX_PICKS = 16384
    MAX_PLANS = 256
    MAX_ALLOCATIONS = 8

    #: NACK retry policy (DESIGN.md §18): after this many consecutive
    #: NACKs the controller stops re-commanding a receiver (pin holds
    #: until an operator ``invalidate``/event touches it) ...
    NACK_MAX_RETRIES = 4
    #: ... and the exponential retry backoff is capped at this many rounds
    NACK_MAX_BACKOFF = 8

    def __init__(self, system: SystemSpec):
        super().__init__(system)
        #: name -> (baseline, surface, table); surface compared by identity
        self._options: dict[
            str, tuple[tuple[float, float], PowerSurface, OptionTable]
        ] = {}
        #: (id(surface), baseline) -> (surface, table)
        self._group_tables: mckp.LRUCache = mckp.LRUCache(self.MAX_GROUP_TABLES)
        #: (table digest, multiplicity, budget) -> aggregate sparse curve
        self._agg_curves: mckp.LRUCache = mckp.LRUCache(self.MAX_AGG_CURVES)
        #: (digest, budget) -> doubling chain (shielded from (d, m) churn)
        self._chain_cache: mckp.LRUCache = mckp.LRUCache(512)
        #: (curve key, spend) -> unwound pick multiset
        self._pick_cache: mckp.LRUCache = mckp.LRUCache(self.MAX_PICKS)
        #: group-token tuple -> merged-class plan
        self._plan_cache: mckp.LRUCache = mckp.LRUCache(self.MAX_PLANS)
        #: (group tokens, budget[, headroom]) -> warm Allocation
        self._alloc_cache: mckp.LRUCache = mckp.LRUCache(self.MAX_ALLOCATIONS)
        #: delta-maintained behaviour-class grouping (DESIGN.md §13)
        self._grouping = _GroupingState()
        #: NACK pin book: name -> {"caps": (c, g) last-confirmed applied,
        #: "fails": consecutive NACKs, "until": round the backoff expires}
        self._pins: dict[str, dict] = {}
        #: round of the latest actuation report (pins apply to the *next*
        #: round's solve)
        self._pin_round: int = -1

    def invalidate(self, names: Sequence[str] | None = None) -> None:
        if names is None:
            self._options.clear()
            self._group_tables.clear()
            self._agg_curves.clear()
            self._chain_cache.clear()
            self._pick_cache.clear()
            self._plan_cache.clear()
            self._alloc_cache.clear()
            self._grouping.reset()
            self._pins.clear()
            self._pin_round = -1
        else:
            for n in names:
                self._options.pop(n, None)
                # an event touching a pinned node (failure, phase change)
                # supersedes the pin — the next solve re-commands it
                self._pins.pop(n, None)

    def _apply_cache_bounds(self, cfg: ControllerConfig) -> None:
        """Resize the warm caches per the config's LRU-bound overrides.
        In place (``LRUCache.resize``) because downstream state — e.g.
        ``mckp.HierState`` — holds references to the same cache objects."""
        for cache, bound in (
            (self._group_tables, cfg.max_group_tables),
            (self._agg_curves, cfg.max_agg_curves),
            (self._pick_cache, cfg.max_picks),
            (self._plan_cache, cfg.max_plans),
            (self._alloc_cache, cfg.max_allocations),
        ):
            if bound is not None:
                cache.resize(bound)

    # -- NACK pinning (DESIGN.md §18) ----------------------------------------

    def notify_actuation(self, report) -> None:
        """Pin NACKed receivers at their last-confirmed applied caps with
        exponential retry backoff: the first NACK retries next round, the
        k-th after ``min(2^(k-1), NACK_MAX_BACKOFF)`` rounds, and after
        ``NACK_MAX_RETRIES`` consecutive NACKs the controller stops
        re-commanding the receiver entirely (the pin holds until an event
        or ``invalidate`` touches the node).  While pinned, a receiver's
        commanded caps equal its applied caps, so the actuation layer acks
        it trivially — an ack clears the pin only once the backoff window
        has expired (``report.round >= until``), which is exactly the
        retry firing and succeeding."""
        r = int(report.round)
        self._pin_round = r
        for nm in report.nacked:
            p = self._pins.get(nm)
            fails = (p["fails"] if p is not None else 0) + 1
            if fails >= self.NACK_MAX_RETRIES:
                until = r + 10**9  # stop retrying: effectively forever
            else:
                until = r + min(2 ** (fails - 1), self.NACK_MAX_BACKOFF)
            applied = report.applied.get(nm)
            caps = (
                (float(applied[0]), float(applied[1]))
                if applied is not None
                else p["caps"]
            )
            self._pins[nm] = {"caps": caps, "fails": fails, "until": until}
        for nm in report.acked:
            p = self._pins.get(nm)
            if p is not None and r >= p["until"]:
                del self._pins[nm]

    def _active_pins(self) -> dict[str, tuple[float, float]]:
        """Pins that constrain the *next* round's solve."""
        if not self._pins:
            return {}
        nxt = self._pin_round + 1
        return {
            nm: p["caps"]
            for nm, p in self._pins.items()
            if nxt <= p["until"]
        }

    def _solve_pinned(
        self,
        batch: ReceiverBatch,
        budget: float,
        pins: Mapping[str, tuple[float, float]],
        domain_extra=None,
    ) -> Allocation:
        """Pinned-class solve: NACKed receivers hold their last-confirmed
        caps; everyone else solves over the *remaining* budget/headroom.

        The pinned extra is fitted to the current constraints first —
        proportionally derated to each domain's headroom
        (``PowerTopology.derate_factors``) and to the total budget — so
        the merged allocation always validates: a stuck actuator's
        *physical* overdraw is PowerGuard's to claw back, but the
        *commanded* allocation never plans a violation.  The free
        receivers re-solve through the ordinary grouped/hierarchical path
        on a standalone (seq=0) sub-batch, so headroom a pin doesn't use
        is redistributed rather than stranded, and the delta grouping
        state skips these rounds cleanly (it resyncs from the next
        engine-sequenced batch)."""
        names = batch.names
        pinned_idx = [i for i, nm in enumerate(names) if nm in pins]
        free_idx = [i for i, nm in enumerate(names) if nm not in pins]
        base = np.asarray(batch.baselines, dtype=np.float64)
        pbase = base[pinned_idx]
        pcaps = np.array(
            [pins[names[i]] for i in pinned_idx], dtype=np.float64
        ).reshape(len(pinned_idx), 2)
        # a pin never takes a receiver below its baseline allotment
        pcaps = np.maximum(pcaps, pbase)
        pextra = pcaps.sum(axis=1) - pbase.sum(axis=1)
        topo = getattr(self, "topology", None)
        dom = (
            np.asarray(batch.domain_ids)[pinned_idx]
            if batch.domain_ids is not None and len(pinned_idx)
            else None
        )
        scale = np.ones(len(pinned_idx))
        if domain_extra is not None and dom is not None and len(pinned_idx):
            leaf = np.zeros(len(topo), dtype=np.float64)
            leaf += np.bincount(dom, weights=pextra, minlength=len(topo))
            spend = topo.aggregate_leaves(leaf)
            scale = topo.derate_factors(
                spend, np.asarray(domain_extra, dtype=np.float64)
            )[dom]
        tot = float((pextra * scale).sum())
        if tot > budget + 1e-12 and tot > 0:
            scale = scale * (float(budget) / tot)
            tot = float((pextra * scale).sum())
        pcaps = pbase + scale[:, None] * (pcaps - pbase)
        pextra = pextra * scale

        free_budget = max(0.0, float(budget) - tot)
        free_extra = None
        if domain_extra is not None:
            free_extra = np.asarray(domain_extra, dtype=np.float64).copy()
            if dom is not None and len(pinned_idx):
                leaf = np.zeros(len(topo), dtype=np.float64)
                leaf += np.bincount(dom, weights=pextra, minlength=len(topo))
                free_extra = np.clip(
                    free_extra - topo.aggregate_leaves(leaf), 0.0, None
                )
        free = None
        if free_idx:
            sub = ReceiverBatch(
                names=[names[i] for i in free_idx],
                surface_ids=[batch.surface_ids[i] for i in free_idx],
                baselines=base[free_idx],
                surfaces=[batch.surfaces[i] for i in free_idx],
                domain_ids=(
                    np.asarray(batch.domain_ids)[free_idx]
                    if batch.domain_ids is not None
                    else None
                ),
                seq=0,
            )
            if domain_extra is not None:
                free = self.allocate_hierarchical(
                    sub, free_budget, free_extra, _skip_pins=True
                )
            else:
                free = self.allocate_grouped(sub, free_budget, _skip_pins=True)
        caps = dict(free.caps) if free is not None else {}
        for k, i in enumerate(pinned_idx):
            caps[names[i]] = (float(pcaps[k, 0]), float(pcaps[k, 1]))
        pinned_spent = float(pextra.sum())
        if domain_extra is not None:
            ds = dict(getattr(self, "last_domain_spent", None) or {})
            if dom is not None and len(pinned_idx):
                leaf = np.zeros(len(topo), dtype=np.float64)
                leaf += np.bincount(dom, weights=pextra, minlength=len(topo))
                for dn, w in zip(topo.names, topo.aggregate_leaves(leaf)):
                    if w:
                        ds[dn] = ds.get(dn, 0.0) + float(w)
            self.last_domain_spent = ds
        self.last_solver = "pinned"
        return Allocation(
            caps=caps,
            spent=(free.spent if free is not None else 0.0) + pinned_spent,
            predicted_improvement=(
                free.predicted_improvement if free is not None else 0.0
            ),
        )

    # -- snapshot / restore (DESIGN.md §18) ----------------------------------

    def snapshot(self) -> dict:
        snap = super().snapshot()
        snap["pins"] = {
            nm: {
                "caps": [float(p["caps"][0]), float(p["caps"][1])],
                "fails": int(p["fails"]),
                "until": int(p["until"]),
            }
            for nm, p in self._pins.items()
        }
        snap["pin_round"] = int(self._pin_round)
        return snap

    def restore(self, state: Mapping) -> None:
        super().restore(state)
        self.invalidate(None)  # restore is self-contained on a warm ctrl
        self._pins = {
            nm: {
                "caps": (float(p["caps"][0]), float(p["caps"][1])),
                "fails": int(p["fails"]),
                "until": int(p["until"]),
            }
            for nm, p in state.get("pins", {}).items()
        }
        self._pin_round = int(state.get("pin_round", -1))

    @property
    def cached_tables(self) -> int:
        return len(self._options) + len(self._group_tables)

    def _options_for(
        self,
        receivers: Sequence[AppSpec],
        baselines: Mapping[str, tuple[float, float]],
        surfaces: Mapping[str, PowerSurface],
    ) -> list[OptionTable]:
        out = []
        for a in as_receiver_order(receivers):
            base = baselines[a.name]
            surf = surfaces[a.name]
            hit = self._options.get(a.name)
            if hit is not None and hit[0] == base and hit[1] is surf:
                out.append(hit[2])
                continue
            # budget-independent: enumerate to the grid headroom ceiling;
            # every solver skips options costing more than the round budget
            table = curves.build_options(
                a.name, surf, base, self.system.grid, np.inf
            )
            self._options[a.name] = (base, surf, table)
            out.append(table)
        return out

    def _group_table(
        self, surf: PowerSurface, base: tuple[float, float]
    ) -> OptionTable:
        key = (id(surf), base)
        hit = self._group_tables.get(key)
        if hit is not None and hit[0] is surf:
            return hit[1]
        table = curves.build_options("class", surf, base, self.system.grid, np.inf)
        self._group_tables[key] = (surf, table)
        return table

    def _grouped_options_for(
        self, batch: ReceiverBatch
    ) -> list[mckp.GroupedOptions]:
        """Collapse a receiver batch into behaviour-class groups.

        Group key is (surface identity, baseline): all members share one
        warm option table, built once per class instead of once per node.
        (Stale identity-keyed table entries age out of the LRU caches.)
        """
        return mckp.collapse_receivers(
            batch.names, batch.surfaces, batch.baselines, self._group_table
        )


@policies_mod.register_controller("ecoshift")
class EcoShiftController(_OptionCachingController):
    """MCKP DP on (predicted) surfaces with warm option tables.

    Optionally holds the NCF predictor handle (``allocator``) so predicted
    surfaces for arriving instances resolve without re-wiring callers.
    """

    policy = "ecoshift"

    def __init__(
        self,
        system: SystemSpec,
        *,
        config: ControllerConfig | None = None,
        allocator=None,
        grouped: bool | None = None,
        incremental: bool | None = None,
        fused: bool | None = None,
        horizon: int | None = None,
        eco_factor: float | None = None,
        plan_levels: int | None = None,
        plan_grid: int | None = None,
    ):
        super().__init__(system)
        cfg = (config if config is not None else ControllerConfig()).merged(
            allocator=allocator, grouped=grouped, incremental=incremental,
            fused=fused, horizon=horizon, eco_factor=eco_factor,
            plan_levels=plan_levels, plan_grid=plan_grid,
        )
        #: the resolved construction config (ControllerConfig)
        self.config = cfg
        #: optional repro.core.allocator.EcoShiftAllocator (warm NCF handle)
        self.allocator = cfg.allocator
        #: group-collapsed allocation (one DP super-stage per behaviour
        #: class); False forces the legacy per-instance path
        self.grouped = cfg.grouped
        #: delta-driven steady-state rounds (DESIGN.md §13): consume batch
        #: deltas into persistent grouping state, reuse cached solutions;
        #: False re-collapses and re-solves from scratch every round (the
        #: PR-4-style baseline the incremental_alloc bench compares against)
        self.incremental = cfg.incremental
        #: device-resident fused rounds (DESIGN.md §14/§17): keep option
        #: banks resident on device and run the whole warm-round decision
        #: pipeline as one jitted Pallas program.  Structure churn stays
        #: fused — rows patch or compact in place under the capacity-slack
        #: layout; only off-lattice keys / oversized grids / empty or
        #: infeasible rounds route to the host sparse path.  Applies on the
        #: incremental path only (``incremental`` and engine-sequenced
        #: batches); other rounds solve on the host.
        self.fused = cfg.fused
        #: resident device banks + capacity-slack layout for fused rounds
        self._fused_state = mckp.FusedState()
        #: 'fused' | 'host' | 'cache' | 'pinned' — which path produced the
        #: last solution
        self.last_solver: str | None = None
        #: why the last fused attempt routed to host ("" when it stayed
        #: fused, wasn't attempted, or hit the alloc cache) — mirrors
        #: ``FusedRoundStats.fallback_reason``
        self.last_fallback_reason: str = ""
        #: receding-horizon planning (DESIGN.md §15): plan length, weighted
        #: spend fraction, and DP bounds — planning is active only when
        #: horizon > 1 AND eco_factor < 1 AND the engine fed an outlook
        self.horizon = int(cfg.horizon)
        self.eco_factor = float(cfg.eco_factor)
        self.plan_levels = int(cfg.plan_levels)
        self.plan_grid = int(cfg.plan_grid)
        #: (caps, weights) forecast fed by the engine, consumed per round
        self._outlook: tuple | None = None
        #: (group tokens, cutoff) -> planning frontier arrays (flat path)
        self._frontier_lru = mckp.LRUCache(32)
        #: budget the planner committed for the last round (None = the
        #: plan did not restrict the round — myopic path taken verbatim)
        self.last_planned_budget: float | None = None
        #: full per-round spend plan behind last_planned_budget
        self.last_plan: tuple | None = None
        self._apply_cache_bounds(cfg)

    def invalidate(self, names: Sequence[str] | None = None) -> None:
        super().invalidate(names)
        if names is None:
            self._fused_state.clear()
            self._frontier_lru.clear()

    def snapshot(self) -> dict:
        # fused banks / HierState / frontiers are rebuilt cold after a
        # restore (bit-for-bit certified vs warm); only the predictor's
        # online-learned state changes allocations and must serialize
        snap = super().snapshot()
        pred = getattr(self, "predictor", None)
        if pred is not None:
            snap["predictor"] = pred.state_dict()
        return snap

    def restore(self, state: Mapping) -> None:
        super().restore(state)
        pred = getattr(self, "predictor", None)
        if pred is not None and "predictor" in state:
            pred.load_state_dict(state["predictor"])

    def crash_reset(self) -> None:
        super().crash_reset()
        pred = getattr(self, "predictor", None)
        if pred is not None:
            pred.wipe()

    # -- receding-horizon planning (DESIGN.md §15) ---------------------------

    def set_budget_outlook(self, caps, weights=None) -> None:
        """Engine hook: the provider-backed budget forecast for the next
        ``len(caps)`` rounds (``caps[0]`` = this round's budget) plus the
        optional CO2/price weight signal.  Consumed by the next allocate
        call; refreshed by the engine every round."""
        self._outlook = (
            tuple(float(c) for c in caps),
            None if weights is None else tuple(float(w) for w in weights),
        )

    def _plan_pending(self) -> bool:
        return (
            self.horizon > 1
            and self.eco_factor < 1.0
            and self._outlook is not None
        )

    def _plan_budget(self, budget: float, frontier_fn) -> float:
        """Run the horizon DP over this round's frontier; returns the
        budget to commit for round 0 (== ``budget`` whenever the plan
        would not restrict it — the caller then proceeds on the literally
        unchanged myopic path)."""
        self.last_planned_budget = None
        self.last_plan = None
        outlook, self._outlook = self._outlook, None
        caps, weights = outlook
        caps = caps[: self.horizon]
        if weights is not None:
            weights = weights[: self.horizon]
        # one frontier serves every horizon cap: states <= any cap are
        # identical whether the DP ran under that cap or under the larger
        # quantized cutoff (the _curve_cutoff invariance argument), so the
        # planning frontier is keyed budget-drift-invariantly
        cutoff = mckp._curve_cutoff(max(max(caps), float(budget)))
        keys, vals = frontier_fn(cutoff)
        plan = mckp.plan_horizon(
            keys, vals, caps, weights,
            eco_factor=self.eco_factor,
            levels=self.plan_levels,
            grid=self.plan_grid,
        )
        if plan is None:
            return budget
        b_eff = min(float(budget), float(plan[0]))
        if b_eff >= budget - 1e-9:
            return budget
        self.last_planned_budget = b_eff
        self.last_plan = tuple(plan)
        return b_eff

    def _planning_frontier(self, groups, cutoff: float):
        """Warm flat-path planning frontier (grouped super-stage DP end
        states), LRU-keyed by (group identity tokens, cutoff)."""
        key = (
            tuple(sorted(mckp._group_token(g) for g in groups)),
            mckp._qkey(cutoff),
        )
        hit = self._frontier_lru.get(key)
        if hit is None:
            hit = mckp.grouped_frontier(
                groups,
                cutoff,
                curve_cache=self._agg_curves,
                plan_cache=self._plan_cache,
                chain_cache=self._chain_cache,
            )
            self._frontier_lru[key] = hit
        return hit

    def fused_stats(self) -> FusedRoundStats:
        """Snapshot of the device-resident round counters."""
        return FusedRoundStats(**self._fused_state.stats)

    def fused_segments(self) -> dict:
        """Last fused round's split (seconds): prep_s / patch_s /
        compact_s / dispatch_s / backtrack_s / assembly_s, the durations
        of its ``fused.*`` spans (``dispatch_s`` = launch + wait) — the
        attribution table behind ``tools/profile_round.py --churn``.
        Empty until a fused round has been attempted."""
        return dict(self._fused_state.last_segments)

    @property
    def supports_grouped(self) -> bool:  # type: ignore[override]
        return self.grouped

    def allocate(self, receivers, baselines, budget, surfaces):
        options = self._options_for(receivers, baselines, surfaces)
        sol = mckp.solve_sparse(options, budget)
        return policies_mod.allocation_from_solution(
            sol,
            *policies_mod.receiver_rows(receivers, baselines),
            budget,
            self.system.grid,
        )

    def _incremental_groups(self, batch: ReceiverBatch, leaf_ids=None):
        """Sync the persistent grouping with a batch (delta or rebuild)."""
        self._grouping.sync(batch, leaf_ids, self._group_table)

    def allocate_grouped(
        self, batch: ReceiverBatch, budget: float, _skip_pins: bool = False
    ) -> Allocation:
        """Group-collapsed round: receivers sharing (surface identity,
        baseline) solve as one multiplicity-m DP super-stage — parity with
        :meth:`allocate` is certified by tests/test_grouped_alloc.py.

        On the incremental path (default, engine-sequenced batches) the
        behaviour-class grouping is delta-maintained across rounds, the
        solve reuses content-keyed curve/pick/plan caches, and
        a round whose classes and budget are unchanged returns the cached
        Allocation outright — bit-for-bit what a from-scratch solve
        produces (tests/test_incremental_alloc.py)."""
        if not _skip_pins and self._pins:
            pins = self._active_pins()
            present = set(batch.names)
            pins = {nm: c for nm, c in pins.items() if nm in present}
            if pins:
                return self._solve_pinned(batch, budget, pins)
        incremental = self.incremental and getattr(batch, "seq", 0) != 0
        with Span("controller.grouping_sync"):
            if incremental:
                self._incremental_groups(batch)
                groups = self._grouping.groups(0)
            else:
                groups = self._grouped_options_for(batch)
        if self._plan_pending():
            with Span("controller.plan_budget"):
                budget = self._plan_budget(
                    budget, lambda cap: self._planning_frontier(groups, cap)
                )
        key = None
        if incremental:
            with Span("controller.cache_key"):
                key = (
                    tuple(sorted(mckp._group_token(g) for g in groups)),
                    mckp._qkey(budget),
                )
                hit = self._alloc_cache.get(key)
            if hit is not None:
                self.last_solver = "cache"
                self.last_fallback_reason = ""
                return hit
        sol = None
        self.last_fallback_reason = ""
        if incremental and self.fused:
            with Span("controller.fused_specs"):
                sol = mckp.solve_grouped_fused(
                    groups,
                    budget,
                    fstate=self._fused_state,
                    curve_cache=self._agg_curves,
                    pick_cache=self._pick_cache,
                    plan_cache=self._plan_cache,
                    chain_cache=self._chain_cache,
                )
            if sol is None:
                self.last_fallback_reason = self._fused_state.stats.get(
                    "fallback_reason", ""
                )
        self.last_solver = "fused" if sol is not None else "host"
        if sol is None:
            with Span("controller.host_solve"):
                sol = mckp.solve_sparse_grouped(
                    groups,
                    budget,
                    curve_cache=self._agg_curves,
                    pick_cache=self._pick_cache if incremental else None,
                    plan_cache=self._plan_cache if incremental else None,
                    chain_cache=self._chain_cache if incremental else None,
                )
        with Span("controller.allocation"):
            alloc = policies_mod.allocation_from_solution(
                sol, batch.names, batch.baselines, budget, self.system.grid
            )
            if key is not None:
                self._alloc_cache[key] = alloc
        return alloc


@policies_mod.register_controller("ecoshift_hier")
class EcoShiftHierController(EcoShiftController):
    """Topology-aware EcoShift: two-level capped-frontier MCKP (DESIGN.md §12).

    The engine hands this controller a columnar receiver batch *with leaf
    domain ids* plus the round's per-domain extra-power headroom; receivers
    collapse into behaviour classes **within each leaf domain** (same warm
    identity-keyed group tables as the flat path), each leaf's class DP
    becomes a capped value-vs-spend frontier, and the upper-level DP splits
    the cluster budget across domains (``mckp.solve_hierarchical``).

    Warm state: the shared aggregate-curve cache plus a **frontier cache**
    keyed by (per-class digest+multiplicity layout, quantized budget) —
    both content-keyed, so telemetry-driven surface swaps invalidate
    implicitly (a swapped surface digests differently and the stale entry
    stops matching).  Passing ``predictor`` sources every receiver surface
    from a telemetry-driven :class:`~repro.cluster.predictor
    .OnlinePredictor` exactly like ``ecoshift_online``.
    """

    policy = "ecoshift_hier"
    supports_hierarchical = True

    #: LRU bound of the leaf-frontier cache (satellite of DESIGN.md §13)
    MAX_FRONTIERS = 512

    def __init__(
        self,
        system: SystemSpec,
        *,
        config: ControllerConfig | None = None,
        topology=None,
        predictor=None,
        allocator=None,
        incremental: bool | None = None,
        fused: bool | None = None,
        horizon: int | None = None,
        eco_factor: float | None = None,
        plan_levels: int | None = None,
        plan_grid: int | None = None,
    ):
        cfg = (config if config is not None else ControllerConfig()).merged(
            topology=topology, predictor=predictor, allocator=allocator,
            incremental=incremental, fused=fused,
            horizon=horizon, eco_factor=eco_factor, plan_levels=plan_levels,
            plan_grid=plan_grid,
        )
        super().__init__(system, config=cfg)
        #: repro.core.topology.PowerTopology (bound here or by the engine)
        self.topology = cfg.topology
        #: optional OnlinePredictor: serve predicted surfaces + ingest telemetry
        self.predictor = cfg.predictor
        #: (class layout, quantized budget) -> leaf frontier DP arrays
        self._frontiers: mckp.LRUCache = mckp.LRUCache(self.MAX_FRONTIERS)
        #: persistent hierarchical warm state: frontier aggregation tree
        #: combines, pick multisets, leaf solutions, merged-class plans —
        #: all content-keyed and LRU-bounded (mckp.HierState)
        if cfg.max_frontiers is not None:
            self._frontiers.resize(cfg.max_frontiers)
        self._hier_state = mckp.HierState(
            curve_cache=self._agg_curves,
            frontier_cache=self._frontiers,
            chain_cache=self._chain_cache,
            pick_cache=self._pick_cache,
            plan_cache=self._plan_cache,
            max_leaf_solutions=128,
        )
        #: per-domain watts spent by the latest hierarchical solve
        self.last_domain_spent: dict[str, float] | None = None

    @property
    def serves_own_surfaces(self) -> bool:
        return self.predictor is not None

    def bind_topology(self, topology) -> None:
        """Attach (or swap) the domain tree; a swap drops warm state."""
        if self.topology is not None and self.topology is not topology:
            self.invalidate()
        self.topology = topology

    def _served_batch(self, batch: ReceiverBatch) -> ReceiverBatch:
        if self.predictor is None:
            return batch
        served = [
            self.predictor.surface_for(name, sid)
            for name, sid in zip(batch.names, batch.surface_ids)
        ]
        return _served_replace(batch, served)

    _NO_TOPOLOGY = (
        "ecoshift_hier allocates per power domain — attach a PowerTopology "
        "to the sim/scenario, or use 'ecoshift' for flat allocation"
    )

    def allocate(self, receivers, baselines, budget, surfaces):
        # reached only when the engine has no topology attached: a silent
        # flat fallback under the hier name would be a footgun
        raise ValueError(self._NO_TOPOLOGY)

    def allocate_grouped(self, batch: ReceiverBatch, budget: float):
        raise ValueError(self._NO_TOPOLOGY)

    def invalidate(self, names: Sequence[str] | None = None) -> None:
        super().invalidate(names)
        if names is None:
            self._frontiers.clear()
            self._hier_state.clear()

    def _grouped_options_by_leaf(
        self, batch: ReceiverBatch
    ) -> dict[int, list[mckp.GroupedOptions]]:
        """Per-leaf-domain behaviour-class collapse over the warm tables."""
        by_leaf: dict[int, list[mckp.GroupedOptions]] = {}
        leaf_ids = np.asarray(batch.domain_ids)
        for leaf in np.unique(leaf_ids):
            ii = np.flatnonzero(leaf_ids == leaf)
            by_leaf[int(leaf)] = mckp.collapse_receivers(
                [batch.names[i] for i in ii],
                [batch.surfaces[i] for i in ii],
                batch.baselines[ii],
                self._group_table,
            )
        return by_leaf

    def allocate_hierarchical(
        self,
        batch: ReceiverBatch,
        budget: float,
        domain_extra: np.ndarray,
        _skip_pins: bool = False,
    ) -> Allocation:
        """One topology-aware round: per-domain capped frontiers + the
        upper-level budget-split DP through the frontier aggregation tree.
        ``domain_extra`` is the per-domain extra-power headroom (preorder
        ids, caps net of committed draw).

        Incremental path (default): the per-leaf grouping is
        delta-maintained from the batch, unchanged leaves reuse their
        frontier DPs / assembled solutions, dirty leaves re-aggregate
        through O(log n_leaves) tree combines, and a round whose classes,
        budget and headroom are all unchanged returns the cached
        Allocation — always bit-for-bit the from-scratch solve."""
        if self.topology is None:
            raise ValueError("ecoshift_hier needs a bound PowerTopology")
        if batch.domain_ids is None:
            raise ValueError("receiver batch carries no domain ids")
        with Span("controller.serve_batch"):
            batch = self._served_batch(batch)
        if not _skip_pins and self._pins:
            pins = self._active_pins()
            present = set(batch.names)
            pins = {nm: c for nm, c in pins.items() if nm in present}
            if pins:
                self.last_domain_spent = {}
                return self._solve_pinned(
                    batch, budget, pins, domain_extra=domain_extra
                )
        incremental = self.incremental and getattr(batch, "seq", 0) != 0
        state = None
        key = None
        with Span("controller.grouping_sync"):
            if incremental:
                self._incremental_groups(
                    batch, leaf_ids=np.asarray(batch.domain_ids)
                )
                by_leaf = self._grouping.by_scope()
                state = self._hier_state
            else:
                by_leaf = self._grouped_options_by_leaf(batch)
        root = None
        if self._plan_pending():
            # the root frontier under the quantized cutoff serves every
            # horizon cap; the primed leaf frontiers and tree combines are
            # the same warm HierState entries the solve below reuses
            with Span("controller.plan_budget"):
                root = policies_mod.domain_tree(self.topology, domain_extra, by_leaf)
                budget = self._plan_budget(
                    budget,
                    lambda cap: mckp.hierarchical_frontier(
                        root, cap, state=self._hier_state
                    ),
                )
        if incremental:
            with Span("controller.cache_key"):
                key = (
                    tuple(
                        (leaf, tuple(sorted(mckp._group_token(g) for g in groups)))
                        for leaf, groups in sorted(by_leaf.items())
                    ),
                    mckp._qkey(budget),
                    np.asarray(domain_extra).tobytes(),
                )
                hit = self._alloc_cache.get(key)
            if hit is not None:
                self.last_domain_spent = hit[1]
                self.last_solver = "cache"
                self.last_fallback_reason = ""
                return hit[0]
        if root is None:
            with Span("controller.domain_tree"):
                root = policies_mod.domain_tree(self.topology, domain_extra, by_leaf)
        sol = None
        self.last_fallback_reason = ""
        if incremental and self.fused:
            fstate = self._fused_state
            with Span("controller.fused_specs"):
                sol = mckp.solve_hierarchical_fused(
                    root, budget, state=self._hier_state, fstate=fstate
                )
            if sol is None:
                self.last_fallback_reason = fstate.stats.get(
                    "fallback_reason", ""
                )
        self.last_solver = "fused" if sol is not None else "host"
        if sol is None:
            with Span("controller.host_solve"):
                sol = mckp.solve_hierarchical(
                    root,
                    budget,
                    curve_cache=self._agg_curves,
                    frontier_cache=self._frontiers,
                    state=state,
                )
        self.last_domain_spent = sol.domain_spent
        with Span("controller.allocation"):
            alloc = policies_mod.allocation_from_solution(
                sol, batch.names, batch.baselines, budget, self.system.grid
            )
            if key is not None:
                self._alloc_cache[key] = (alloc, sol.domain_spent)
        return alloc

    def ingest_telemetry(self, records) -> None:
        if self.predictor is not None:
            self.predictor.observe(records)
            self.predictor.refresh()


@policies_mod.register_controller("ecoshift_online", pure=False)
class EcoShiftOnlineController(EcoShiftController):
    """EcoShift with a telemetry-driven online predictor as surface source.

    Ignores the ``surfaces`` mapping the engine passes to ``allocate`` —
    every receiver's surface comes from the attached
    :class:`~repro.cluster.predictor.OnlinePredictor` (population prior
    for cold-start apps).  After each measured round the engine feeds the
    telemetry back via :meth:`ingest_telemetry` and the predictor
    refreshes the apps whose telemetry warrants it.  Cache invalidation
    is implicit: the warm option cache is keyed by surface *identity*
    (``_OptionCachingController._options_for``), and the predictor swaps
    a surface object only on tolerance-exceeding moves — so re-solves
    stay warm exactly while predictions are stable, with no extra
    bookkeeping here.
    """

    policy = "ecoshift_online"
    #: the engine skips filling ReceiverBatch.surfaces: every surface
    #: comes from the predictor, and ground truth must not transit here
    serves_own_surfaces = True

    def __init__(
        self,
        system: SystemSpec,
        *,
        predictor=None,
        config: ControllerConfig | None = None,
    ):
        cfg = (config if config is not None else ControllerConfig()).merged(
            predictor=predictor
        )
        if cfg.predictor is None:
            raise ValueError("ecoshift_online needs a predictor")
        super().__init__(system, config=cfg)
        #: repro.cluster.predictor.OnlinePredictor (required)
        self.predictor = cfg.predictor

    def allocate(self, receivers, baselines, budget, surfaces=None):
        seen = {
            a.name: self.predictor.surface_for(a.name, a.surface_id)
            for a in receivers
        }
        return super().allocate(receivers, baselines, budget, seen)

    def allocate_grouped(
        self, batch: ReceiverBatch, budget: float, _skip_pins: bool = False
    ):
        served = [
            self.predictor.surface_for(name, sid)
            for name, sid in zip(batch.names, batch.surface_ids)
        ]
        return super().allocate_grouped(
            _served_replace(batch, served), budget, _skip_pins=_skip_pins
        )

    def ingest_telemetry(self, records) -> None:
        self.predictor.observe(records)
        self.predictor.refresh()


@policies_mod.register_controller("oracle")
class OracleController(_OptionCachingController):
    """Exhaustive/DP optimum on true surfaces (``sees_truth``)."""

    policy = "oracle"
    sees_truth = True
    supports_grouped = True

    def __init__(
        self,
        system: SystemSpec,
        *,
        exhaustive: bool | None = None,
        config: ControllerConfig | None = None,
    ):
        super().__init__(system)
        cfg = (config if config is not None else ControllerConfig()).merged(
            exhaustive=exhaustive
        )
        self.config = cfg
        #: None = auto (brute force iff <= 10 receivers, like run_round)
        self.exhaustive = cfg.exhaustive
        self._apply_cache_bounds(cfg)

    def allocate(self, receivers, baselines, budget, surfaces):
        options = self._options_for(receivers, baselines, surfaces)
        exhaustive = (
            len(receivers) <= 10 if self.exhaustive is None else self.exhaustive
        )
        sol = (
            mckp.brute_force(options, budget)
            if exhaustive
            else mckp.solve_sparse(options, budget)
        )
        return policies_mod.allocation_from_solution(
            sol,
            *policies_mod.receiver_rows(receivers, baselines),
            budget,
            self.system.grid,
        )

    def allocate_grouped(
        self, batch: ReceiverBatch, budget: float, _skip_pins: bool = False
    ) -> Allocation:
        if not _skip_pins and self._pins:
            pins = self._active_pins()
            present = set(batch.names)
            pins = {nm: c for nm, c in pins.items() if nm in present}
            if pins:
                return self._solve_pinned(batch, budget, pins)
        groups = self._grouped_options_for(batch)
        exhaustive = (
            len(batch) <= 10 if self.exhaustive is None else self.exhaustive
        )
        sol = (
            mckp.brute_force(mckp.expand_groups(groups), budget)
            if exhaustive
            else mckp.solve_sparse_grouped(
                groups, budget, curve_cache=self._agg_curves
            )
        )
        return policies_mod.allocation_from_solution(
            sol, batch.names, batch.baselines, budget, self.system.grid
        )


def make_controller(policy: str, system: SystemSpec, **kwargs) -> Controller:
    """Instantiate a registered controller by policy name."""
    return policies_mod.get_controller(policy, system, **kwargs)


# ---------------------------------------------------------------------------
# Snapshot persistence (DESIGN.md §18)
# ---------------------------------------------------------------------------


def _pack(obj):
    """Encode a snapshot tree for msgpack: ndarrays as tagged
    dtype/shape/bytes, tuples and non-str-keyed dicts as tagged lists
    (msgpack has neither).  Inverse of :func:`_unpack`; numpy float64 and
    msgpack doubles round-trip exactly, so file round-trips keep the
    bit-for-bit restore contract."""
    if isinstance(obj, np.ndarray):
        return {
            "__nd__": True,
            "dtype": str(obj.dtype),
            "shape": list(obj.shape),
            "data": obj.tobytes(),
        }
    if isinstance(obj, (np.floating, np.integer, np.bool_)):
        return obj.item()
    if isinstance(obj, tuple):
        return {"__tup__": [_pack(v) for v in obj]}
    if isinstance(obj, list):
        return [_pack(v) for v in obj]
    if isinstance(obj, dict):
        if all(isinstance(k, str) for k in obj):
            return {k: _pack(v) for k, v in obj.items()}
        return {"__map__": [[_pack(k), _pack(v)] for k, v in obj.items()]}
    return obj


def _unpack(obj):
    if isinstance(obj, dict):
        if obj.get("__nd__"):
            return (
                np.frombuffer(obj["data"], dtype=obj["dtype"])
                .reshape(obj["shape"])
                .copy()
            )
        if "__tup__" in obj:
            return tuple(_unpack(v) for v in obj["__tup__"])
        if "__map__" in obj:
            return {_unpack(k): _unpack(v) for k, v in obj["__map__"]}
        return {k: _unpack(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_unpack(v) for v in obj]
    return obj


def save_snapshot(path: str, snap: Mapping) -> None:
    """Persist a ``Controller.snapshot()`` crash-safely.

    Same atomic-write discipline as ``repro.train.checkpoint``: write to a
    sibling temp file, flush + fsync, then ``os.replace`` — a crash
    mid-write leaves the previous snapshot intact, never a torn file."""
    import os

    import msgpack

    blob = msgpack.packb(_pack(dict(snap)), use_bin_type=True)
    tmp = f"{path}.tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def load_snapshot(path: str) -> dict:
    """Read a snapshot written by :func:`save_snapshot` (feed the result
    to ``Controller.restore``)."""
    import msgpack

    with open(path, "rb") as f:
        return _unpack(msgpack.unpackb(f.read(), raw=False))
