"""Time-stepped multi-round cluster simulation engine (paper §5.4, temporal).

``ClusterSim`` owns the cluster state and steps a :class:`Scenario` against
a stateful :class:`~repro.cluster.controller.Controller`:

 1. apply this round's events (failures, stragglers, arrivals, phase
    changes) and invalidate the controller's per-receiver warm state;
 2. partition donors/receivers, derive (or read) the reclaimed budget;
 3. controller allocates; the engine measures true improvements.

State is **columnar** (DESIGN.md §11): a :class:`NodeTable` keeps caps,
liveness, slowdowns and interned surface/app ids as struct-of-arrays, so
partitioning, event application and measurement are numpy passes instead of
per-node Python.  ``NodeState`` dataclass views are materialized on demand
(``sim.nodes``) for compatibility — assigning a node list re-ingests it.

Measurement is *vectorized*: the engine evaluates each distinct
(surface, slowdown) class once over all of its receivers' cap vectors and
draws the whole ``[n, n_repeats, 2]`` noise block in one call.  The RNG
stream is *identical* to the sequential loop (numpy ``Generator`` array
fills consume the bit stream in element order), so improvements match the
legacy path bit-for-bit — certified by tests/test_cluster.py.
``measure_improvements_loop`` keeps the legacy per-node loop as the
equivalence/benchmark reference.

Every vectorized measurement is emitted as **array-native telemetry**
(:class:`repro.cluster.predictor.TelemetryBatch` — the same mean measured
runtimes and improvements, bit-for-bit, with lazy
:class:`~repro.cluster.predictor.TelemetryRecord` views): ``run_round``
stashes the round's batch in ``last_telemetry`` and ``run`` hands it to the
controller's ``ingest_telemetry`` hook after each round, closing the online
prediction loop (DESIGN.md §10).

Controllers exposing ``supports_grouped`` (the DP policies) receive a
:class:`~repro.core.types.ReceiverBatch` instead of per-instance AppSpec
lists, enabling group-collapsed allocation: one option table and one DP
super-stage per behaviour class (DESIGN.md §11).

A :class:`~repro.core.topology.PowerTopology` attaches a **hierarchical
power-domain tree** (DESIGN.md §12): the table interns each node's owning
leaf domain, the engine accounts per-domain committed draw (receiver
baselines + donor natural draw) each round, hierarchy-aware controllers
(``supports_hierarchical``) allocate through per-domain capped frontiers,
and a sim-side conservation check asserts no domain ever draws above its
cap — including mid-scenario ``DomainCapChange`` deratings.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import zlib
from typing import Callable, Mapping, Sequence

import numpy as np

from repro.cluster import budget as budget_mod
from repro.cluster import scenario as scenario_mod
from repro.cluster.predictor import TelemetryBatch
from repro.cluster.scenario import Scenario
from repro.core.spans import Span
from repro.core.surfaces import PowerSurface, measured_runtime
from repro.core.types import (
    Allocation,
    AppSpec,
    EmulationResult,
    ReceiverBatch,
    SystemSpec,
)

#: per-round offset into the measurement RNG stream (round 0 == the legacy
#: single-round stream, so migrated paths reproduce run_round exactly)
_ROUND_STRIDE = 1000003

#: process-global batch sequence: seq values are unique across *all* sims,
#: so a controller reused by two sims can never mistake one sim's batch
#: chain for the other's (the delta contract keys on seq continuity)
_BATCH_SEQ = itertools.count(1)


@dataclasses.dataclass(frozen=True)
class NodeState:
    node_id: int
    app: AppSpec  # instance (name is unique per node)
    base_app: str  # underlying app name (surface / predictor identity)
    caps: tuple[float, float]
    alive: bool = True
    slowdown: float = 1.0  # straggler factor on the true surface


@dataclasses.dataclass(frozen=True)
class _SlowedSurface(PowerSurface):
    base: PowerSurface
    slowdown: float

    def runtime(self, c, g):
        return self.base.runtime(c, g) * self.slowdown

    def power_draw(self, c, g):
        return self.base.power_draw(c, g)

    def improvement(self, base, c, g):
        # relative improvement is *exactly* invariant under a constant
        # slowdown: delegate so a straggler's option table digests
        # bit-identical to its healthy peers' (the class-merge invariant
        # the grouped solvers rely on; computing (s*t0 - s*t1)/(s*t0)
        # instead would drift in the last float bit and split the class)
        return self.base.improvement(base, c, g)


# ---------------------------------------------------------------------------
# Columnar node state
# ---------------------------------------------------------------------------


class _Interner:
    """Append-only string -> small-int table shared by a NodeTable."""

    __slots__ = ("strings", "_ids")

    def __init__(self):
        self.strings: list[str] = []
        self._ids: dict[str, int] = {}

    def intern(self, s: str) -> int:
        i = self._ids.get(s)
        if i is None:
            i = len(self.strings)
            self.strings.append(s)
            self._ids[s] = i
        return i

    def __getitem__(self, i: int) -> str:
        return self.strings[i]


#: dirty-row log horizon: consumers lagging more than this many bumps
#: behind fall back to a full rebuild
_DIRTY_HORIZON = 64


@functools.cache
def _device_patch_fn():
    """Donated row scatter shared by every device-view column: the donation
    reuses the resident buffer so a steady-state refresh uploads only the
    dirty rows."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def patch(col, rows, vals):
        return col.at[rows].set(vals)

    return patch


class DeviceView:
    """Device-resident mirror of the hot :class:`NodeTable` columns.

    The fused steady-state round (DESIGN.md §14/§17) keeps its decision
    pipeline on device; this view gives the engine the matching residency
    for the numeric cluster state: ``caps``/``alive``/``slowdown``/
    ``domain_id`` live as jax device arrays (the float columns in
    ``ops.device_value_dtype()``: float64 on the CPU, float32 on a TPU,
    whose kernels take no float64), and
    :meth:`refresh` syncs them against the table's dirty-row log — one
    donated row scatter per changed column in steady state.  Growth is
    O(growth), not O(cluster): the resident prefix is reused as-is on
    device and only the appended tail uploads (``extends`` counts these
    repacks, mirroring the fused banks' compaction story).  A full
    re-upload happens only on an unprovable delta or when more than half
    the table moved.  Counters (``uploads_full`` / ``uploads_rows`` /
    ``extends``) expose the churn boundary to profiling tools.
    """

    _COLS = ("caps", "alive", "slowdown", "domain_id")

    def __init__(self, table: "NodeTable"):
        self._table = table
        self.version = -1
        self._n = -1
        self.uploads_full = 0
        self.uploads_rows = 0
        self.extends = 0
        self.caps = None
        self.alive = None
        self.slowdown = None
        self.domain_id = None

    def _col(self, name: str, rows=slice(None)):
        """Host column slice in its device dtype."""
        import jax.numpy as jnp

        from repro.kernels import ops

        col = getattr(self._table, name)[rows]
        if col.dtype.kind == "f":
            return jnp.asarray(col, dtype=ops.device_value_dtype())
        return jnp.asarray(col)

    def refresh(self) -> "DeviceView":
        import jax.numpy as jnp

        from repro.kernels import ops

        t = self._table
        if t.version == self.version and self._n == len(t):
            return self
        dirty = t.dirty_since(self.version) if self.version >= 0 else None
        with ops.device_value_scope():
            # patching more than half the table costs more dispatches than
            # one bulk upload
            if dirty is None or len(dirty) > max(1, len(t) // 2):
                for c in self._COLS:
                    setattr(self, c, self._col(c))
                self.uploads_full += 1
            else:
                if len(t) > self._n:
                    # device-side extend (rows are append-only): keep the
                    # resident prefix, upload only the appended tail
                    for c in self._COLS:
                        tail = self._col(c, slice(self._n, None))
                        setattr(
                            self, c,
                            jnp.concatenate([getattr(self, c), tail]),
                        )
                    self.extends += 1
                    self.uploads_rows += len(t) - self._n
                    dirty = dirty[dirty < self._n]
                if len(dirty):
                    rows = jnp.asarray(dirty, dtype=jnp.int32)
                    patch = _device_patch_fn()
                    for c in self._COLS:
                        vals = self._col(c, dirty)
                        setattr(self, c, patch(getattr(self, c), rows, vals))
                    self.uploads_rows += int(len(dirty))
        self.version = t.version
        self._n = len(t)
        return self


class NodeTable:
    """Struct-of-arrays cluster node state.

    Columns: ``caps [n,2]``, ``alive [n]``, ``slowdown [n]``,
    ``node_ids [n]`` plus interned-id columns ``base_gid`` (true-surface /
    base-app name), ``sid_gid`` (the instance AppSpec's surface id),
    ``name_gid`` (instance name) and ``sclass_gid``, all indexing the shared
    :class:`_Interner`.  Rows are append-only (failures flip ``alive``).

    **Delta tracking** (DESIGN.md §13): every mutation bumps ``version``
    and logs the *dirty rows* it touched.  Consumers remember the version
    they last materialized against and ask :meth:`dirty_since` for exactly
    the rows that moved — natural-draw caching, partitioning, receiver
    batches and the per-domain draw accounting all update O(churn) state
    instead of rebuilding whole-cluster arrays each round.  A coarse
    ``bump()`` (no rows) marks everything dirty, so legacy callers stay
    correct by falling back to full rebuilds.
    """

    def __init__(self):
        self.interner = _Interner()
        self.node_ids = np.empty(0, dtype=np.int64)
        self.caps = np.empty((0, 2), dtype=np.float64)
        self.alive = np.empty(0, dtype=bool)
        self.slowdown = np.empty(0, dtype=np.float64)
        self.base_gid = np.empty(0, dtype=np.int32)
        self.sid_gid = np.empty(0, dtype=np.int32)
        self.name_gid = np.empty(0, dtype=np.int32)
        self.sclass_gid = np.empty(0, dtype=np.int32)
        #: owning leaf power-domain id (PowerTopology preorder; -1 = none)
        self.domain_id = np.empty(0, dtype=np.int32)
        self.names: list[str] = []
        self.version = 0
        self._row_of: dict[int, int] | None = None
        #: (version, dirty row array | None-for-everything) ring
        self._dirty_log: list[tuple[int, np.ndarray | None]] = []
        self._device_view: DeviceView | None = None

    def __len__(self) -> int:
        return len(self.node_ids)

    @property
    def strings(self) -> list[str]:
        return self.interner.strings

    def bump(self, rows: Sequence[int] | np.ndarray | None = None) -> None:
        """Advance ``version``; ``rows`` are the row indices this mutation
        touched (``None`` marks the whole table dirty)."""
        self.version += 1
        if rows is not None:
            rows = np.asarray(rows, dtype=np.int64)
        self._dirty_log.append((self.version, rows))
        if len(self._dirty_log) > _DIRTY_HORIZON:
            del self._dirty_log[: len(self._dirty_log) - _DIRTY_HORIZON]

    def dirty_since(self, version: int) -> np.ndarray | None:
        """Rows dirtied in ``(version, self.version]``, or None when the
        log can't prove a bound (horizon exceeded, unbounded bump, or a
        ``version`` this table never issued)."""
        if version == self.version:
            return np.empty(0, dtype=np.int64)
        if version > self.version:
            return None
        log = self._dirty_log
        if not log or log[0][0] > version + 1:
            return None
        parts = []
        for v, rows in log:
            if v <= version:
                continue
            if rows is None:
                return None
            parts.append(rows)
        if not parts:
            return None
        return np.unique(np.concatenate(parts))

    def device_view(self) -> DeviceView:
        """Refreshed device-resident mirror of the hot numeric columns
        (lazily created; O(churn) donated row patches in steady state)."""
        if self._device_view is None:
            self._device_view = DeviceView(self)
        return self._device_view.refresh()

    @staticmethod
    def from_nodes(nodes: Sequence[NodeState]) -> "NodeTable":
        t = NodeTable()
        if not nodes:
            return t
        t.node_ids = np.array([n.node_id for n in nodes], dtype=np.int64)
        t.caps = np.array([n.caps for n in nodes], dtype=np.float64)
        t.alive = np.array([n.alive for n in nodes], dtype=bool)
        t.slowdown = np.array([n.slowdown for n in nodes], dtype=np.float64)
        t.names = [n.app.name for n in nodes]
        t.base_gid = np.array(
            [t.interner.intern(n.base_app) for n in nodes], dtype=np.int32
        )
        t.sid_gid = np.array(
            [t.interner.intern(n.app.surface_id) for n in nodes], dtype=np.int32
        )
        t.name_gid = np.array(
            [t.interner.intern(n.app.name) for n in nodes], dtype=np.int32
        )
        t.sclass_gid = np.array(
            [t.interner.intern(n.app.sclass) for n in nodes], dtype=np.int32
        )
        t.domain_id = np.full(len(nodes), -1, dtype=np.int32)
        return t

    def append(
        self,
        *,
        node_id: int,
        name: str,
        base_app: str,
        surface_id: str,
        sclass: str,
        caps: tuple[float, float],
        domain_id: int = -1,
    ) -> None:
        self.node_ids = np.append(self.node_ids, np.int64(node_id))
        self.caps = np.concatenate(
            [self.caps, np.asarray([caps], dtype=np.float64)]
        )
        self.alive = np.append(self.alive, True)
        self.slowdown = np.append(self.slowdown, 1.0)
        self.names.append(name)
        self.base_gid = np.append(
            self.base_gid, np.int32(self.interner.intern(base_app))
        )
        self.sid_gid = np.append(
            self.sid_gid, np.int32(self.interner.intern(surface_id))
        )
        self.name_gid = np.append(
            self.name_gid, np.int32(self.interner.intern(name))
        )
        self.sclass_gid = np.append(
            self.sclass_gid, np.int32(self.interner.intern(sclass))
        )
        self.domain_id = np.append(self.domain_id, np.int32(domain_id))
        if self._row_of is not None:
            self._row_of[int(node_id)] = len(self.node_ids) - 1

    def next_node_id(self) -> int:
        return 1 + int(self.node_ids.max()) if len(self) else 0

    def rows_for_ids(self, ids: Sequence[int]) -> np.ndarray:
        if self._row_of is None:
            self._row_of = {
                int(nid): r for r, nid in enumerate(self.node_ids)
            }
        return np.array([self._row_of[int(i)] for i in ids], dtype=np.int64)

    def view(self, row: int) -> NodeState:
        s = self.interner.strings
        return NodeState(
            node_id=int(self.node_ids[row]),
            app=AppSpec(
                name=self.names[row],
                sclass=s[self.sclass_gid[row]],
                surface_id=s[self.sid_gid[row]],
            ),
            base_app=s[self.base_gid[row]],
            caps=(float(self.caps[row, 0]), float(self.caps[row, 1])),
            alive=bool(self.alive[row]),
            slowdown=float(self.slowdown[row]),
        )

    def views(self, rows: Sequence[int] | None = None) -> list[NodeState]:
        if rows is None:
            rows = range(len(self))
        return [self.view(r) for r in rows]


def build_nodes(
    system: SystemSpec,
    apps: Sequence[AppSpec],
    *,
    n_nodes: int,
    seed: int,
    initial_caps: tuple[float, float] | None = None,
) -> list[NodeState]:
    """Place ``n_nodes`` instances by cycling a shuffled app list."""
    rng = np.random.default_rng(seed)
    order = list(apps)
    rng.shuffle(order)
    caps = initial_caps or (system.init_cpu, system.init_gpu)
    nodes = []
    for i in range(n_nodes):
        a = order[i % len(order)]
        inst = AppSpec(
            name=f"{a.name}#n{i}", sclass=a.sclass, surface_id=a.surface_id
        )
        nodes.append(NodeState(node_id=i, app=inst, base_app=a.name, caps=caps))
    return nodes


# ---------------------------------------------------------------------------
# Round records
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoundRecord:
    """Everything observed in one simulated round."""

    round: int
    result: EmulationResult
    pool: float  # donor-derived reclaimed pool this round
    n_alive: int
    events: tuple = ()
    power_price: float | None = None
    #: grid CO2 intensity this round (scenario carbon signal), if any
    carbon_intensity: float | None = None
    #: per-receiver noisy measurements: a TelemetryBatch on the vectorized
    #: path (iterable of TelemetryRecord views), () on the legacy loop path
    telemetry: object = ()
    #: per-domain draw / cap watts this round (topology sims only)
    domain_draw: dict | None = None
    domain_caps: dict | None = None
    #: PowerGuard columns (fault-injected runs, DESIGN.md §18): worst
    #: pre-derate cap excursion in watts, total watts the emergency derate
    #: clawed back, and the domains that excursed this round
    overdraw_w: float = 0.0
    derate_w: float = 0.0
    excursion_domains: tuple = ()
    #: receivers whose applied caps deviated from the command (NACK /
    #: partial / delayed actuation, or a PowerGuard derate)
    nacked: tuple = ()
    #: telemetry fault kinds applied to this round's batch
    telemetry_faults: tuple = ()

    @property
    def avg_improvement(self) -> float:
        return self.result.avg_improvement


@dataclasses.dataclass
class SimResult:
    """Trace of a whole scenario under one controller."""

    policy: str
    records: list[RoundRecord]

    @property
    def n_rounds(self) -> int:
        return len(self.records)

    @property
    def improvement_trace(self) -> np.ndarray:
        return np.array([r.avg_improvement for r in self.records])

    def improvements_of(self, name: str) -> np.ndarray:
        """Per-round improvement of one instance (NaN when not a receiver)."""
        return np.array(
            [r.result.improvements.get(name, np.nan) for r in self.records]
        )


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class ClusterSim:
    """Columnar multi-round cluster engine.

    Constructed either from a ``nodes`` list (ingested into a
    :class:`NodeTable`) or from an existing ``table``.  ``sim.nodes`` stays
    a readable/assignable list of :class:`NodeState` views for
    compatibility with the pre-columnar engine.
    """

    def __init__(
        self,
        system: SystemSpec,
        nodes: Sequence[NodeState] | None = None,
        surfaces: Mapping[str, PowerSurface] | None = None,
        n_repeats: int = 5,
        seed: int = 0,
        *,
        table: NodeTable | None = None,
        topology=None,
    ):
        self.system = system
        #: true surfaces keyed by *base* app name
        self.surfaces: Mapping[str, PowerSurface] = surfaces or {}
        self.n_repeats = n_repeats
        self.seed = seed
        self.table = (
            table if table is not None else NodeTable.from_nodes(nodes or [])
        )
        #: memoized straggler views: stable object identity per (app, slowdown)
        #: so controllers' identity-keyed option caches stay warm across rounds
        self._slowed: dict = {}
        #: natural-draw cache per base-app gid (identity-checked)
        self._naturals: dict[int, tuple[PowerSurface, float, float]] = {}
        #: whole-cluster natural-draw array, keyed by table version (the
        #: partition and the per-domain accounting both read it each round);
        #: delta-patched via the table's dirty-row log
        self._nat_cache: tuple[int, np.ndarray, np.ndarray] | None = None
        #: memoized partition per (version, nat identity): stable row-array
        #: objects double as identity tokens for downstream caches
        self._part_cache: tuple | None = None
        #: cached deterministic baseline runtimes (version, rows, t_base,
        #: per-(gid, slowdown) surface identities)
        self._tbase_cache: tuple | None = None
        #: memoized (base surface, slowdown) grouping per (version, rows)
        self._measure_groups_cache: tuple | None = None
        #: receiver-batch cache: (mode, version, rows, batch)
        self._batch_cache: tuple | None = None
        #: (alloc, names list, [n,2] caps array) of the latest round — the
        #: conservation check and measurement share one gather, and a
        #: cache-hit allocation skips it entirely
        self._alloc_caps_cache: tuple | None = None
        #: per-phase seconds of the latest run_round (``partition_s`` ..
        #: ``measure_s``, the durations of its engine spans) and the
        #: solver that served it (tools/profile_round)
        self.last_round_profile: dict[str, float | str] = {}
        #: telemetry emitted by the latest vectorized-measurement round
        self.last_telemetry: object = ()
        self._views_cache: tuple[int, list[NodeState]] | None = None
        #: hierarchical power-domain tree (repro.core.topology.PowerTopology)
        self.topology = None
        #: DomainCapChange routing: per-domain (round, cap) steps resolved
        #: through the provider-backed budget subsystem — a step applies
        #: from its round on, with the same float coercion as scenario
        #: budgets (repro.cluster.budget.OverrideBook)
        self._cap_overrides = budget_mod.OverrideBook()
        #: per-domain draw/cap observed by the latest topology round
        self.last_domain_draw: dict[str, float] | None = None
        self.last_domain_caps: dict[str, float] | None = None
        #: actuator registers (fault-injected runs): name -> (c, g) caps
        #: physically applied last round (absent = at table baseline), and
        #: name -> command queued by a one-round delayed application
        self._applied_caps: dict[str, tuple[float, float]] = {}
        self._pending_cmds: dict[str, tuple[float, float]] = {}
        #: ActuationReport / PowerGuard stats of the latest faulted round
        self.last_actuation: object | None = None
        self.last_guard: dict | None = None
        if topology is not None:
            self.attach_topology(topology)

    @staticmethod
    def build(
        system: SystemSpec,
        apps: Sequence[AppSpec],
        surfaces: Mapping[str, PowerSurface],
        *,
        n_nodes: int = 100,
        seed: int = 0,
        initial_caps: tuple[float, float] | None = None,
        topology=None,
    ) -> "ClusterSim":
        nodes = build_nodes(
            system, apps, n_nodes=n_nodes, seed=seed, initial_caps=initial_caps
        )
        return ClusterSim(
            system=system,
            nodes=nodes,
            surfaces=surfaces,
            seed=seed,
            topology=topology,
        )

    # -- power-domain topology ------------------------------------------------

    def attach_topology(self, topology) -> None:
        """Adopt a power-domain tree: intern every node's owning leaf.

        Raises if any current node id sits outside every leaf range —
        the engine-side counterpart of the scenario's build-time check.
        Interning happens before any state changes, so a failed attach
        leaves the sim exactly as it was.
        """
        t = self.table
        domain_id = (
            topology.leaf_of(t.node_ids).astype(np.int32) if len(t) else None
        )
        self.topology = topology
        self._cap_overrides = budget_mod.OverrideBook()
        if domain_id is not None:
            t.domain_id = domain_id
            t.bump()

    def _committed_draw(
        self, recv_rows: np.ndarray | None = None
    ) -> np.ndarray:
        """[n] per-node committed watts: a receiver pins its baseline cap
        allotment, a donor its natural draw, a dead node nothing.

        ``recv_rows`` forces those rows to receiver accounting — when a
        caller overrides ``run_round(receivers=...)``, a node the slack
        heuristic would call a donor still gets grown from its baseline,
        so it must commit its caps, not its natural draw.
        """
        t = self.table
        nat, donor = self._donor_mask()
        committed = np.where(donor, nat.sum(axis=1), t.caps.sum(axis=1))
        if recv_rows is not None and len(recv_rows):
            committed[recv_rows] = t.caps[recv_rows].sum(axis=1)
        committed[~t.alive] = 0.0
        return committed

    def domain_headroom(
        self,
        round_index: int = 0,
        recv_rows: np.ndarray | None = None,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Per-domain ``(extra, committed, caps)`` at ``round_index``.

        ``caps`` resolves each domain's cap trace with persisted
        ``DomainCapChange`` overrides applied; ``committed`` aggregates the
        per-node committed draw up the tree (``recv_rows`` as in
        :meth:`_committed_draw`); ``extra`` is the headroom the
        hierarchical allocator may spend inside each domain (>= 0).
        """
        topo = self.topology
        caps = topo.cap_at(round_index, self._cap_overrides.active(round_index))
        leaf = np.zeros(len(topo), dtype=np.float64)
        t = self.table
        if len(t):
            owned = t.domain_id >= 0
            leaf += np.bincount(
                t.domain_id[owned],
                weights=self._committed_draw(recv_rows)[owned],
                minlength=len(topo),
            )
        committed = topo.aggregate_leaves(leaf)
        extra = np.clip(caps - committed, 0.0, None)
        return extra, committed, caps

    # -- node state ----------------------------------------------------------

    @property
    def nodes(self) -> list[NodeState]:
        """NodeState views of the columnar table (fresh list each access).

        Views are snapshots: mutate cluster state by *assigning* a node
        list (``sim.nodes = [...]``) or via :meth:`apply_events` — editing
        the returned list in place has no effect on the table.
        """
        cache = self._views_cache
        if cache is None or cache[0] != self.table.version:
            cache = (self.table.version, self.table.views())
            self._views_cache = cache
        return list(cache[1])

    @nodes.setter
    def nodes(self, value: Sequence[NodeState]) -> None:
        table = NodeTable.from_nodes(value)
        if self.topology is not None and len(table):
            # intern before swapping state in: a failed leaf_of leaves the
            # sim's previous table intact
            table.domain_id = self.topology.leaf_of(table.node_ids).astype(
                np.int32
            )
        self.table = table
        self._views_cache = None
        self._naturals.clear()
        self._nat_cache = None
        self._part_cache = None
        self._batch_cache = None
        self._tbase_cache = None
        self._measure_groups_cache = None

    def _surface(self, node: NodeState) -> PowerSurface:
        return self._surface_of(node.base_app, node.slowdown)

    def _surface_of(self, base_app: str, slowdown: float) -> PowerSurface:
        s = self.surfaces[base_app]
        if slowdown == 1.0:
            return s
        key = (base_app, slowdown)
        hit = self._slowed.get(key)
        if hit is None or hit.base is not s:
            hit = _SlowedSurface(s, slowdown)
            self._slowed[key] = hit
        return hit

    def alive_nodes(self) -> list[NodeState]:
        return [n for n in self.nodes if n.alive]

    def _nat_of_gid(self, gid: int) -> tuple[float, float]:
        """Cached natural draw of one base-app gid (identity-validated)."""
        t = self.table
        surf = self.surfaces[t.strings[gid]]
        hit = self._naturals.get(gid)
        if hit is None or hit[0] is not surf:
            c, g = surf.power_draw(1e9, 1e9)
            hit = (surf, float(c), float(g))
            self._naturals[gid] = hit
        return hit[1:]

    def _nat_gids_fresh(self, gids: np.ndarray) -> bool:
        t = self.table
        for gid in gids:
            hit = self._naturals.get(int(gid))
            if hit is None or hit[0] is not self.surfaces[t.strings[gid]]:
                return False
        return True

    def _natural_draws(self) -> np.ndarray:
        """[n, 2] natural (uncapped) component draws, one surface query per
        distinct base app (draws are cap- and slowdown-independent).

        The assembled array is cached per table version (validated against
        per-gid surface identity, so online surface swaps still refresh).
        When the table's dirty-row log bounds what moved since the cached
        version, only the dirty rows are refilled — the steady-state round
        never rebuilds the whole-cluster array (DESIGN.md §13).
        """
        t = self.table
        cache = self._nat_cache
        if cache is not None and cache[0] == t.version:
            if self._nat_gids_fresh(cache[2]):
                return cache[1]
            cache = None
        if cache is not None:
            dirty = t.dirty_since(cache[0])
            if dirty is not None and self._nat_gids_fresh(cache[2]):
                nat = cache[1]
                if len(nat) < len(t):
                    nat = np.concatenate(
                        [nat, np.empty((len(t) - len(nat), 2), np.float64)]
                    )
                gids = cache[2]
                if len(dirty):
                    d_gids = t.base_gid[dirty]
                    for gid in np.unique(d_gids):
                        nat[dirty[d_gids == gid]] = self._nat_of_gid(int(gid))
                    gids = np.union1d(gids, np.unique(d_gids))
                self._nat_cache = (t.version, nat, gids)
                return nat
        nat = np.empty((len(t), 2), dtype=np.float64)
        gids = np.unique(t.base_gid)
        for gid in gids:
            nat[t.base_gid == gid] = self._nat_of_gid(int(gid))
        self._nat_cache = (t.version, nat, gids)
        return nat

    def _donor_mask(self) -> tuple[np.ndarray, np.ndarray]:
        """(natural draws [n, 2], donor mask [n]): a node donates iff its
        natural draw sits below its caps on both components (margin 1 W).
        The one donor predicate shared by partitioning and the per-domain
        committed-draw accounting."""
        t = self.table
        nat = self._natural_draws()
        slack = t.caps - nat
        donor = t.alive & (slack[:, 0] > 1.0) & (slack[:, 1] > 1.0)
        return nat, donor

    def partition_rows(self) -> tuple[np.ndarray, np.ndarray, float]:
        """Array-native partition: (donor_rows, receiver_rows, pool).

        A node donates iff its natural draw sits below its caps on both
        components (margin 1 W); a dead node donates its entire cap
        allotment.  The result is memoized per (table version, natural-draw
        array): steady-state rounds return the *same* row-array objects,
        which downstream caches (receiver batches, measurement groups) use
        as identity tokens.
        """
        t = self.table
        if not len(t):
            z = np.empty(0, dtype=np.int64)
            return z, z, 0.0
        nat = self._natural_draws()
        c = self._part_cache
        if c is not None and c[0] == t.version and c[1] is nat:
            return c[2], c[3], c[4]
        _, donor = self._donor_mask()
        recv = t.alive & ~donor
        dead = ~t.alive
        pool = float(
            t.caps[dead].sum() + (t.caps - nat)[donor].sum()
        )
        out = (np.flatnonzero(donor), np.flatnonzero(recv), pool)
        self._part_cache = (t.version, nat, *out)
        return out

    def partition(self) -> tuple[list[NodeState], list[NodeState], float]:
        """(donors, receivers, reclaimed_pool) as NodeState views."""
        donors, recv, pool = self.partition_rows()
        return self.table.views(donors), self.table.views(recv), pool

    # -- events ---------------------------------------------------------------

    def apply_events(self, events: Sequence) -> list[str]:
        """Apply one round's scenario events in a single columnar pass.

        Events mutate the table's columns in place (order preserved —
        later events see earlier ones), replacing the legacy one-O(n)-
        list-rebuild-per-event path; returns affected instance names.
        """
        with Span("engine.apply_events"):
            t = self.table
            touched: list[str] = []
            dirty: list[np.ndarray] = []
            for event in events:
                if isinstance(event, scenario_mod.NodeFailure):
                    rows = np.flatnonzero(
                        np.isin(t.node_ids, np.asarray(event.node_ids))
                    )
                    touched.extend(t.names[r] for r in rows)
                    t.alive[rows] = False
                    dirty.append(rows)
                elif isinstance(event, scenario_mod.StragglerOnset):
                    rows = np.flatnonzero(t.node_ids == event.node_id)
                    t.slowdown[rows] = event.slowdown
                    touched.extend(t.names[r] for r in rows)
                    dirty.append(rows)
                elif isinstance(event, scenario_mod.PhaseChange):
                    if event.surface_id not in self.surfaces:
                        raise KeyError(f"unknown surface {event.surface_id!r}")
                    rows = np.flatnonzero(t.node_ids == event.node_id)
                    gid = np.int32(t.interner.intern(event.surface_id))
                    # rebind the instance's surface identity too, so
                    # predictor-backed controllers resolve the new phase
                    t.base_gid[rows] = gid
                    t.sid_gid[rows] = gid
                    touched.extend(t.names[r] for r in rows)
                    dirty.append(rows)
                elif isinstance(event, scenario_mod.NodeArrival):
                    if event.surface is not None:
                        # a genuinely new app: register its ground-truth surface
                        self.surfaces = {
                            **self.surfaces, event.app.name: event.surface
                        }
                    if event.app.name not in self.surfaces:
                        raise KeyError(
                            f"no surface for arriving app {event.app.name!r}"
                        )
                    nid = t.next_node_id()
                    domain_id = -1
                    if self.topology is not None:
                        if event.domain is not None:
                            domain_id = self.topology.require_leaf(event.domain)
                        else:
                            # the assigned id must fall inside some leaf range
                            try:
                                domain_id = int(self.topology.leaf_of([nid])[0])
                            except ValueError:
                                raise ValueError(
                                    f"arrival of {event.app.name!r} at round "
                                    f"{event.round} got node id {nid}, which no "
                                    f"leaf domain owns — pass "
                                    f"NodeArrival(domain=...) to place it"
                                ) from None
                    caps = event.caps or (self.system.init_cpu, self.system.init_gpu)
                    t.append(
                        node_id=nid,
                        name=f"{event.app.name}#n{nid}",
                        base_app=event.app.name,
                        surface_id=event.app.surface_id,
                        sclass=event.app.sclass,
                        caps=caps,
                        domain_id=domain_id,
                    )
                    dirty.append(np.array([len(t) - 1], dtype=np.int64))
                elif isinstance(event, scenario_mod.DomainCapChange):
                    if self.topology is None:
                        raise ValueError(
                            "DomainCapChange requires an attached PowerTopology"
                        )
                    if event.domain not in self.topology.index:
                        raise KeyError(f"unknown domain {event.domain!r}")
                    self._cap_overrides.set(
                        self.topology.index[event.domain], event.round, event.cap
                    )
                else:
                    known = ", ".join(
                        c.__name__ for c in scenario_mod.Event.__args__
                    )
                    raise TypeError(
                        f"unknown event type {type(event).__name__!r}: {event!r} "
                        f"(expected one of: {known}; fault events attach via "
                        f"Scenario.with_faults, not the event timeline)"
                    )
            rows = (
                np.unique(np.concatenate(dirty))
                if dirty
                else np.empty(0, dtype=np.int64)
            )
            t.bump(rows)
            return touched

    def apply_event(self, event) -> list[str]:
        """Apply one scenario event; returns affected instance names."""
        return self.apply_events([event])

    # -- measurement ----------------------------------------------------------

    def _measure_groups(self, rows: np.ndarray):
        """Distinct (base surface, slowdown) classes among ``rows`` as
        (gid, slowdown, member positions into ``rows``) triples.

        Keys pack (gid, interned slowdown rank) into one int64 so the
        grouping is a cheap integer sort instead of a structured-array
        argsort; the (gid asc, slowdown asc) group order and ascending
        member positions match the structured form exactly.  Memoized per
        (table version, rows object) — the batch freshness probe, the
        surface fill and the measurement all share one grouping per round.
        """
        t = self.table
        c = self._measure_groups_cache
        if c is not None and c[0] == t.version and c[1] is rows:
            return c[2]
        sl = t.slowdown[rows]
        uniq_s, s_rank = np.unique(sl, return_inverse=True)
        key = t.base_gid[rows].astype(np.int64) * len(uniq_s) + s_rank
        uniq, inv = np.unique(key, return_inverse=True)
        order = np.argsort(inv, kind="stable")
        counts = np.bincount(inv, minlength=len(uniq))
        splits = np.split(order, np.cumsum(counts)[:-1])
        ns = len(uniq_s)
        groups = [
            (int(uniq[k] // ns), float(uniq_s[uniq[k] % ns]), splits[k])
            for k in range(len(uniq))
        ]
        self._measure_groups_cache = (t.version, rows, groups)
        return groups

    def _measure_rows(
        self,
        rows: np.ndarray,
        base: np.ndarray,
        new: np.ndarray,
        rng: np.random.Generator,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Vectorized measurement core: per-receiver mean measured runtimes
        at (baseline, allocated) caps plus relative improvements — the same
        arrays back both the engine's reported improvements and the
        telemetry batch, so the two are bit-identical by construction.

        Baseline runtimes are deterministic per (surface, slowdown, caps)
        row, so they are cached across rounds and re-evaluated only for
        groups touching dirty rows or swapped surfaces — allocated-caps
        runtimes (and the per-round noise) are always fresh.
        """
        n = len(rows)
        if n == 0:
            z = np.zeros(0, dtype=np.float64)
            return z, z, z
        t = self.table
        strings = t.strings
        groups = self._measure_groups(rows)
        t_base: np.ndarray | None = None
        dirty_mask: np.ndarray | None = None
        csurfs: dict = {}
        c = self._tbase_cache
        if c is not None:
            cv, crows, ctb, cs = c
            if cv == t.version and crows is rows:
                t_base = ctb.copy()
                csurfs = dict(cs)
                dirty_mask = np.zeros(n, dtype=bool)
            else:
                d = t.dirty_since(cv)
                if (
                    d is not None
                    and len(crows) == n
                    and self._rows_ascending(rows)
                    and np.array_equal(crows, rows)
                ):
                    t_base = ctb.copy()
                    csurfs = dict(cs)
                    dirty_mask = np.zeros(n, dtype=bool)
                    dirty_mask[
                        np.searchsorted(rows, np.intersect1d(d, rows))
                    ] = True
        if t_base is None:
            t_base = np.empty(n, dtype=np.float64)
        t_new = np.empty(n, dtype=np.float64)
        for gid, slowdown, ii in groups:
            surf = self.surfaces[strings[gid]]
            tn = np.asarray(surf.runtime(new[ii, 0], new[ii, 1]), np.float64)
            t_new[ii] = tn * slowdown
            if (
                dirty_mask is None
                or csurfs.get((gid, slowdown)) is not surf
                or dirty_mask[ii].any()
            ):
                tb = np.asarray(
                    surf.runtime(base[ii, 0], base[ii, 1]), np.float64
                )
                t_base[ii] = tb * slowdown
            csurfs[(gid, slowdown)] = surf
        self._tbase_cache = (t.version, rows, t_base, csurfs)

        sigma = self.system.noise_sigma
        if sigma > 0:
            # C-order fill == the sequential per-(node, repeat, base/new)
            # scalar draws of the legacy loop
            factors = np.exp(rng.normal(0.0, sigma, size=(n, self.n_repeats, 2)))
            t0 = (t_base[:, None] * factors[:, :, 0]).mean(axis=1)
            t1 = (t_new[:, None] * factors[:, :, 1]).mean(axis=1)
        else:
            t0, t1 = t_base, t_new
        imp = (t0 - t1) / t0
        return t0, t1, imp

    def _rows_for_nodes(self, recv_nodes: Sequence[NodeState]) -> np.ndarray:
        return self.table.rows_for_ids([n.node_id for n in recv_nodes])

    def measure_improvements(
        self,
        recv_nodes: Sequence[NodeState],
        alloc: Allocation,
        rng: np.random.Generator,
    ) -> dict[str, float]:
        """Vectorized measurement of all receivers x repeats.

        One surface evaluation per distinct (app, slowdown) class and one
        RNG fill for the whole noise block; bit-for-bit equal to
        :func:`measure_improvements_loop`.
        """
        rows = self._rows_for_nodes(recv_nodes)
        base = self.table.caps[rows]
        names = [self.table.names[r] for r in rows]
        new = np.array([alloc.caps[nm] for nm in names], dtype=np.float64)
        _, _, imp = self._measure_rows(rows, base, new, rng)
        return {nm: float(imp[i]) for i, nm in enumerate(names)}

    def measure_improvements_loop(
        self,
        recv_nodes: Sequence[NodeState],
        alloc: Allocation,
        rng: np.random.Generator,
    ) -> dict[str, float]:
        """Legacy per-node measurement loop (equivalence/benchmark reference)."""
        improvements: dict[str, float] = {}
        for node in recv_nodes:
            surf = self._surface(node)
            c, g = alloc.caps[node.app.name]
            base_ts, new_ts = [], []
            for _ in range(self.n_repeats):
                base_ts.append(
                    measured_runtime(
                        surf,
                        *node.caps,
                        rng=rng,
                        noise_sigma=self.system.noise_sigma,
                    )
                )
                new_ts.append(
                    measured_runtime(
                        surf, c, g, rng=rng, noise_sigma=self.system.noise_sigma
                    )
                )
            t0, t1 = float(np.mean(base_ts)), float(np.mean(new_ts))
            improvements[node.app.name] = (t0 - t1) / t0
        return improvements

    # -- rounds ---------------------------------------------------------------

    def round_rng(self, policy: str, round_index: int) -> np.random.Generator:
        """Measurement RNG: round 0 replays the legacy run_round stream."""
        return np.random.default_rng(
            self.seed
            + zlib.crc32(policy.encode()) % 100003
            + round_index * _ROUND_STRIDE
        )

    def _fill_true_surfaces(
        self, rows: np.ndarray, surfaces: list
    ) -> None:
        strings = self.table.strings
        for gid, slowdown, ii in self._measure_groups(rows):
            surf = self._surface_of(strings[gid], slowdown)
            for i in ii:
                surfaces[i] = surf

    @staticmethod
    def _rows_ascending(rows: np.ndarray) -> bool:
        """The delta-patch caches position-match via searchsorted/setdiff1d,
        which require ascending (partition-ordered) row arrays; explicit
        ``run_round(receivers=...)`` callers may pass any order and must
        fall back to full rebuilds."""
        return len(rows) < 2 or bool(np.all(rows[1:] > rows[:-1]))

    def _batch_surfaces_fresh(self, rows: np.ndarray, batch) -> bool:
        """One identity probe per (surface, slowdown) class: catches true
        surfaces swapped without a table bump (direct reassignment)."""
        strings = self.table.strings
        for gid, slowdown, ii in self._measure_groups(rows):
            if batch.surfaces[ii[0]] is not self._surface_of(
                strings[gid], slowdown
            ):
                return False
        return True

    def _patch_batch(
        self, mode: str, c: tuple, rows: np.ndarray
    ) -> ReceiverBatch | None:
        """Derive this round's batch from the cached one, or None to force
        a full rebuild.

        Three outcomes, in order: the cached batch is returned unchanged
        when nothing moved (same version, same rows, surfaces still
        identity-fresh); a copy-on-write *patched* batch carrying the
        delta contract is returned when the dirty-row log bounds what
        changed and the patched surfaces probe fresh; otherwise None —
        unbounded change, non-partition row order (searchsorted/setdiff
        need ascending rows), or a surface swapped without dirtying its
        rows (e.g. NodeArrival re-registering an app's ground truth).
        """
        t = self.table
        _, c_version, c_rows, c_batch = c
        if c_version == t.version and c_rows is rows:
            if mode != "true" or self._batch_surfaces_fresh(rows, c_batch):
                return c_batch
            return None  # surfaces swapped underneath: rebuild
        dirty = t.dirty_since(c_version)
        if (
            dirty is None
            or not self._rows_ascending(rows)
            or not self._rows_ascending(c_rows)
        ):
            return None
        joined = np.setdiff1d(rows, c_rows, assume_unique=True)
        left = np.setdiff1d(c_rows, rows, assume_unique=True)
        changed = np.union1d(
            np.intersect1d(dirty, rows, assume_unique=False), joined
        )
        pos = np.searchsorted(rows, changed)
        strings = t.strings
        if mode == "skip":
            surfaces: list = [None] * len(rows)
        else:
            surfaces = list(c_batch.surfaces)
        if len(joined) or len(left):
            # membership moved: carry surviving surfaces over by row id
            # (vectorized), rebuild the positional columns
            names = [t.names[r] for r in rows]
            surface_ids = [strings[t.sid_gid[r]] for r in rows]
            if mode == "true":
                common = np.setdiff1d(rows, joined, assume_unique=True)
                sarr = np.empty(len(rows), dtype=object)
                old = np.array(c_batch.surfaces, dtype=object)
                sarr[np.searchsorted(rows, common)] = old[
                    np.searchsorted(c_rows, common)
                ]
                surfaces = sarr.tolist()
        else:
            names = list(c_batch.names)
            surface_ids = list(c_batch.surface_ids)
            for p in pos:
                surface_ids[p] = strings[t.sid_gid[rows[p]]]
        if mode == "true":
            for p in pos:
                r = rows[p]
                surfaces[p] = self._surface_of(
                    strings[t.base_gid[r]], float(t.slowdown[r])
                )
        batch = ReceiverBatch(
            names=names,
            surface_ids=surface_ids,
            baselines=t.caps[rows],
            surfaces=surfaces,
            domain_ids=(
                t.domain_id[rows] if self.topology is not None else None
            ),
            seq=next(_BATCH_SEQ),
            prev_seq=c_batch.seq,
            delta=tuple(int(p) for p in pos),
            removed=tuple(t.names[r] for r in left),
        )
        if mode == "true" and not self._batch_surfaces_fresh(rows, batch):
            return None
        self._batch_cache = (mode, t.version, rows, batch)
        return batch

    def _receiver_batch(
        self,
        rows: np.ndarray,
        policy_surfaces: Mapping[str, PowerSurface] | None,
        sees_truth: bool,
        *,
        skip_surfaces: bool = False,
    ) -> ReceiverBatch:
        """Columnar receiver view for group-collapsing controllers.

        ``skip_surfaces`` leaves the surface column unfilled for
        controllers that serve their own surfaces (``ecoshift_online``) —
        ground truth must never even transit their inputs (DESIGN.md §10
        information discipline).

        Batches are cached per (mode, table version, receiver rows): an
        event-free round returns the previous batch object unchanged
        (``delta == ()``), and a round whose dirty rows are bounded by the
        table's delta log ships a patched copy with the changed positions
        in ``delta`` — the O(churn) contract incremental controllers key
        their warm grouping state on (DESIGN.md §13).
        """
        t = self.table
        mode = (
            "skip" if skip_surfaces
            else "true" if (policy_surfaces is None or sees_truth)
            else None
        )
        c = self._batch_cache
        if mode is not None and c is not None and c[0] == mode:
            batch = self._patch_batch(mode, c, rows)
            if batch is not None:
                return batch
        names = [t.names[r] for r in rows]
        strings = t.strings
        surface_ids = [strings[t.sid_gid[r]] for r in rows]
        surfaces = [None] * len(rows)  # type: ignore[list-item]
        if skip_surfaces:
            pass
        elif policy_surfaces is not None and not sees_truth:
            surfaces = [policy_surfaces[nm] for nm in names]
        else:
            self._fill_true_surfaces(rows, surfaces)
        batch = ReceiverBatch(
            names=names,
            surface_ids=surface_ids,
            baselines=t.caps[rows],
            surfaces=surfaces,
            domain_ids=t.domain_id[rows] if self.topology is not None else None,
            seq=next(_BATCH_SEQ),
        )
        if mode is not None:
            self._batch_cache = (mode, t.version, rows, batch)
        return batch

    def _alloc_caps_array(self, alloc: Allocation, names) -> np.ndarray:
        """[n, 2] allocated caps aligned with ``names`` — one gather shared
        by the conservation check and the measurement, memoized while both
        the allocation and the names list are the reused warm objects."""
        c = self._alloc_caps_cache
        if c is not None and c[0] is alloc and c[1] is names:
            return c[2]
        new = np.array([alloc.caps[nm] for nm in names], dtype=np.float64)
        self._alloc_caps_cache = (alloc, names, new)
        return new

    def _check_domain_conservation(
        self,
        recv_rows: np.ndarray,
        names: Sequence[str],
        base: np.ndarray,
        alloc: Allocation,
        round_index: int,
        headroom: tuple[np.ndarray, np.ndarray, np.ndarray],
        *,
        enforce: bool,
    ) -> None:
        """Sim-side per-domain draw accounting after an allocation.

        Every domain's draw (committed + allocated extra, aggregated up the
        tree) is recorded in ``last_domain_draw`` / ``last_domain_caps``;
        with ``enforce`` a cap violation raises — the conservation
        guarantee of the hierarchical allocator.  Flat controllers on a
        topology sim only get the accounting (their violations are the
        point of the comparison benchmarks).
        """
        topo = self.topology
        t = self.table
        new = self._alloc_caps_array(alloc, names)
        extra_node = new.sum(axis=1) - base.sum(axis=1) if len(names) else []
        leaf = np.zeros(len(topo), dtype=np.float64)
        if len(names):
            leaf += np.bincount(
                t.domain_id[recv_rows],
                weights=extra_node,
                minlength=len(topo),
            )
        spend = topo.aggregate_leaves(leaf)
        extra, committed, caps = headroom
        draw = committed + spend
        dnames = topo.names
        self.last_domain_draw = dict(zip(dnames, draw.tolist()))
        self.last_domain_caps = dict(zip(dnames, caps.tolist()))
        if enforce:
            # the allocator is accountable for the *extra* it places: it can
            # never spend past a domain's headroom.  (A cap already below
            # the committed baseline draw is unsatisfiable under the
            # monotone-upgrade model — the allocator just gets 0 headroom.)
            over = np.flatnonzero(spend > extra + 1e-6)
            if over.size:
                i = int(over[0])
                raise RuntimeError(
                    f"round {round_index}: domain {dnames[i]!r} draws "
                    f"{draw[i]:.3f} W over its {caps[i]:.3f} W cap "
                    f"(allocated {spend[i]:.3f} W > {extra[i]:.3f} W headroom)"
                )

    def _actuate_and_guard(
        self,
        recv_rows: np.ndarray,
        names: Sequence[str],
        base: np.ndarray,
        new: np.ndarray,
        budget: float,
        round_index: int,
        headroom,
        injector,
    ):
        """Resolve actuation faults, then run the PowerGuard watchdog.

        **Actuation** replays this round's commanded caps through the
        per-receiver actuator registers: a NACKed receiver keeps its
        previously applied caps, a partial application moves only a
        fraction of the way from them, a delayed command lands *next*
        round (displacing that round's own command).  **PowerGuard** is
        the firmware-level safety net below the control-plane RPC channel:
        it checks the *applied* (post-fault) per-domain draw against the
        topology caps — and the cluster total against the round budget —
        and claws any overdraw back with the proportional emergency
        derate of ``PowerTopology.derate_factors``.  The derate lands
        within the same round, so a stuck actuator causes at most a
        sub-round excursion; registers settle on the post-derate caps, so
        the stuck state itself is safe from the next round on (DESIGN.md
        §18).

        Returns ``(applied, report, guard)``: the settled [n, 2] caps that
        measurement (and therefore telemetry) sees, the
        :class:`~repro.cluster.faults.ActuationReport` for the controller,
        and the PowerGuard stats dict (overdraw/derate/excursions).
        """
        from repro.cluster import faults as faults_mod

        t = self.table
        node_ids = t.node_ids[recv_rows]
        applied = new.copy()
        plan = injector.actuation_plan(round_index, list(names), node_ids)
        pend = self._pending_cmds
        for i, nm in enumerate(names):
            reg = self._applied_caps.get(nm)
            prev = np.asarray(reg, dtype=np.float64) if reg is not None else base[i]
            cmd = new[i]
            queued = pend.pop(nm, None)
            if queued is not None:
                # last round's delayed command lands now, displacing this
                # round's own command for this receiver
                cmd = np.asarray(queued, dtype=np.float64)
            kind, param = plan.get(nm, (None, 0.0))
            if kind == "nack":
                applied[i] = prev
            elif kind == "partial":
                applied[i] = prev + param * (cmd - prev)
            elif kind == "delay":
                pend[nm] = (float(new[i, 0]), float(new[i, 1]))
                applied[i] = prev
            else:
                applied[i] = cmd

        # -- PowerGuard: settle the applied caps under every power cap ----
        guard = {
            "overdraw_w": 0.0,
            "derate_w": 0.0,
            "excursion_domains": (),
        }
        extra_node = (
            applied.sum(axis=1) - base.sum(axis=1)
            if len(names)
            else np.zeros(0)
        )
        excursions: list[str] = []
        worst = 0.0
        pre_total = float(extra_node.sum()) if len(names) else 0.0
        if self.topology is not None and len(names):
            topo = self.topology
            leaf = np.zeros(len(topo), dtype=np.float64)
            leaf += np.bincount(
                t.domain_id[recv_rows], weights=extra_node, minlength=len(topo)
            )
            spend = topo.aggregate_leaves(leaf)
            allowed, committed, caps = headroom
            over = spend - allowed
            hot = np.flatnonzero(over > 1e-9)
            if hot.size:
                worst = float(over[hot].max())
                excursions.extend(topo.names[int(i)] for i in hot)
                factors = topo.derate_factors(spend, allowed)
                f_leaf = factors[t.domain_id[recv_rows]]
                applied = base + f_leaf[:, None] * (applied - base)
                extra_node = applied.sum(axis=1) - base.sum(axis=1)
        if len(names):
            tot = float(extra_node.sum())
            if tot > budget + 1e-9:
                worst = max(worst, tot - budget)
                if not excursions:
                    excursions.append("__budget__")
                scale = budget / tot if tot > 0 else 0.0
                applied = base + scale * (applied - base)
                extra_node = applied.sum(axis=1) - base.sum(axis=1)
            guard["derate_w"] = max(0.0, pre_total - float(extra_node.sum()))
        guard["overdraw_w"] = worst
        guard["excursion_domains"] = tuple(excursions)
        if self.topology is not None and len(names):
            # settled per-domain draw overwrites the commanded accounting
            topo = self.topology
            leaf = np.zeros(len(topo), dtype=np.float64)
            leaf += np.bincount(
                t.domain_id[recv_rows], weights=extra_node, minlength=len(topo)
            )
            spend = topo.aggregate_leaves(leaf)
            _, committed, caps = headroom
            self.last_domain_draw = dict(
                zip(topo.names, (committed + spend).tolist())
            )

        # -- settle registers + report ------------------------------------
        acked: list[str] = []
        nacked: list[str] = []
        applied_map: dict[str, tuple[float, float]] = {}
        for i, nm in enumerate(names):
            a = (float(applied[i, 0]), float(applied[i, 1]))
            self._applied_caps[nm] = a
            if (
                abs(a[0] - new[i, 0]) <= 1e-9
                and abs(a[1] - new[i, 1]) <= 1e-9
            ):
                acked.append(nm)
            else:
                nacked.append(nm)
                applied_map[nm] = a
        # non-receivers revert to baseline caps: drop their registers so a
        # later receiver round starts from the table baseline again
        cur = set(names)
        for nm in [k for k in self._applied_caps if k not in cur]:
            del self._applied_caps[nm]
            self._pending_cmds.pop(nm, None)
        report = faults_mod.ActuationReport(
            round=round_index,
            acked=tuple(acked),
            nacked=tuple(nacked),
            applied=applied_map,
        )
        return applied, report, guard

    def run_round(
        self,
        controller,
        budget: float | None = None,
        *,
        policy_surfaces: Mapping[str, PowerSurface] | None = None,
        receivers: Sequence[NodeState] | None = None,
        round_index: int = 0,
        use_loop_measurement: bool = False,
        _recv_rows: np.ndarray | None = None,
        _fault_injector=None,
    ) -> EmulationResult:
        """One redistribution round under a stateful controller.

        ``policy_surfaces`` is what the policy sees (predicted surfaces for
        EcoShift; defaults to true surfaces keyed per instance).  ``budget``
        defaults to the donor-derived reclaimed pool.  Controllers with
        ``supports_grouped`` allocate from a columnar ``ReceiverBatch``
        (group-collapsed DP); everyone else gets the per-instance view.
        """
        prof = self.last_round_profile = {}
        t = self.table
        with Span("engine.round", round=round_index):
            with Span("engine.partition") as sp:
                if receivers is not None:
                    _recv_rows = self._rows_for_nodes(receivers)
                if _recv_rows is not None and budget is not None:
                    recv_rows = np.asarray(_recv_rows)
                else:
                    _, part_rows, pool = self.partition_rows()
                    recv_rows = (
                        np.asarray(_recv_rows) if _recv_rows is not None else part_rows
                    )
                b = float(pool if budget is None else budget)
                base = t.caps[recv_rows]

                hierarchical = self.topology is not None and getattr(
                    controller, "supports_hierarchical", False
                )
                headroom = (
                    self.domain_headroom(round_index, recv_rows)
                    if self.topology is not None
                    else None
                )
            prof["partition_s"] = sp.seconds

            with Span("engine.batch") as sp:
                names: Sequence[str] | None = None
                batch = None
                if hierarchical or getattr(controller, "supports_grouped", False):
                    batch = self._receiver_batch(
                        recv_rows,
                        policy_surfaces,
                        controller.sees_truth,
                        skip_surfaces=getattr(controller, "serves_own_surfaces", False),
                    )
                    names = batch.names
            prof["batch_s"] = sp.seconds

            with Span("engine.allocate") as sp:
                if hierarchical:
                    controller.bind_topology(self.topology)
                    alloc = controller.allocate_hierarchical(batch, b, headroom[0])
                elif batch is not None:
                    alloc = controller.allocate_grouped(batch, b)
                else:
                    recv_nodes = t.views(recv_rows)
                    names = [n.app.name for n in recv_nodes]
                    recv_apps = [n.app for n in recv_nodes]
                    baselines = {n.app.name: n.caps for n in recv_nodes}
                    true_by_inst = {n.app.name: self._surface(n) for n in recv_nodes}
                    seen = (
                        policy_surfaces if policy_surfaces is not None else true_by_inst
                    )
                    if controller.sees_truth:
                        seen = true_by_inst
                    alloc = controller.allocate(recv_apps, baselines, b, seen)
            prof["allocate_s"] = sp.seconds
            # which path produced the solution (DESIGN.md §14); the fused
            # round's own split is the controller's fused_segments()
            prof["alloc_solver"] = getattr(controller, "last_solver", None) or ""
            prof["alloc_fallback_reason"] = (
                getattr(controller, "last_fallback_reason", "") or ""
            )

            with Span("engine.conserve") as sp:
                if self.topology is not None:
                    self._check_domain_conservation(
                        recv_rows, names, base, alloc, round_index, headroom,
                        enforce=hierarchical,
                    )
            prof["conserve_s"] = sp.seconds

            # -- actuation + PowerGuard (fault-injected runs, DESIGN.md §18) --
            with Span("engine.actuate") as sp:
                self.last_actuation = None
                self.last_guard = None
                applied: np.ndarray | None = None
                if _fault_injector is not None and names is not None:
                    cmd = self._alloc_caps_array(alloc, names)
                    applied, report, guard = self._actuate_and_guard(
                        recv_rows, names, base, cmd, b, round_index,
                        headroom, _fault_injector,
                    )
                    self.last_actuation = report
                    self.last_guard = guard
                    notify = getattr(controller, "notify_actuation", None)
                    if notify is not None:
                        notify(report)
            prof["actuate_s"] = sp.seconds

            with Span("engine.measure") as sp:
                rng = self.round_rng(controller.policy, round_index)
                if use_loop_measurement:
                    recv_nodes = t.views(recv_rows)
                    improvements = self.measure_improvements_loop(recv_nodes, alloc, rng)
                    self.last_telemetry = ()
                else:
                    new = (
                        applied
                        if applied is not None
                        else self._alloc_caps_array(alloc, names)
                    )
                    t0, t1, imp = self._measure_rows(recv_rows, base, new, rng)
                    improvements = dict(zip(names, imp.tolist()))
                    self.last_telemetry = TelemetryBatch(
                        round=round_index,
                        inst_gids=t.name_gid[recv_rows],
                        app_gids=t.base_gid[recv_rows],
                        strings=t.strings,
                        baseline_caps=base,
                        allocated_caps=new,
                        t_baseline=t0,
                        t_allocated=t1,
                        improvement=imp,
                    )
            prof["measure_s"] = sp.seconds
        return EmulationResult(
            policy=controller.policy,
            improvements=improvements,
            allocation=alloc,
            budget=b,
        )

    def run(
        self,
        scenario: Scenario,
        controller,
        *,
        policy_surfaces: Mapping[str, PowerSurface]
        | Callable[["ClusterSim"], Mapping[str, PowerSurface]]
        | None = None,
    ) -> SimResult:
        """Step a scenario: per round, apply events -> allocate -> measure
        -> feed telemetry back to the controller.

        ``policy_surfaces`` may be a mapping (static predicted surfaces) or
        a callable ``sim -> mapping`` re-evaluated each round (the node set
        changes under arrivals/failures).  Predictor-backed controllers
        (``ecoshift_online``) ignore it and serve their own surfaces; they
        receive each round's telemetry via ``ingest_telemetry`` and
        invalidate their warm caches only for surfaces that actually moved.
        """
        if isinstance(controller, str):
            from repro.core import policies as policies_mod

            controller = policies_mod.get_controller(controller, self.system)
        if scenario.topology is not None:
            if self.topology is None:
                self.attach_topology(scenario.topology)
            elif self.topology is not scenario.topology:
                raise ValueError(
                    "scenario topology differs from the sim's attached one"
                )
        injector = None
        if getattr(scenario, "faults", ()):
            from repro.cluster import faults as faults_mod

            injector = faults_mod.FaultInjector(scenario.faults)
            # fresh actuator state per run: registers model the physical
            # caps of *this* run's actuation channel
            self._applied_caps.clear()
            self._pending_cmds.clear()
        records: list[RoundRecord] = []
        # receding-horizon controllers get a per-round budget outlook: the
        # provider-backed cap forecast plus the CO2 (or price) weight
        # signal over the controller's horizon (DESIGN.md §15)
        horizon = int(getattr(controller, "horizon", 1) or 1)
        feeds_outlook = horizon > 1 and hasattr(
            controller, "set_budget_outlook"
        )
        for r in range(scenario.n_rounds):
            if injector is not None:
                # controller crashes fire at round start, before the round's
                # events and solve — the replacement process (restored or
                # cold) must handle everything the round throws at it
                injector.maybe_crash(r, controller)
            events = scenario.events_at(r)
            touched = self.apply_events(events) if events else []
            if touched:
                controller.invalidate(touched)
            seen = (
                policy_surfaces(self)
                if callable(policy_surfaces)
                else policy_surfaces
            )
            _, recv_rows, pool = self.partition_rows()
            b = scenario.budget_at(r)
            if feeds_outlook:
                caps = [
                    pool if c is None else float(c)
                    for c in scenario.budget_forecast(r, horizon)
                ]
                caps[0] = float(pool if b is None else b)
                weights = scenario.carbon_forecast(r, horizon)
                if all(w is None for w in weights):
                    weights = scenario.price_forecast(r, horizon)
                controller.set_budget_outlook(
                    caps,
                    None
                    if all(w is None for w in weights)
                    else [1.0 if w is None else float(w) for w in weights],
                )
            res = self.run_round(
                controller,
                budget=pool if b is None else b,
                policy_surfaces=seen,
                round_index=r,
                _recv_rows=recv_rows,
                _fault_injector=injector,
            )
            if injector is not None:
                delivered, tkinds = injector.deliver(r, self.last_telemetry)
            else:
                delivered, tkinds = [self.last_telemetry], ()
            guard = self.last_guard or {}
            report = self.last_actuation
            records.append(
                RoundRecord(
                    round=r,
                    result=res,
                    pool=pool,
                    n_alive=int(np.count_nonzero(self.table.alive)),
                    events=events,
                    power_price=scenario.price_at(r),
                    carbon_intensity=scenario.carbon_at(r),
                    telemetry=self.last_telemetry,
                    domain_draw=self.last_domain_draw,
                    domain_caps=self.last_domain_caps,
                    overdraw_w=float(guard.get("overdraw_w", 0.0)),
                    derate_w=float(guard.get("derate_w", 0.0)),
                    excursion_domains=tuple(
                        guard.get("excursion_domains", ())
                    ),
                    nacked=tuple(report.nacked) if report is not None else (),
                    telemetry_faults=tkinds,
                )
            )
            for tb in delivered:
                controller.ingest_telemetry(tb)
            if injector is not None:
                injector.end_round(r, controller)
        return SimResult(policy=controller.policy, records=records)
