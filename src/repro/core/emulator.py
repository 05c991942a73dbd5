"""Emulation-based cluster evaluation (paper §5.4) — single-round facade.

Since the cluster control loop moved into ``repro.cluster`` (scenario /
controller / sim), this module is a thin wrapper kept for the paper-figure
benchmarks and tests: one ``ClusterEmulator`` is one ``ClusterSim`` plus
the legacy ``run_round(policy_name, ...)`` calling convention (a fresh
stateless controller per call, measurement RNG seeded exactly as before).

Multi-round studies — failures mid-run, straggler onsets, budget traces —
should use :class:`repro.cluster.sim.ClusterSim` with a
:class:`~repro.cluster.scenario.Scenario` directly; ``fail_nodes`` /
``add_straggler`` here mutate state between independent single rounds.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

from repro.cluster.sim import (  # noqa: F401  (re-exported legacy names)
    ClusterSim,
    NodeState,
    _SlowedSurface,
)
from repro.core import policies as policies_mod
from repro.core.surfaces import PowerSurface
from repro.core.types import AppSpec, EmulationResult, SystemSpec


@dataclasses.dataclass
class ClusterEmulator:
    system: SystemSpec
    nodes: list[NodeState]
    #: true surfaces keyed by *base* app name
    surfaces: Mapping[str, PowerSurface]
    n_repeats: int = 5
    seed: int = 0

    # -- construction --------------------------------------------------------

    @staticmethod
    def build(
        system: SystemSpec,
        apps: Sequence[AppSpec],
        surfaces: Mapping[str, PowerSurface],
        *,
        n_nodes: int = 100,
        seed: int = 0,
        initial_caps: tuple[float, float] | None = None,
    ) -> "ClusterEmulator":
        """Place ``n_nodes`` instances by cycling a shuffled app list."""
        sim = ClusterSim.build(
            system,
            apps,
            surfaces,
            n_nodes=n_nodes,
            seed=seed,
            initial_caps=initial_caps,
        )
        return ClusterEmulator(
            system=system, nodes=sim.nodes, surfaces=surfaces, seed=seed
        )

    def _sim(self) -> ClusterSim:
        """Engine view sharing this emulator's node list."""
        return ClusterSim(
            system=self.system,
            nodes=self.nodes,
            surfaces=self.surfaces,
            n_repeats=self.n_repeats,
            seed=self.seed,
        )

    # -- donor / receiver partition ------------------------------------------

    def _surface(self, node: NodeState) -> PowerSurface:
        return self._sim()._surface(node)

    def partition(self) -> tuple[list[NodeState], list[NodeState], float]:
        """(donors, receivers, reclaimed_pool) — see ClusterSim.partition."""
        return self._sim().partition()

    # -- one redistribution round ---------------------------------------------

    def run_round(
        self,
        policy: str,
        budget: float | None = None,
        *,
        policy_surfaces: Mapping[str, PowerSurface] | None = None,
        receivers: Sequence[NodeState] | None = None,
    ) -> EmulationResult:
        """Apply ``policy`` and measure improvements on true surfaces.

        ``policy_surfaces`` is what the policy sees (predicted surfaces for
        EcoShift; defaults to true surfaces keyed per instance).  ``budget``
        defaults to the donor-derived reclaimed pool.
        """
        controller = policies_mod.get_controller(policy, self.system)
        return self._sim().run_round(
            controller,
            budget=budget,
            policy_surfaces=policy_surfaces,
            receivers=receivers,
        )

    # -- fault tolerance / stragglers -----------------------------------------

    def fail_nodes(self, node_ids: Sequence[int]) -> None:
        """Kill nodes; their power returns to the pool on the next round."""
        ids = set(node_ids)
        self.nodes = [
            dataclasses.replace(n, alive=False) if n.node_id in ids else n
            for n in self.nodes
        ]

    def add_straggler(self, node_id: int, slowdown: float) -> None:
        self.nodes = [
            dataclasses.replace(n, slowdown=slowdown) if n.node_id == node_id else n
            for n in self.nodes
        ]

    def alive_nodes(self) -> list[NodeState]:
        return [n for n in self.nodes if n.alive]
