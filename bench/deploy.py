"""Hand a deployment and its traffic to the program under test.

The only module of the benchmark that builds the program's objects: the
surfaces, nodes and power tree of ``bench/cluster.py`` become a
``ClusterSim`` with an attached ``PowerTopology`` and the fused
``ecoshift_hier`` controller, and each round's plain events become the
program's scenario events.
"""

from __future__ import annotations

from bench.cluster import Deployment, NodeState


def system_spec(dep: Deployment):
    """The program's system spec named by the configuration, checked
    against the grid the configuration states."""
    from repro.core import types

    spec = types.SYSTEMS[dep.config["system"]["name"]]
    g = spec.grid
    got = {"cpu_min": g.cpu_min, "cpu_max": g.cpu_max, "gpu_min": g.gpu_min,
           "gpu_max": g.gpu_max, "step": g.step}
    if got != {k: float(v) for k, v in dep.grid.items()}:
        raise ValueError(f"system {spec.name}: grid {got} != config {dep.grid}")
    return spec


def surfaces(dep: Deployment) -> tuple[list, dict]:
    """(apps, surfaces) of the program, from the benchmark's parameters."""
    from repro.core.surfaces import AnalyticSurface, SpeedCurve
    from repro.core.types import AppSpec

    apps, surfs = [], {}
    for name, p in dep.params.items():
        apps.append(AppSpec(name=name, sclass=p["sclass"], surface_id=name))
        surfs[name] = AnalyticSurface(
            host_work=p["host_work"],
            dev_work=p["dev_work"],
            phi_h=SpeedCurve(p0=p["phi_h"][0], tau=p["phi_h"][1]),
            phi_d=SpeedCurve(p0=p["phi_d"][0], tau=p["phi_d"][1]),
            rho=p["rho"],
            natural_cpu=p["natural"][0],
            natural_gpu=p["natural"][1],
        )
    return apps, surfs


def topology(dep: Deployment):
    """The program's ``PowerTopology`` of the deployment's tree and caps."""
    from repro.core.topology import PowerDomain, PowerTopology

    tree, caps = dep.tree, dep.domain_caps
    leaf_pos = {int(d): k for k, d in enumerate(tree.leaf_ids)}

    def build(i: int):
        kids = tree.children(i)
        if not len(kids):
            return PowerDomain(
                name=tree.names[i], cap=float(caps[i]),
                nodes=(tree.leaf_ranges[leaf_pos[i]],),
            )
        return PowerDomain(
            name=tree.names[i], cap=float(caps[i]),
            children=tuple(build(int(c)) for c in kids),
        )

    return PowerTopology(build(0), n_nodes=dep.n_nodes)


def build(dep: Deployment, state: NodeState, seed: int):
    """(sim, controller, apps-by-name) for the deployment's initial state."""
    from repro.cluster import ClusterSim
    from repro.cluster.controller import make_controller
    from repro.cluster.sim import NodeState as ProgramNode
    from repro.core.types import AppSpec

    system = system_spec(dep)
    apps, surfs = surfaces(dep)
    by_name = {a.name: a for a in apps}
    names = dep.app_names
    nodes = []
    for nid in range(len(state.app)):
        a = by_name[names[state.app[nid]]]
        nodes.append(ProgramNode(
            node_id=nid,
            app=AppSpec(name=state.name(nid, names), sclass=a.sclass,
                        surface_id=a.surface_id),
            base_app=a.name,
            caps=dep.init_caps,
        ))
    sim = ClusterSim(
        system=system, nodes=nodes, surfaces=surfs,
        seed=int(seed) % (2**32), topology=topology(dep),
    )
    ctrl = make_controller("ecoshift_hier", system, fused=True)
    return sim, ctrl, by_name


def program_events(events: list[tuple], r: int, by_name: dict, dep: Deployment,
                   leaf_names: list[str]) -> list:
    """The program's scenario events for one round's plain events."""
    from repro.cluster import scenario as sc

    out = []
    for ev in events:
        kind = ev[0]
        if kind == "straggler":
            out.append(sc.StragglerOnset(round=r, node_id=ev[1], slowdown=ev[2]))
        elif kind == "phase":
            out.append(sc.PhaseChange(round=r, node_id=ev[1], surface_id=ev[2]))
        elif kind == "failure":
            out.append(sc.NodeFailure(round=r, node_ids=(ev[1],)))
        elif kind == "arrival":
            out.append(sc.NodeArrival(
                round=r, app=by_name[ev[2]], domain=leaf_names[ev[3]],
                caps=dep.init_caps,
            ))
        else:
            raise ValueError(f"unknown event {ev!r}")
    return out


def leaf_names(dep: Deployment) -> list[str]:
    return [dep.tree.names[int(i)] for i in dep.tree.leaf_ids]

