"""Run the fused control round natively on a TPU and check it against the host.

Drives the site-scale HPC deployment of ``benchmarks/incremental_alloc.py``
(10,000 nodes of the paper's System 1 suite on a binding 16-rack
site -> rack topology, 12 rounds under budget drift with 10% mixed churn:
stragglers, phase changes, failures replaced by arrivals; then the same
12 rounds event-free, where every round spends — under churn the arrivals'
committed draw exhausts the rack headroom after round 1) through the
normal entry points — churn events -> ``ClusterSim`` ->
``make_controller("ecoshift_hier", fused=True)`` -> the jitted device
pipeline with its Mosaic-compiled (max,+) kernels.  The host incremental
controller runs through an identical sim in the same process as the
reference, and every round is held to the on-chip contract of DESIGN.md
§14: the caps respect every domain cap and the budget exactly, and the
round's total value trails the host optimum by at most its float32 bound.

    python chip_smoke.py                # one chip, unsharded leaf DPs
    python chip_smoke.py --four-chips   # leaf DPs sharded over four chips

One process drives the chip and starts no other.  Exits nonzero, with no
result line, when JAX finds no TPU, when a fused-path kernel was built for
interpret mode or did not compile to a Mosaic kernel, when a post-warmup
round left the fused path or fell back to the host, or when any round
broke the contract.  Earlier lines are informational (per-round allocate
times are host-clock figures, not a benchmark); the last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

N_NODES = 10_000
N_RACKS = 16
#: phases: the 10% mixed-churn storm, then event-free budget drift
CHURNS = (0.10, 0.0)
#: one step of the integer micro-watt lattice every spend lives on
CAP_TOL_W = 1e-6


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _check_round(r, budget, fused, host) -> dict:
    """The DESIGN.md §14 contract for one round of the fused controller
    against the host incremental controller on an identical sim."""
    import numpy as np

    _, fsim, fctrl, fres = fused
    _, _, _, hres = host
    fa, ha = fres.allocation, hres.allocation
    _check(
        fa.spent <= budget + CAP_TOL_W,
        f"round {r}: spends {fa.spent} W over the {budget} W budget",
    )
    topo = fsim.topology
    _, recv_rows, _ = fsim.partition_rows()
    extra, committed, _caps = fsim.domain_headroom(r, recv_rows)
    draw = np.array([fsim.last_domain_draw[nm] for nm in topo.names])
    over = (draw - committed) - extra
    _check(
        bool((over <= CAP_TOL_W).all()),
        f"round {r}: domain {topo.names[int(np.argmax(over))]!r} spends "
        f"{float(over.max())} W past its headroom",
    )
    same = dict(fa.caps) == dict(ha.caps)
    n = len(ha.caps)
    _check(len(fa.caps) == n, f"round {r}: receiver sets differ")
    gap = (ha.predicted_improvement - fa.predicted_improvement) * n
    slop = 1e-12 * max(1.0, abs(ha.predicted_improvement * n))
    if fctrl.last_solver == "fused":
        bound = fctrl.fused_stats().value_bound
    else:
        bound = 0.0  # host-served rounds are the host's own answer
    if same:
        _check(
            fa.spent == ha.spent and gap == 0.0,
            f"round {r}: equal picks but spend/value differ",
        )
    _check(
        -slop <= gap <= bound + slop,
        f"round {r}: value gap {gap} outside [0, {bound}]",
    )
    return {"gap": gap, "bound": bound, "same_picks": same}


def _check_placement(fstate, n_chips: int) -> None:
    """The resident banks spread leaf-wise over every shard's device."""
    for bank in (fstate.kb_dev, fstate.vb_dev):
        devs = {s.device for s in bank.addressable_shards}
        _check(
            len(devs) == n_chips,
            f"resident bank on {len(devs)} devices, not {n_chips}",
        )


def smoke_rounds(n_nodes: int, n_racks: int, churn: float) -> dict:
    """Run the fused-vs-host scenario at one churn rate and check every
    round.

    Raises ``SystemExit`` on the first broken check; returns a summary.
    Runs on whatever backend JAX has — ``main`` insists on a TPU."""
    from benchmarks import incremental_alloc as ia
    from benchmarks.common import get_suite

    system, apps, surfs = get_suite("system1-a100")
    topo = ia._topology(
        system, apps, surfs, n_nodes, n_racks, ia._budget(n_nodes)
    )
    variants = (("fused", {"fused": True}), ("host", {}))
    rounds = []
    warm_fallbacks = None
    fctrl = None
    for r, b, results in ia.fused_churn_rounds(
        system, apps, surfs, n_nodes, churn, topology=topo,
        policy="ecoshift_hier", variants=variants,
    ):
        fused, host = results
        fctrl = fused[2]
        chk = _check_round(r, b, fused, host)
        prof_f, prof_h = fused[1].last_round_profile, host[1].last_round_profile
        row = {
            "churn": churn,
            "round": r,
            "budget_w": b,
            "solver": fctrl.last_solver,
            "fused_alloc_s": prof_f["allocate_s"],
            "fused_device_s": (
                fctrl.fused_segments()["dispatch_s"]
                if fctrl.last_solver == "fused" else 0.0
            ),
            "host_alloc_s": prof_h["allocate_s"],
            "spent_w": fused[3].allocation.spent,
            **chk,
        }
        rounds.append(row)
        print(json.dumps(row), flush=True)
        if r >= ia.CHURN_WARMUP_ROUNDS:
            _check(
                fctrl.last_solver == "fused",
                f"round {r}: served by {fctrl.last_solver!r}, not fused "
                f"({fctrl.last_fallback_reason or 'no fallback reason'})",
            )
        if r == ia.CHURN_WARMUP_ROUNDS - 1:
            warm_fallbacks = fctrl.fused_stats().fallbacks
    stats = fctrl.fused_stats()
    _check(
        stats.fallbacks == warm_fallbacks,
        f"{stats.fallbacks - warm_fallbacks} post-warmup host fallbacks",
    )
    fstate = fctrl._fused_state
    return {
        "rounds": rounds,
        "fstate": fstate,
        "stats": stats,
        "layout": fstate.shape[:6],
        "max_gap": max(x["gap"] for x in rounds),
        "max_bound": max(x["bound"] for x in rounds),
        "rounds_with_other_picks": sum(not x["same_picks"] for x in rounds),
        "rounds_spending": sum(x["spent_w"] > 0 for x in rounds),
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="shard the fused round's leaf DPs over four chips (needs a "
        "four-chip host); runs only that path and its host reference",
    )
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    os.environ["REPRO_FUSED_SHARDS"] = str(n_chips)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    import jax

    from repro.core import mckp
    from repro.kernels import ops

    cache_dir = ops.use_compile_cache()
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU (JAX backend {backend!r})", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < n_chips:
        print(
            f"chip_smoke: needs {n_chips} chips, JAX sees {len(devices)}",
            file=sys.stderr,
        )
        return 2
    print(
        f"device: {devices[0].device_kind} x{len(devices)}  "
        f"shards: {n_chips}  compile cache: {cache_dir}",
        flush=True,
    )

    compiles = {"n": 0, "s": 0.0}

    def on_compile(event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    # record how every fused pipeline was built, and its last call
    pipelines = {"interpret": set(), "last": None}
    build = mckp._fused_pipeline_fn

    def recording_build(*spec):
        run = build(*spec)
        pipelines["interpret"].add(spec[-1])

        def call(*args):
            pipelines["last"] = (run, args)
            return run(*args)

        return call

    mckp._fused_pipeline_fn = recording_build
    for churn in CHURNS:
        t0 = time.perf_counter()
        summary = smoke_rounds(N_NODES, N_RACKS, churn)
        _check_placement(summary["fstate"], n_chips)
        stats = summary["stats"]
        print(json.dumps({
            "phase": f"churn={churn}",
            "device_kind": devices[0].device_kind,
            "shards": n_chips,
            "layout(kind,L,S,K,NB,NBT)": list(summary["layout"]),
            "value_dtype": str(summary["fstate"].vb_dev.dtype),
            "compiles": compiles["n"],
            "compile_s": compiles["s"],
            "wall_s": time.perf_counter() - t0,
            "fused_rounds": stats.rounds,
            "fallbacks": stats.fallbacks,
            "rebuilds": stats.rebuilds,
            "compactions": stats.compactions,
            "row_uploads": stats.row_uploads,
            "rounds_spending": summary["rounds_spending"],
            "max_value_gap": summary["max_gap"],
            "max_value_bound": summary["max_bound"],
            "rounds_with_other_picks": summary["rounds_with_other_picks"],
        }), flush=True)

    _check(
        pipelines["interpret"] == {False},
        f"fused pipelines built with interpret={pipelines['interpret']}",
    )
    run, run_args = pipelines["last"]
    hlo = run.lower(*run_args).compile().as_text()
    _check(
        "tpu_custom_call" in hlo,
        "the fused pipeline holds no Mosaic kernel",
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
