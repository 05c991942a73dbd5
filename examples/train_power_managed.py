"""End-to-end driver: train an LM with checkpointing under EcoShift rounds.

Trains a reduced granite-family model (use --d-model/--layers/--steps to
scale up to ~100M params on real hardware) with the full substrate:
packed-Zipf data pipeline, AdamW + cosine schedule, atomic checkpoints,
crash-resume, and a periodic EcoShift power round that treats this job and
its emulated co-tenants as receivers of reclaimed pod power (surfaces from
the roofline power model).

    PYTHONPATH=src python examples/train_power_managed.py --steps 120
"""

import argparse
import dataclasses
import pathlib
import tempfile

from repro import configs
from repro.cluster import ClusterSim, Scenario
from repro.cluster.sim import NodeState
from repro.core import policies
from repro.core.arch_surfaces import RooflineSurface
from repro.core.types import SYSTEM_TPU_V5E, AppSpec
from repro.kernels.ops import use_compile_cache
from repro.models.model import Model
from repro.train.checkpoint import CheckpointManager
from repro.train.data import make_batch_fn
from repro.train.train_loop import Trainer


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--power-round-every", type=int, default=40)
    args = ap.parse_args()

    cfg = configs.smoke_config(args.arch)
    if args.d_model:
        cfg = dataclasses.replace(cfg, d_model=args.d_model)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    model = Model(cfg)

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="ecoshift_train_")
    trainer = Trainer(
        model=model,
        batch_fn=make_batch_fn(cfg, batch=args.batch, seq=args.seq),
        ckpt=CheckpointManager(pathlib.Path(ckpt_dir)),
        ckpt_every=20,
        peak_lr=3e-3,
        total_steps=args.steps,
    )
    if trainer.resume():
        print(f"resumed from checkpoint at step {trainer.step}")
    else:
        trainer.init()
        print(f"fresh run; checkpoints -> {ckpt_dir}")

    # this job + emulated co-tenants as a 3-node EcoShift pod: a declarative
    # scenario drives the budget trace and ONE stateful controller carries
    # its cached option tables across every power round
    me = AppSpec("this-train-job", "G", "this-train-job")
    peers = [
        AppSpec("decode-service", "C", "decode-service"),
        AppSpec("prefill-burst", "B", "prefill-burst"),
    ]
    surfs = {
        "this-train-job": RooflineSurface(5e13, 1e11, 5e9, 1e6, 0.010),
        "decode-service": RooflineSurface(5e9, 5e9, 1e8, 1e5, 0.020),
        "prefill-burst": RooflineSurface(2e13, 8e10, 3e9, 5e5, 0.012),
    }
    nodes = [
        NodeState(node_id=i, app=a, base_app=a.name, caps=(250.0, 150.0))
        for i, a in enumerate((me, *peers))
    ]
    sim = ClusterSim(
        system=SYSTEM_TPU_V5E, nodes=nodes, surfaces=surfs, n_repeats=1
    )
    n_rounds = -(-args.steps // args.power_round_every)
    scen = Scenario.constant(n_rounds, budget=120.0)
    controller = policies.get_controller("ecoshift", SYSTEM_TPU_V5E)

    round_idx = 0
    while trainer.step < args.steps:
        n = min(args.power_round_every, args.steps - trainer.step)
        hist = trainer.run(n)
        loss = hist[-1]["loss"]
        res = sim.run_round(
            controller,
            budget=scen.budget_at(round_idx),
            receivers=sim.nodes,
            round_index=round_idx,
        )
        round_idx += 1
        c, g = res.allocation.caps["this-train-job"]
        gain = float(
            surfs["this-train-job"].improvement((250.0, 150.0), c, g)
        )
        print(
            f"step {trainer.step:4d}  loss {loss:.4f}  "
            f"power round: this job -> ({c:.0f} W host, {g:.0f} W chip), "
            f"predicted speedup {gain*100:.1f}%"
        )
    first, last = trainer.history[0]["loss"], trainer.history[-1]["loss"]
    print(f"done: loss {first:.3f} -> {last:.3f} over {trainer.step} steps")


if __name__ == "__main__":
    main()
