import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    ).strip()
# ^ MUST precede every other import (jax locks the device count on first
#   init).  The 512 placeholder host devices exist ONLY for this dry-run:
#   importing a helper from this module leaves the process's devices alone.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the abstract step function (train_step / prefill / serve_step),
  2. lowers it with ShapeDtypeStruct inputs under the production mesh
     (16x16 single-pod, 2x16x16 multi-pod) with the full sharding rules,
  3. compiles, prints memory_analysis() (proof-of-fit) and cost_analysis(),
  4. analyzes the partitioned HLO (trip-count-corrected flops / bytes /
     per-kind collective bytes) and derives the three roofline terms,
  5. writes one JSON per cell under experiments/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-27b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import pathlib
import time
import traceback

import jax
import numpy as np

from repro import configs
from repro.launch import sharding as shr
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.models.shardctx import use_rules
from repro.roofline import hlo as hlo_mod
from repro.roofline import model as roof

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def cell_applicable(cfg, shape_name: str) -> tuple[bool, str]:
    info = shr.SHAPES[shape_name]
    if info["kind"] == "decode" and not cfg.supports_decode():
        return False, "encoder-only arch has no decode step"
    if shape_name == "long_500k" and not cfg.supports_long_context():
        return False, "full-attention arch skips 500k decode (DESIGN.md §4)"
    return True, ""


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    accum: int | None = None,
    layout: str = "fsdp_tp",
    ssm_chunk: int | None = None,
) -> dict:
    cfg = configs.get_config(arch)
    import dataclasses as _dc

    if accum:
        cfg = _dc.replace(cfg, grad_accum=accum)
    if ssm_chunk and cfg.ssm:
        cfg = _dc.replace(cfg, ssm=_dc.replace(cfg.ssm, chunk=ssm_chunk))
    ok, why = cell_applicable(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    info = shr.SHAPES[shape_name]
    kind = info["kind"]
    if kind != "train":
        # serving deploys bf16 weights (fp32 masters are a training artifact)
        import dataclasses as _dc

        cfg = _dc.replace(cfg, param_dtype=cfg.dtype)
    model = Model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    specs = steps_mod.input_specs(model, shape_name)

    mb = info["batch"] // (cfg.grad_accum if kind == "train" else 1)
    rules = shr.activation_rules(
        cfg, mesh, multi_pod, mb, mode=kind, seq=info["seq"], layout=layout
    )

    t0 = time.time()
    if kind == "train":
        step, _ = steps_mod.make_train_step(model)
        state_sh = shr.state_sharding(specs["state"], mesh, multi_pod, layout)
        batch_sh = shr.batch_sharding(specs["batch"], mesh, multi_pod, layout)
        jitted = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, shr.replicated(mesh)),
            donate_argnums=(0,),
        )
        with use_rules(rules):
            lowered = jitted.lower(specs["state"], specs["batch"])
    elif kind == "prefill":
        step = steps_mod.make_prefill_step(model)
        params_sh = shr.params_sharding(specs["params"], mesh, multi_pod, layout)
        batch_sh = shr.batch_sharding(specs["batch"], mesh, multi_pod, layout)
        # the emitted KV cache leaves sharded via the production-point
        # `cache_kv` constraint inside each layer (an out_shardings
        # constraint on the stacked scan ys triggers the partitioner's
        # replicate-then-reshard fallback instead)
        jitted = jax.jit(step, in_shardings=(params_sh, batch_sh))
        with use_rules(rules):
            lowered = jitted.lower(specs["params"], specs["batch"])
    else:  # decode
        step = steps_mod.make_serve_step(model)
        params_sh = shr.params_sharding(specs["params"], mesh, multi_pod, layout)
        batch_sh = shr.batch_sharding(specs["batch"], mesh, multi_pod, layout)
        cache_sh = shr.cache_sharding(
            specs["cache"], cfg, mesh, multi_pod, info["batch"], layout
        )
        jitted = jax.jit(
            step,
            in_shardings=(params_sh, batch_sh, cache_sh, shr.replicated(mesh)),
            out_shardings=(shr.replicated(mesh), cache_sh),
            donate_argnums=(2,),
        )
        with use_rules(rules):
            lowered = jitted.lower(
                specs["params"], specs["batch"], specs["cache"], specs["lengths"]
            )
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    hc = hlo_mod.analyze(txt)

    # memory_analysis is per-device for SPMD executables
    mem_stats = {
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
    }
    peak = (
        mem_stats["argument_bytes"]
        + mem_stats["temp_bytes"]
        + mem_stats["output_bytes"]
        - mem_stats["alias_bytes"]
    )

    terms = roof.terms_from_perdevice(
        hc.dot_flops, hc.traffic_bytes, hc.collective_bytes
    )
    mflops = roof.model_flops(cfg, info)
    result = {
        "arch": arch,
        "shape": shape_name,
        "layout": layout,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "kind": kind,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem_stats,
        "peak_bytes_per_device": int(peak),
        "fits_16gb": bool(peak < 16e9),
        "cost_analysis_flops_raw": float(cost.get("flops", 0.0)),
        "hlo_dot_flops_per_device": hc.dot_flops,
        "hlo_traffic_bytes_per_device": hc.traffic_bytes,
        "hlo_collective_bytes_per_device": hc.collective_bytes,
        "collective_by_kind": {k: float(v) for k, v in hc.collective_by_kind.items()},
        "collective_counts": {k: float(v) for k, v in hc.collective_counts.items()},
        "while_trip_counts": hc.while_trips[:32],
        "roofline": terms.as_dict(),
        "model_flops_global": mflops,
        "model_flops_per_device": mflops / n_dev,
        "useful_flops_ratio": (
            mflops / n_dev / hc.dot_flops if hc.dot_flops else 0.0
        ),
    }
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(shr.SHAPES) + [None])
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--layout", default="fsdp_tp", choices=["fsdp_tp", "pure_dp", "ep_pod"])
    ap.add_argument("--ssm-chunk", type=int, default=None)
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = [args.arch] if args.arch else configs.all_arch_ids()
    shapes = [args.shape] if args.shape else list(shr.SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                tag = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}"
                if args.layout != "fsdp_tp":
                    tag += f"__{args.layout}"
                path = out_dir / f"{tag}.json"
                try:
                    res = run_cell(
                        arch, shape_name, multi_pod,
                        accum=args.accum, layout=args.layout,
                        ssm_chunk=args.ssm_chunk,
                    )
                except Exception as e:  # noqa: BLE001 - report and continue
                    traceback.print_exc()
                    res = {
                        "arch": arch,
                        "shape": shape_name,
                        "mesh": "2x16x16" if multi_pod else "16x16",
                        "error": f"{type(e).__name__}: {e}",
                    }
                    failures.append(tag)
                path.write_text(json.dumps(res, indent=2))
                if "skipped" in res:
                    print(f"[skip] {tag}: {res['skipped']}")
                elif "error" in res:
                    print(f"[FAIL] {tag}: {res['error'][:200]}")
                else:
                    r = res["roofline"]
                    print(
                        f"[ ok ] {tag}: peak={res['peak_bytes_per_device']/1e9:.2f}GB"
                        f" compute={r['compute_s']*1e3:.2f}ms"
                        f" mem={r['memory_s']*1e3:.2f}ms"
                        f" coll={r['collective_s']*1e3:.2f}ms"
                        f" bottleneck={r['bottleneck']}"
                        f" (compile {res['compile_s']}s)"
                    )
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("\ndry-run complete")


if __name__ == "__main__":
    main()
