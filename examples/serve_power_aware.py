"""Power-aware serving: batched prefill+decode with cap-dependent latency.

Serves a reduced gemma3-family model (5:1 local:global attention with ring
KV caches) through the batched engine, then reports the roofline power
model's token latency across chip caps — the surface EcoShift uses to
decide whether this service deserves reclaimed watts.

    PYTHONPATH=src python examples/serve_power_aware.py
"""

import dataclasses
import time

import jax
import numpy as np

from repro import configs
from repro.core.arch_surfaces import RooflineSurface
from repro.kernels.ops import use_compile_cache
from repro.models.model import Model
from repro.roofline import model as roof
from repro.serving.engine import ServeEngine


def main() -> None:
    use_compile_cache()
    cfg = dataclasses.replace(configs.smoke_config("gemma3-27b"), dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model=model, params=params, s_max=96)

    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 48), 0, cfg.vocab)
    }
    t0 = time.time()
    out = engine.generate(batch, n_steps=8)
    dt = time.time() - t0
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({out.size / dt:.1f} tok/s on CPU smoke model)")
    print("sample:", np.asarray(out[0]))

    # the production-cell picture: gemma3-27b decode_32k on a v5e pod
    surf = RooflineSurface(
        flops_pd=2e10, bytes_pd=1.2e11, coll_pd=5e9, host_bytes_pd=1e5,
        host_base_s=0.020,
    )
    print("\nroofline token latency vs chip cap (host cap 300 W):")
    for cap in (100, 140, 180, 220, 250):
        t = float(surf.runtime(300.0, cap))
        print(f"  chip cap {cap:3d} W -> {t*1e3:7.2f} ms/token "
              f"(freq x{roof.freq_fraction(cap):.2f})")
    print("\nhost-cap sensitivity at chip 180 W:")
    for cap in (150, 250, 350, 450):
        t = float(surf.runtime(cap, 180.0))
        print(f"  host cap {cap:3d} W -> {t*1e3:7.2f} ms/token")


if __name__ == "__main__":
    main()
