"""The EcoShift paper's application suite as plain data.

A copy of the Table-1 generator of the program under test: 40
applications in four sensitivity classes, each with an analytic
power-performance surface drawn from a stable per-(system, app) seed.
The benchmark draws the surfaces here and hands them to the program, so
the plain reference reads the same numbers without taking any table the
program made.

    T(c, g) = max(Th, Td) + rho * min(Th, Td)
    Th = host_work / phi_h(c),  Td = dev_work / phi_d(g)
    phi(p) = clip(1 - exp(-(p - p0) / tau), 0.05, 1)
"""

from __future__ import annotations

import hashlib

import numpy as np

#: sensitivity classes: CPU-bound, GPU-bound, both, insensitive
CLASS_CPU, CLASS_GPU, CLASS_BOTH, CLASS_NONE = "C", "G", "B", "N"

#: (suite, app, class) of Table 1
TABLE_1: tuple[tuple[str, str, str], ...] = (
    ("altis", "gemm", "C"), ("altis", "gups", "N"), ("altis", "maxflops", "C"),
    ("altis", "bfs", "C"), ("altis", "particlefilter_float", "G"),
    ("altis", "cfd_double", "B"), ("altis", "particlefilter_naive", "C"),
    ("altis", "raytracing", "G"), ("altis", "fdtd2d", "G"), ("altis", "nw", "B"),
    ("altis", "cfd", "C"), ("altis", "lavamd", "C"), ("altis", "sort", "C"),
    ("hecbench", "kalman", "C"), ("hecbench", "stencil3d", "C"),
    ("hecbench", "extrema", "B"), ("hecbench", "knn", "C"),
    ("hecbench", "dropout", "N"), ("hecbench", "aobench", "N"),
    ("hecbench", "zoom", "C"), ("hecbench", "convolution3D", "B"),
    ("hecbench", "softmax", "C"), ("hecbench", "chacha20", "N"),
    ("hecbench", "zmddft", "G"), ("hecbench", "residualLayerNorm", "B"),
    ("hecbench", "backgroundSubtract", "C"), ("mlperf", "UNet", "B"),
    ("mlperf", "BERT", "G"), ("mlperf", "ResNet50", "B"), ("ecp", "sw4lite", "C"),
    ("ecp", "XSBench", "B"), ("ecp", "Laghos", "N"), ("ecp", "miniGAN", "B"),
    ("hpc", "GROMACS", "C"), ("hpc", "LAMMPS", "C"), ("spec", "lbm", "G"),
    ("spec", "cloverleaf", "C"), ("spec", "tealeaf", "G"),
    ("spec", "minisweep", "N"), ("spec", "pot3d", "G"),
)

#: phi's lower clip
PHI_FLOOR = 0.05


def _stable_seed(*parts: str) -> int:
    h = hashlib.sha256("/".join(parts).encode()).digest()
    return int.from_bytes(h[:4], "little")


def _random_surface(rng, sclass: str, grid: dict, init_caps) -> dict:
    c_span = grid["cpu_max"] - grid["cpu_min"]
    g_span = grid["gpu_max"] - grid["gpu_min"]

    def sensitive(span, lo):
        p0 = lo - rng.uniform(0.1, 0.6) * span
        return float(p0), float(rng.uniform(0.30, 0.70) * span)

    def saturated(span, lo):
        p0 = lo - rng.uniform(2.0, 4.0) * span
        return float(p0), float(rng.uniform(0.5, 1.0) * span)

    rho = float(rng.uniform(0.02, 0.15))
    if sclass == CLASS_CPU:
        hw, dw = 1.0, float(rng.uniform(0.15, 0.5))
        ph = sensitive(c_span, grid["cpu_min"])
        pd = saturated(g_span, grid["gpu_min"])
        nat = (grid["cpu_max"] * 1.1, rng.uniform(0.4, 0.8) * grid["gpu_max"])
    elif sclass == CLASS_GPU:
        hw, dw = float(rng.uniform(0.15, 0.5)), 1.0
        ph = saturated(c_span, grid["cpu_min"])
        pd = sensitive(g_span, grid["gpu_min"])
        nat = (rng.uniform(0.4, 0.8) * grid["cpu_max"], grid["gpu_max"] * 1.1)
    elif sclass == CLASS_BOTH:
        hw, dw = 1.0, float(rng.uniform(0.8, 1.2))
        ph = sensitive(c_span, grid["cpu_min"])
        pd = sensitive(g_span, grid["gpu_min"])
        rho = float(rng.uniform(0.1, 0.35))
        nat = (grid["cpu_max"] * 1.1, grid["gpu_max"] * 1.1)
    else:
        hw, dw = 1.0, float(rng.uniform(0.5, 1.0))
        ph = saturated(c_span, grid["cpu_min"])
        pd = saturated(g_span, grid["gpu_min"])
        nat = (
            rng.uniform(0.3, 0.7) * init_caps[0],
            rng.uniform(0.3, 0.7) * init_caps[1],
        )
    return {
        "host_work": hw, "dev_work": dw,
        "phi_h": ph, "phi_d": pd, "rho": rho,
        "natural": (float(nat[0]), float(nat[1])),
    }


def paper_suite(system: dict) -> dict[str, dict]:
    """App name -> surface parameters, in Table-1 order.

    ``system``: the configuration's ``system`` block (``name``, ``grid``,
    ``suite_init_caps`` — the system's default caps, which set the
    insensitive class's natural draw)."""
    out = {}
    for suite, app, sclass in TABLE_1:
        name = f"{suite}.{app}"
        rng = np.random.default_rng(_stable_seed(system["name"], name))
        p = _random_surface(rng, sclass, system["grid"], system["suite_init_caps"])
        out[name] = {"sclass": sclass, **p}
    return out


def runtime(p: dict, c, g) -> np.ndarray:
    """Plain runtime model of one surface at caps ``(c, g)``."""

    def phi(curve, x):
        p0, tau = curve
        return np.clip(1.0 - np.exp(-(np.asarray(x, np.float64) - p0) / tau),
                       PHI_FLOOR, 1.0)

    th = p["host_work"] / phi(p["phi_h"], c)
    td = p["dev_work"] / phi(p["phi_d"], g)
    return np.maximum(th, td) + p["rho"] * np.minimum(th, td)
