"""One benchmark run: build a cell, warm it up, time a window, check it.

``run_cell`` is the whole run after the command line and the device
check; ``bench/run.py`` is the command.  Everything that belongs to one
configuration, traffic mix or per-layer metric is data or a reader that
this module finds by name: ``BENCHMARK.json`` names the configuration's
file, ``bench/traffic/<traffic>.json`` the mix, ``bench/limits/<cell>.json``
the limits of the comparison and ``bench/metrics/<metric>.py`` each
per-layer reader.

A round is one closed-loop control period: the mix's events for the
round (``ClusterSim.apply_events`` + ``ctrl.invalidate``), then
``ClusterSim.run_round`` under the round's budget, then a
``block_until_ready`` on the fused controller's resident banks.  The
next round starts when the last one's caps are out.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import json
import os
import sys
import time

import numpy as np

from bench import deploy, reference, roofline
from bench.cluster import BENCH_DIR, Deployment, initial_state, load_json
from bench.traffic import STREAM_POPULATION, STREAM_SAMPLE, Traffic, rng_for

ROOT = os.path.dirname(BENCH_DIR)

#: warm-up ends after this many consecutive rounds that needed no new
#: executable (compiled or read from the persistent cache) ...
QUIET_ROUNDS = 5
#: ... and never before this many rounds
MIN_WARMUP_ROUNDS = 8
#: churn scale of the opening warm-up rounds: the first builds the
#: resident banks, the rest step down by sqrt(2) so each pow2 scatter
#: tier that a round of the mix can need is met (see ``run_cell``)
WARMUP_BURSTS = (1.0, 2.0, 1.41, 1.0, 0.71, 0.5, 0.35)
#: a cell that still compiles after this many rounds is refused
MAX_WARMUP_ROUNDS = 300
#: rounds drawn from the seed (reservoir over the window) for the check,
#: besides the window's last and slowest rounds
SAMPLE_ROUNDS = 4
#: the numbers the comparison reports, in order
CHECKS = ("value_err_rel", "overdraw_w", "receiver_mismatch", "off_grid")


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_inputs(bench: dict, workload: str, root: str = ROOT) -> tuple[dict, dict, dict, dict]:
    """(cell, configuration, mix, limits) of a workload, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    mix = load_json(f"traffic/{cell['traffic']}.json")
    limits = load_json(f"limits/{workload}.json")
    return cell, config, mix, limits


class CompileCounter:
    """Executables the process needed (``n``: one backend-compile event
    each, compiled or read from the persistent cache) and how many of
    them the cache held (``hits``), through ``jax.monitoring``."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.n = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        self.n += event == self.COMPILE

    def _on_event(self, event: str, **_kw) -> None:
        self.hits += event == self.HIT


@dataclasses.dataclass
class Sample:
    """One round kept for the comparison: its inputs and the caps out."""

    round: int
    budget: float
    state: object
    caps: dict
    reported: float  # the allocation's summed predicted improvement
    wall_s: float


@dataclasses.dataclass
class Window:
    """What per-layer readers read: the traced window's rounds."""

    rounds: int
    wall_s: list
    spans: list  # per round: {"ingest": s, "run_round": s, "sync": s}
    profiles: list  # per round: sim.last_round_profile
    segments: list  # per round: fused segments ({} when not fused)
    dp_sizes: list  # per round: roofline.dp_sizes (None when not fused)
    fused_rounds: int
    compiles: int
    trace: dict | None
    peaks: dict | None


class GcClock:
    """Seconds and count of Python's garbage collections by generation,
    through ``gc.callbacks``, to place host stalls in the window."""

    def __init__(self):
        self.s = [0.0, 0.0, 0.0]
        self.n = [0, 0, 0]
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            g = int(info["generation"])
            self.s[g] += time.perf_counter() - self._t0
            self.n[g] += 1
            self._t0 = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def _sync(ctrl) -> None:
    import jax

    fstate = getattr(ctrl, "_fused_state", None)
    if fstate is None:
        return
    for buf in (fstate.kb_dev, fstate.vb_dev):
        if buf is not None:
            jax.block_until_ready(buf)


class Cell:
    """A built cell: deployment, traffic, the program's sim and controller."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.dep = Deployment(config)
        state = initial_state(self.dep, rng_for(seed, STREAM_POPULATION))
        self.dep.set_domain_caps(state)
        self.traffic = Traffic(self.dep, mix, seed, state)
        self.sim, self.ctrl, self.by_name = deploy.build(self.dep, state, seed)
        self.leaf_names = deploy.leaf_names(self.dep)

    def round(self, spans: dict | None = None, annotate=None, budget=None, burst=1.0):
        """One closed-loop round; fills ``spans`` with its host spans.
        ``budget`` overrides the mix's budget and ``burst`` scales its
        churn (warm-up only)."""
        ann = annotate or (lambda _name: contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann("ingest"):
            r, mix_budget, events = self.traffic.next_round(burst)
            budget = mix_budget if budget is None else budget
            if events:
                touched = self.sim.apply_events(deploy.program_events(
                    events, r, self.by_name, self.dep, self.leaf_names
                ))
                self.ctrl.invalidate(touched)
        t1 = time.perf_counter()
        with ann("run_round"):
            res = self.sim.run_round(self.ctrl, budget=budget, round_index=r)
        t2 = time.perf_counter()
        with ann("sync"):
            _sync(self.ctrl)
        t3 = time.perf_counter()
        if spans is not None:
            spans.update(ingest=t1 - t0, run_round=t2 - t1, sync=t3 - t2)
        return r, budget, res, t3 - t0


def run_cell(
    config: dict, mix: dict, limits: dict, *, seed: int, seconds: float,
    trace: bool, t_start: float, trace_dir: str | None = None,
    per_layer: list | None = None, fault=None,
) -> tuple[dict, list[str]]:
    """Build, warm up, measure and check one cell.

    Returns the result line's object and the check lines for standard
    error.  ``fault`` (tests only) wraps the built cell to break the
    timed path underneath."""
    import jax

    counter = CompileCounter()
    t_build = time.perf_counter()
    cell = Cell(config, mix, seed)
    if fault is not None:
        fault(cell)
    t_warm = time.perf_counter()

    # the fused round's padded tiers only grow, and the largest budget
    # sets the widest root grid; the rows a round changes pick the pow2
    # scatter tier of its bank patch.  Open the warm-up at the envelope's
    # top with churn scaled through every tier around the mix's own, so
    # the window never meets a new shape
    for burst in WARMUP_BURSTS:
        cell.round(budget=cell.dep.envelope[1], burst=burst)
    quiet = 0
    n_warm = 0
    while n_warm < MIN_WARMUP_ROUNDS or quiet < QUIET_ROUNDS:
        before = counter.n
        cell.round()
        n_warm += 1
        quiet = quiet + 1 if counter.n == before else 0
        if n_warm >= MAX_WARMUP_ROUNDS:
            raise RuntimeError(f"still compiling after {n_warm} warm-up rounds")

    setup_s = time.perf_counter() - t_start
    setup_note = (
        f"setup: before_build_s={t_build - t_start:.3f} build_s={t_warm - t_build:.3f} "
        f"warmup_s={time.perf_counter() - t_warm:.3f} warmup_rounds={n_warm + len(WARMUP_BURSTS)} "
        f"executables={counter.n} cache_hits={counter.hits}"
    )
    devices = jax.devices()[: int(config.get("chips", 1))]
    kind = devices[0].device_kind
    sample_rng = rng_for(seed, STREAM_SAMPLE)
    reservoir: list[Sample] = []
    slowest: Sample | None = None
    last: Sample | None = None
    wall, spans, profiles, segments, sizes = [], [], [], [], []
    fused0 = cell.ctrl.fused_stats().rounds
    c0 = counter.n
    annotate = None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    gclock = GcClock()
    slow_spans: dict = {}
    t_w0 = time.perf_counter()
    n = 0
    while True:
        sp: dict = {}
        r, budget, res, dt = cell.round(sp, annotate)
        n += 1
        wall.append(dt)
        if trace:
            spans.append(sp)
            profiles.append(dict(cell.sim.last_round_profile))
            fused = cell.ctrl.last_solver == "fused"
            segments.append(cell.ctrl.fused_segments() if fused else {})
            sizes.append(roofline.dp_sizes(cell.ctrl._fused_state) if fused else None)
        alloc = res.allocation
        caps, reported = alloc.caps, alloc.predicted_improvement * len(alloc.caps)
        # reservoir sampling: every window round equally likely to be kept
        filling = len(reservoir) < SAMPLE_ROUNDS
        slot = len(reservoir) if filling else int(sample_rng.integers(n))
        newmax = slowest is None or dt > slowest.wall_s
        if slot < SAMPLE_ROUNDS or newmax:
            s = Sample(r, budget, cell.traffic.state.copy(), caps, reported, dt)
            if filling:
                reservoir.append(s)
            elif slot < SAMPLE_ROUNDS:
                reservoir[slot] = s
            if newmax:
                slowest = s
                slow_spans = {k: round(1e3 * v, 3) for k, v in sp.items()}
                slow_spans.update(
                    (k, round(1e3 * v, 3)) for k, v in cell.sim.last_round_profile.items()
                    if k.endswith("_s") and isinstance(v, float)
                )
        last = (r, budget, caps, reported, dt)
        if time.perf_counter() - t_w0 >= seconds:
            break
    t_w1 = time.perf_counter()
    gclock.close()
    if trace:
        jax.profiler.stop_trace()
    compiles = counter.n - c0
    fused_rounds = cell.ctrl.fused_stats().rounds - fused0
    last_s = Sample(last[0], last[1], cell.traffic.state.copy(), *last[2:])
    mem = 0
    for d in devices:
        st = d.memory_stats() or {}
        mem = max(mem, int(st.get("peak_bytes_in_use", 0)))

    # free the program's state before the reference runs
    del cell, res, alloc, caps
    gc.collect()

    window_s = t_w1 - t_w0
    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": mem,
    }
    out: dict = {"correct": False, "attempted": n, "failed": 0}
    if not trace:
        out["metrics"] = {
            "round_ms": {"value": 1e3 * window_s / n, "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    else:
        from bench import trace as trace_mod

        reduced = trace_mod.reduce_dir(trace_dir) if trace_dir else None
        pk = roofline.peaks(kind) if devices[0].platform == "tpu" else None
        win = Window(
            rounds=n, wall_s=wall, spans=spans,
            profiles=profiles, segments=segments, dp_sizes=sizes,
            fused_rounds=fused_rounds, compiles=compiles, trace=reduced, peaks=pk,
        )
        out["metrics"] = read_per_layer(win, per_layer or [])
        if reduced is not None and reduced.get("busy_s"):
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
    out["device"] = device

    # the comparison, on the sampled rounds, after the window
    dep = Deployment(config)
    dep.set_domain_caps(initial_state(dep, rng_for(seed, STREAM_POPULATION)))
    curves = reference.option_curves(dep)
    worst = {k: 0.0 for k in CHECKS}
    failed = 0
    seen = set()
    for s in [*reservoir, slowest, last_s]:
        if s.round in seen:
            continue
        seen.add(s.round)
        got = reference.check_round(
            reference.Round(dep, s.state, s.budget), curves, s.caps, s.reported
        )
        bad = any(got[k] > limits[k] for k in CHECKS)
        failed += bad
        for k in CHECKS:
            worst[k] = max(worst[k], got[k])
    out["failed"] = failed
    out["correct"] = failed == 0 and bool(seen)
    out["checks"] = {k: {"value": worst[k], "limit": limits[k]} for k in CHECKS}
    deciles = [
        round(1e3 * float(np.mean(wall[i * n // 10:(i + 1) * n // 10])), 3)
        for i in range(10)
    ] if n >= 10 else []
    window_note = (
        f"window: rounds={n} seconds={window_s:.3f} executables={compiles} "
        f"round_ms_by_tenth={deciles} gc_s_by_generation={[round(x, 4) for x in gclock.s]} "
        f"gc_count={gclock.n} slowest_round_ms={slow_spans}"
    )
    lines = [setup_note, window_note] + [
        f"check {k}: {worst[k]!r} (limit {limits[k]!r})" for k in CHECKS
    ]
    return out, lines


def read_per_layer(win: Window, per_layer: list) -> dict:
    """Each per-layer metric through its own reader; a reader that finds
    nothing to read leaves its metric out."""
    out = {}
    for m in per_layer:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        v = mod.read(win)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def setup_process(chips: int):
    """Pin the program's leaf shards to the cell's chips, put the
    persistent compile cache in the checkout (every executable, however
    small) and bring up JAX; returns the ``jax`` module."""
    os.environ["REPRO_FUSED_SHARDS"] = str(chips)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.kernels import ops

    ops.use_compile_cache()
    return jax


def main(argv: list[str] | None, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="EcoShift control-round benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cell, config, mix, limits = cell_inputs(bench, args.workload)
    chips = int(cell["chips"])
    jax = setup_process(chips)
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"bench: no TPU (JAX backend {backend!r})", file=sys.stderr)
        return 2
    if jax.device_count() < chips:
        print(f"bench: cell needs {chips} chips, JAX sees {jax.device_count()}",
              file=sys.stderr)
        return 2

    import tempfile

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        out, lines = run_cell(
            config, mix, limits, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), t_start=t_start,
            trace_dir=tdir if args.trace else None, per_layer=bench["per_layer"],
        )
    print(json.dumps(out), flush=True)
    for ln in lines:
        print(ln, file=sys.stderr)
    return 0
