"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData``.  Device planes are named
``/device:TPU:<n>``; their op line (``XLA Ops``) holds one event per
executed HLO op and their module line (``XLA Modules``) one per program
execution.  The benchmark's own host spans (``ingest``, ``run_round``,
``sync``, from ``jax.profiler.TraceAnnotation``) sit on a line of the
host plane (the main thread's), on the same clock.

``reduce_file`` returns, for the traced window (first span start to last
span end):

* ``busy_s``: union of the device-op intervals, averaged over devices;
* ``window_s``: the window's length;
* ``kernel_s``: summed device durations of the (max,+) kernels' events;
* ``pipeline_s``: summed device durations of the fused pipeline
  program's executions (module ``jit_run``: every op of it);
* ``device_ops``: the 10 op names with the most device self time (a
  ``while`` op's time less the ops of its body);
* ``idle_gaps``: device idle time split by the host span open during it.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

HOST_PLANE = "/host:CPU"
SPANS = ("ingest", "run_round", "sync")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
#: the (max,+) Pallas kernels: custom calls named after their Pallas
#: wrappers (``maxplus_stage_pallas_batched``, ``maxplus_conv_pallas_batched``)
KERNEL = re.compile(r"^%?maxplus\w*pallas\w*(\.\d+)? = .*custom-call\(")
#: the jitted fused pipeline (``mckp._fused_pipeline_fn``'s ``run``)
PIPELINE_MODULE = re.compile(r"^jit_run(\(|$)")


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge [start, end) intervals into disjoint sorted ones."""
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def op_name(event_name: str) -> str:
    """``%fusion.63 = f32[..] fusion(..)`` -> ``fusion.63``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _self_times(events) -> dict[str, float]:
    """Per op name, device time not covered by an op nested inside it
    (a ``while`` op holds its body's ops)."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, name, child time]
    for e in evs:
        while stack and stack[-1][0] <= e.start_ns:
            end, nm, child = stack.pop()
            out[nm] = out.get(nm, 0.0) - child
        if stack:
            stack[-1][2] += e.duration_ns
        nm = op_name(e.name)
        out[nm] = out.get(nm, 0.0) + e.duration_ns
        stack.append([e.end_ns, nm, 0.0])
    for _end, nm, child in stack:
        out[nm] = out.get(nm, 0.0) - child
    return out


def _idle_by_span(gaps: np.ndarray, spans: list, span_lo: np.ndarray) -> dict:
    """Idle nanoseconds per host span label; idle time under no span is
    ``between_rounds``.  ``spans`` are sorted and do not overlap."""
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        if g1 <= g0:
            continue
        covered = 0.0
        j = max(0, int(np.searchsorted(span_lo, g0, side="right")) - 1)
        while j < len(spans) and spans[j][0] < g1:
            s0, s1, label = spans[j]
            t = min(s1, g1) - max(s0, g0)
            if t > 0:
                out[label] = out.get(label, 0.0) + t
                covered += t
            j += 1
        if g1 - g0 - covered > 0:
            out["between_rounds"] = out.get("between_rounds", 0.0) + (g1 - g0 - covered)
    return out


def reduce_file(path: str, device_prefix: str = "/device:TPU") -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path) if not path.endswith(".gz") else \
        ProfileData.from_serialized_xspace(_gunzip(path))
    return reduce_profile(pd, device_prefix)


def _gunzip(path: str) -> bytes:
    import gzip

    with gzip.open(path, "rb") as f:
        return f.read()


def reduce_profile(pd, device_prefix: str = "/device:TPU") -> dict:
    host_spans = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            host_spans += [
                (e.start_ns, e.end_ns, e.name) for e in line.events if e.name in SPANS
            ]
    host_spans.sort()
    if not host_spans:
        return {}
    lo, hi = host_spans[0][0], max(e for _s, e, _n in host_spans)
    window_ns = hi - lo
    span_lo = np.array([s for s, _e, _n in host_spans])

    busy, kernel, pipeline = [], [], []
    op_time: dict[str, float] = {}
    gaps_by: dict[str, float] = {}
    for plane in pd.planes:
        if not plane.name.startswith(device_prefix):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OP_LINE not in lines:
            continue
        ops = [e for e in lines[OP_LINE].events if e.end_ns > lo and e.start_ns < hi]
        iv = np.array([(max(e.start_ns, lo), min(e.end_ns, hi)) for e in ops],
                      dtype=np.float64).reshape(-1, 2)
        iv = _union(iv[iv[:, 1] > iv[:, 0]])
        busy.append(float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0)
        kernel.append(sum(e.duration_ns for e in ops if KERNEL.match(e.name)))
        for nm, t in _self_times(ops).items():
            op_time[nm] = op_time.get(nm, 0.0) + t
        mods = lines[MODULE_LINE].events if MODULE_LINE in lines else []
        pipeline.append(sum(
            e.duration_ns for e in mods
            if PIPELINE_MODULE.match(e.name) and e.end_ns > lo and e.start_ns < hi
        ))
        # idle time inside the window, split by the host span open during it
        edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
        for label, t in _idle_by_span(edges, host_spans, span_lo).items():
            gaps_by[label] = gaps_by.get(label, 0.0) + t
    n_dev = len(busy)
    if not n_dev:
        return {"window_s": window_ns * 1e-9}
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": float(np.mean(busy)) * 1e-9,
        "window_s": window_ns * 1e-9,
        "kernel_s": float(np.mean(kernel)) * 1e-9,
        "pipeline_s": float(np.mean(pipeline)) * 1e-9,
        "devices": n_dev,
        "device_ops": [[k, v * 1e-9 / n_dev] for k, v in top],
        "idle_gaps": [
            [k, float(v) * 1e-9 / n_dev]
            for k, v in sorted(gaps_by.items(), key=lambda kv: -kv[1])[:10]
        ],
    }


def reduce_dir(trace_dir: str, kind_filter: str = "TPU") -> dict | None:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        return None
    return reduce_file(paths[-1], device_prefix=f"/device:{kind_filter}")


def describe(path: str) -> str:
    """A by-hand view of a trace: planes, lines, event counts and the
    most frequent event names of each line."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names: dict[str, list] = {}
            for e in evs:
                names.setdefault(e.name, []).append(e.duration_ns)
            top = sorted(names.items(), key=lambda kv: -sum(kv[1]))[:12]
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for nm, d in top:
                out.append(f"    {len(d):6d} x {sum(d) / 1e6:10.3f} ms  {nm[:120]}")
            if evs:
                e = evs[0]
                out.append(f"    first: {e.name[:80]} start={e.start_ns} "
                           f"stats={[(k, str(v)[:60]) for k, v in e.stats][:6]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    for d in sys.argv[1:]:
        for p in sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)):
            print(p, os.path.getsize(p))
            print(describe(p))
