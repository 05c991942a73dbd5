"""The trace by the program's own spans and device scopes.

An addition to ``bench/trace.py``'s reduction, over the same window
(first to last of the benchmark's own spans ``ingest``/``run_round``/
``sync``) and the same device planes.  The program (``repro.core.spans``)
opens ``jax.profiler.TraceAnnotation`` spans named ``engine.*``,
``controller.*`` and ``fused.*`` inside ``run_round``, and its fused
pipeline labels its ops with ``jax.named_scope`` (``leaf_scan``,
``option_scatter``, ``stage_mask``, ``frontier_wave<i>``, ``root_argmax``,
``tree_backtrack``, ``leaf_backtrack``).

``reduce_profile`` returns:

* ``idle_by_span``: device idle seconds per innermost open host span —
  a program span where one is open, else the benchmark's span, else
  ``between_rounds`` — with every innermost span of the window listed,
  at 0.0 where the device never idled under it;
* ``idle_gaps``: its 10 largest, the form of ``trace.py``'s
  ``idle_gaps`` refined by the program's spans (the totals per benchmark
  span are the same);
* ``self_s``: host self seconds per span in the window (its time less
  the spans open inside it);
* ``scope_s``: device self seconds (a ``while`` op less its body's ops)
  per innermost named scope.  An op's scope comes from its HLO
  ``op_name`` (``jit(run)/leaf_scan/.../option_scatter/scatter-max``),
  which the trace keeps in the device plane's event metadata as the
  ``tf_op`` stat; an op in no named scope counts under its program
  (``jit(run)``, ``jit(patch)``).

``jax.profiler.ProfileData`` does not expose event metadata stats, and
no ``xplane_pb2`` module is installed, so ``op_paths`` reads them from
the serialized ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``) with a
plain protobuf wire decoder, skipping every line of events unread.

The harness hands a per-layer reader the window and ``trace.py``'s
reduction only; ``for_window`` finds the raw trace of the run that
called the reader through that ``run_cell`` call's ``trace_dir`` and
reduces it once, and raises where a window with device time has none.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import sys

import numpy as np

from bench import trace

#: the program's span names begin with their layer
PROGRAM_PREFIXES = ("engine.", "controller.", "fused.")
#: the fused pipeline's named device scopes
SCOPE = re.compile(
    r"^(leaf_scan|option_scatter|stage_mask|frontier_wave\d+|root_argmax"
    r"|tree_backtrack|leaf_backtrack)$"
)

_Op = collections.namedtuple("_Op", "name start_ns duration_ns end_ns")


def innermost(spans: list) -> list:
    """Nested ``(start, end, name)`` spans -> disjoint, sorted
    ``(start, end, name)`` segments, each named by the innermost span
    open over it; time under no span is left out."""
    out = []
    stack: list[tuple] = []  # (end, name) of the open spans
    t = None
    for s, e, nm in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            if t < end:
                out.append((t, end, name))
                t = end
        if stack and t < s:
            out.append((t, s, stack[-1][1]))
        stack.append((e, nm))
        t = s
    while stack:
        end, name = stack.pop()
        if t < end:
            out.append((t, end, name))
            t = end
    return out


def scope_of(op_path: str) -> str:
    """``jit(run)/leaf_scan/while/body/.../option_scatter/scatter-max`` ->
    ``option_scatter``: the innermost named scope, else the program."""
    parts = op_path.split("/")
    for part in reversed(parts[:-1]):
        if SCOPE.match(part):
            return part
    return parts[0]


# -- the protobuf wire format, as far as XSpace needs it ----------------------


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of one message: an int for varints, a
    (start, end) slice for length-delimited fields; fixed-width fields
    are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _map_values(buf, entry):
    """The value (field 2) of one map entry."""
    for f, v in _fields(buf, *entry):
        if f == 2:
            return v
    return None


def op_paths(raw: bytes, device_prefix: str = "/device:TPU") -> dict[str, str]:
    """Event name -> HLO op path (``tf_op``) of every op of the device
    planes of a serialized ``XSpace``."""
    buf = memoryview(raw)
    out: dict[str, str] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:  # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(buf, *plane):
            if pf == 2:  # XPlane.name
                name = bytes(buf[pv[0]:pv[1]]).decode()
            elif pf == 4:  # XPlane.event_metadata
                events.append(_map_values(buf, pv))
            elif pf == 5:  # XPlane.stat_metadata: id 1, name 2
                v = _map_values(buf, pv)
                sm = dict(_fields(buf, *v)) if v else {}
                if 2 in sm:
                    stat_names[sm.get(1, 0)] = bytes(buf[slice(*sm[2])]).decode()
        if not name.startswith(device_prefix):
            continue
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        if not tf_op:
            continue
        for ev in events:
            if ev is None:
                continue
            ev_name, path = None, None
            for ef, evv in _fields(buf, *ev):
                if ef == 2:  # XEventMetadata.name
                    ev_name = bytes(buf[evv[0]:evv[1]]).decode()
                elif ef == 5:  # XEventMetadata.stats
                    st = dict(_fields(buf, *evv))
                    if st.get(1) != tf_op[0]:
                        continue
                    if 5 in st:  # str_value
                        path = bytes(buf[slice(*st[5])]).decode()
                    elif 7 in st:  # ref_value: a stat metadata's name
                        path = stat_names.get(st[7])
            if ev_name and path:
                out[ev_name] = path.rsplit(":", 1)[0]
    return out


# -- the reduction --------------------------------------------------------------


def reduce_profile(pd, raw: bytes, device_prefix: str = "/device:TPU") -> dict:
    bench_spans, spans = [], []
    for plane in pd.planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in trace.SPANS:
                    bench_spans.append((e.start_ns, e.end_ns))
                elif not e.name.startswith(PROGRAM_PREFIXES):
                    continue
                spans.append((e.start_ns, e.end_ns, e.name))
    if not bench_spans:
        return {}
    # the window of bench/trace.py: the benchmark's own spans only
    lo = min(s for s, _e in bench_spans)
    hi = max(e for _s, e in bench_spans)
    segs = [
        (max(s, lo), min(e, hi), nm) for s, e, nm in innermost(spans)
        if e > lo and s < hi
    ]
    seg_lo = np.array([s for s, _e, _n in segs])
    self_s: dict[str, float] = {}
    for s, e, nm in segs:
        self_s[nm] = self_s.get(nm, 0.0) + (e - s) * 1e-9

    paths = op_paths(raw, device_prefix)
    idle_ns = {nm: 0.0 for nm in self_s}
    scope_ns: dict[str, float] = {}
    n_dev = 0
    for plane in pd.planes:
        if not plane.name.startswith(device_prefix):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if trace.OP_LINE not in lines:
            continue
        n_dev += 1
        ops = [e for e in lines[trace.OP_LINE].events if e.end_ns > lo and e.start_ns < hi]
        iv = np.array([(max(e.start_ns, lo), min(e.end_ns, hi)) for e in ops],
                      dtype=np.float64).reshape(-1, 2)
        iv = trace._union(iv[iv[:, 1] > iv[:, 0]])
        edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
        for nm, t in trace._idle_by_span(edges, segs, seg_lo).items():
            idle_ns[nm] = idle_ns.get(nm, 0.0) + t
        # trace._self_times keys by the text before " = ": name each op
        # by its scope, so the self times come out per scope
        scoped = [
            _Op(f"{scope_of(paths.get(e.name, 'other'))} = ", e.start_ns,
                e.duration_ns, e.end_ns)
            for e in ops
        ]
        for nm, t in trace._self_times(scoped).items():
            scope_ns[nm] = scope_ns.get(nm, 0.0) + t
    if not n_dev:
        return {}
    idle = {k: float(v) * 1e-9 / n_dev for k, v in idle_ns.items()}
    return {
        "idle_by_span": idle,
        "idle_gaps": sorted(
            ([k, v] for k, v in idle.items() if v > 0), key=lambda kv: -kv[1]
        )[:10],
        "self_s": self_s,
        "scope_s": {k: float(v) * 1e-9 / n_dev for k, v in scope_ns.items()},
    }


def reduce_file(path: str, device_prefix: str = "/device:TPU") -> dict:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        raw = trace._gunzip(path)
    else:
        with open(path, "rb") as f:
            raw = f.read()
    return reduce_profile(ProfileData.from_serialized_xspace(raw), raw, device_prefix)


_reduced: dict = {}


def _run_trace_dir() -> str | None:
    """``trace_dir`` of the ``run_cell`` call that is reading its metrics."""
    frame = sys._getframe(1)
    while frame is not None:
        if frame.f_code.co_name == "run_cell":
            d = frame.f_locals.get("trace_dir")
            return d if isinstance(d, str) else None
        frame = frame.f_back
    return None


def for_window(win) -> dict | None:
    """This run's trace reduced by spans and scopes (once per trace), or
    None where the run has no TPU trace.  A window with device time
    whose raw trace cannot be found raises: the readers' yardstick is
    lost, and a run that silently left their metrics out would hide it."""
    if not win.trace or not win.trace.get("busy_s"):
        return None
    d = _run_trace_dir()
    if d is None:
        raise RuntimeError(
            "spantrace: the window has device time, but no run_cell call with a "
            "trace_dir is reading its metrics"
        )
    paths = sorted(
        glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        raise RuntimeError(f"spantrace: no .xplane.pb under the run's trace_dir {d}")
    if paths[-1] not in _reduced:
        _reduced.clear()
        _reduced[paths[-1]] = reduce_file(paths[-1])
    return _reduced[paths[-1]] or None
