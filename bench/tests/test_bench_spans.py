"""The trace by the program's spans and device scopes (``bench/spantrace.py``), on the CPU.

Synthetic intervals for the idle attribution, the recorded traces of
``data/`` for the op metadata and the pinned reduction, and a CPU
profiler trace of the test-only ``tiny_rack4`` cell for the spans'
nesting on the host plane."""

import collections
import glob
import os
import time

import pytest

from bench import harness, spantrace, trace
from bench.cluster import BENCH_DIR, load_json
from bench.metrics import controller_self_ms, dispatch_idle_ms, frontier_ms, option_scatter_ms
from bench.tests import mix_json

DATA = os.path.join(BENCH_DIR, "tests", "data")
OLD_TRACE = os.path.join(DATA, "rack16_drift_12rounds.xplane.pb.gz")
#: a ``--trace 1`` run of ``sys2_rack16_drift`` on one TPU v5e with a
#: 0.2 s window (10 rounds), with the program's spans and scopes
SPANS_TRACE = os.path.join(DATA, "sys2_rack16_drift_spans.xplane.pb.gz")

#: its reduction (the spans' and scopes' seconds)
PIN = {
    "window_s": 0.216730284,
    "top_idle": ["controller.allocation", "fused.backtrack", "engine.measure", "fused.assembly"],
    "idle_by_span": {
        "controller.allocation": 0.02137266, "fused.launch": 0.009463265,
        "fused.wait": 0.01065636, "run_round": 0.000183468, "engine.round": 0.000262295,
    },
    "scope_s": {
        "option_scatter": 0.039022955, "leaf_scan": 0.033360375,
        "frontier_wave3": 0.004951462, "leaf_backtrack": 0.000337007,
    },
}

Ev = collections.namedtuple("Ev", "name start_ns end_ns duration_ns")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")
Profile = collections.namedtuple("Profile", "planes")

#: one round: the benchmark's spans with the program's nested inside
SPANS = [
    (0, 10, "ingest"), (2, 8, "engine.apply_events"), (10, 100, "run_round"),
    (12, 98, "engine.round"), (14, 30, "engine.allocate"),
    (16, 20, "controller.cache_key"), (20, 50, "fused.launch"),
    (50, 80, "fused.wait"), (100, 105, "sync"),
]


def _ev(s, e, name):
    return Ev(name, s, e, e - s)


def _profile(spans, ops):
    host = Plane(trace.HOST_PLANE, [Line("python3", [_ev(*x) for x in spans])])
    dev = Plane("/device:TPU:0", [Line(trace.OP_LINE, [_ev(*x) for x in ops])])
    return Profile([host, dev])


def test_innermost_names_each_instant_by_the_innermost_open_span():
    got = spantrace.innermost([(0, 10, "a"), (2, 6, "b"), (3, 4, "c"), (10, 12, "d")])
    assert got == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "b"), (6, 10, "a"), (10, 12, "d"),
    ]


def test_idle_split_by_innermost_span_keeps_the_benchmark_span_totals():
    # device busy [0, 5), [15, 45), [55, 85): idle 5-15, 45-55, 85-105
    ops = [(0, 5, "%fusion.1 = f32[] fusion()"), (15, 45, "%fusion.2 = f32[] fusion()"),
           (55, 85, "%while.3 = () while()")]
    pd = _profile(SPANS, ops)
    got = spantrace.reduce_profile(pd, b"")
    idle = {k: round(v * 1e9, 6) for k, v in got["idle_by_span"].items()}
    assert idle == {
        "ingest": 2.0, "engine.apply_events": 3.0, "run_round": 4.0,
        "engine.round": 15.0, "engine.allocate": 1.0, "controller.cache_key": 0.0,
        "fused.launch": 5.0, "fused.wait": 5.0, "sync": 5.0,
    }
    # the benchmark's own reduction of the same trace: the program spans
    # refine its labels and leave its totals and window as they were
    old = dict(trace.reduce_profile(pd)["idle_gaps"])
    top = {"engine.apply_events": "ingest"}
    by_top: dict = {}
    for k, v in got["idle_by_span"].items():
        k = top.get(k, "run_round" if k.startswith(spantrace.PROGRAM_PREFIXES) else k)
        by_top[k] = by_top.get(k, 0.0) + v
    assert {k: v for k, v in by_top.items() if v} == pytest.approx(old, abs=1e-15)
    assert got["idle_gaps"][0] == ["engine.round", pytest.approx(15e-9)]
    assert "controller.cache_key" not in dict(got["idle_gaps"])
    assert sum(got["self_s"].values()) == pytest.approx(105e-9)
    assert got["self_s"]["fused.wait"] == pytest.approx(30e-9)
    # no op metadata: the ops count under "other", a while op less its body
    assert got["scope_s"] == {"other": pytest.approx(65e-9)}


def test_scope_of_an_op_path():
    p = "jit(run)/leaf_scan/while/body/closed_call/jit(maxplus_stage_pallas_batched)/"
    assert spantrace.scope_of(p + "option_scatter/scatter-max") == "option_scatter"
    assert spantrace.scope_of(p + "pallas_call") == "leaf_scan"
    assert spantrace.scope_of("jit(run)/frontier_wave3/jit(_where)/select_n") == "frontier_wave3"
    assert spantrace.scope_of("jit(run)/concatenate") == "jit(run)"
    assert spantrace.scope_of("jit(patch)/scatter") == "jit(patch)"


def test_op_paths_of_the_recorded_trace():
    raw = trace._gunzip(OLD_TRACE)
    paths = spantrace.op_paths(raw)
    kernel = [p for n, p in paths.items() if n.startswith("%maxplus_stage_pallas_batched.4 = ")]
    assert kernel == [
        "jit(run)/while/body/closed_call/jit(maxplus_stage_pallas_batched)/pallas_call"
    ]
    assert all(p.startswith("jit(") for p in paths.values())
    assert spantrace.op_paths(raw, device_prefix="/device:GPU") == {}


def test_recorded_trace_without_program_spans_reads_as_the_benchmark_did():
    """The first version's trace has no program span or scope: the idle
    split is ``trace.py``'s, and every device op counts under a program."""
    got = spantrace.reduce_file(OLD_TRACE)
    old = trace.reduce_file(OLD_TRACE)
    assert got["idle_gaps"] == old["idle_gaps"]
    assert sum(got["scope_s"].values()) == pytest.approx(old["busy_s"], abs=1e-8)
    assert set(got["scope_s"]) == {"jit(run)", "other"}


@pytest.fixture(scope="module")
def spans_trace():
    return spantrace.reduce_file(SPANS_TRACE), trace.reduce_file(SPANS_TRACE)


def test_recorded_trace_with_program_spans(spans_trace):
    got, old = spans_trace
    assert old["window_s"] == pytest.approx(PIN["window_s"], abs=1e-9)
    assert [k for k, _ in got["idle_gaps"][:4]] == PIN["top_idle"]
    for k, v in PIN["idle_by_span"].items():
        assert got["idle_by_span"][k] == pytest.approx(v, abs=1e-9), k
    # the program's spans refine trace.py's labels: the idle time per
    # benchmark span and in all is what trace.py reads
    idle = old["window_s"] - old["busy_s"]
    assert sum(got["idle_by_span"].values()) == pytest.approx(idle, abs=1e-8)
    in_round = [k for k in got["idle_by_span"] if k == "run_round" or (
        k.startswith(spantrace.PROGRAM_PREFIXES) and k != "engine.apply_events")]
    inside = sum(got["idle_by_span"][k] for k in in_round)
    assert inside == pytest.approx(dict(old["idle_gaps"])["run_round"], abs=1e-8)
    # bare run_round and engine.round's own time hold little of it
    assert got["idle_by_span"]["run_round"] + got["idle_by_span"]["engine.round"] < 0.01 * inside


def test_recorded_trace_scopes(spans_trace):
    got, old = spans_trace
    for k, v in PIN["scope_s"].items():
        assert got["scope_s"][k] == pytest.approx(v, abs=1e-9), k
    assert {f"frontier_wave{i}" for i in range(4)} <= set(got["scope_s"])
    assert sum(got["scope_s"].values()) == pytest.approx(old["busy_s"], abs=1e-8)


class _Win:
    rounds = 10


@pytest.mark.parametrize("red,want", [
    ({"scope_s": {"option_scatter": 0.05, "frontier_wave0": 0.004, "frontier_wave1": 0.006,
                  "leaf_scan": 0.03},
      "idle_by_span": {"fused.launch": 0.012, "fused.wait": 0.008, "engine.measure": 0.04},
      "self_s": {"controller.cache_key": 0.01, "controller.allocation": 0.02, "fused.prep": 0.1}},
     {"option_scatter_ms": 5.0, "frontier_ms": 1.0, "dispatch_idle_ms": 2.0,
      "controller_self_ms": 3.0}),
    # the parent program: no program span, no named scope
    ({"scope_s": {"jit(run)": 0.1}, "idle_by_span": {"run_round": 0.2},
      "self_s": {"run_round": 0.3}},
     {"option_scatter_ms": None, "frontier_ms": None, "dispatch_idle_ms": None,
      "controller_self_ms": None}),
])
def test_readers(monkeypatch, red, want):
    monkeypatch.setattr(spantrace, "for_window", lambda win: red)
    readers = {"option_scatter_ms": option_scatter_ms, "frontier_ms": frontier_ms,
               "dispatch_idle_ms": dispatch_idle_ms, "controller_self_ms": controller_self_ms}
    got = {k: m.read(_Win()) for k, m in readers.items()}
    assert got == {k: (None if v is None else pytest.approx(v)) for k, v in want.items()}


def test_program_spans_nest_under_run_round_on_the_host_plane(tmp_path):
    """A CPU profiler trace of two rounds of ``tiny_rack4``: each round's
    ``engine.round`` (with its round index) sits inside the benchmark's
    ``run_round`` span, and the controller's and fused round's spans
    inside ``engine.allocate``."""
    import jax
    from jax.profiler import ProfileData

    cell = harness.Cell(load_json("tests/data/tiny_rack4.json"), mix_json("drift"), 2**31 + 7)
    for _ in range(3):
        cell.round()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    rounds = [cell.round({}, jax.profiler.TraceAnnotation)[0] for _ in range(2)]
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [
        e for plane in ProfileData.from_file(path).planes if plane.name == trace.HOST_PLANE
        for line in plane.lines for e in line.events
        if e.name in trace.SPANS or e.name.startswith(spantrace.PROGRAM_PREFIXES)
    ]

    def inside(e, outer):
        return outer.start_ns <= e.start_ns and e.end_ns <= outer.end_ns

    run_rounds = [e for e in events if e.name == "run_round"]
    eng = [e for e in events if e.name == "engine.round"]
    assert len(run_rounds) == len(eng) == 2
    assert [dict(e.stats)["round"] for e in eng] == rounds
    for outer, e in zip(run_rounds, eng):
        assert inside(e, outer)
    alloc = [e for e in events if e.name == "engine.allocate"]
    for name in ("controller.cache_key", "controller.fused_specs", "fused.launch",
                 "fused.wait", "fused.assembly"):
        spans = [e for e in events if e.name == name]
        assert len(spans) == 2, name
        assert all(inside(e, a) for e, a in zip(spans, alloc)), name
    for name in ("engine.partition", "engine.measure"):
        assert all(inside(e, r) for e, r in zip((e for e in events if e.name == name), eng))
    # the reduction of a trace with no TPU plane finds nothing to read
    assert spantrace.reduce_file(path) == {}


def test_readers_find_the_run_trace_through_run_cell(monkeypatch, tmp_path):
    """The harness's own route: ``run_cell`` reduces its trace and calls
    the readers, which find the raw trace through its ``trace_dir``.  The
    CPU run's trace has no TPU plane, so the recorded chip trace is put
    beside it, newest, and ``trace.py`` reduces that one."""
    raw = trace._gunzip(SPANS_TRACE)

    def reduce_dir(trace_dir, kind_filter="TPU"):
        dst = os.path.join(trace_dir, "chip", "recorded.xplane.pb")
        os.makedirs(os.path.dirname(dst))
        with open(dst, "wb") as f:
            f.write(raw)
        newest = max(os.path.getmtime(p) for p in glob.glob(
            os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
        os.utime(dst, (newest + 1, newest + 1))
        return trace.reduce_file(dst)

    monkeypatch.setattr(trace, "reduce_dir", reduce_dir)
    bench = harness.load_benchmark()
    out, _ = harness.run_cell(
        load_json("tests/data/tiny_rack4.json"), mix_json("drift"),
        load_json("tests/data/tiny_limits.json"), seed=2**31 + 11, seconds=0.5,
        trace=True, t_start=time.perf_counter(), trace_dir=str(tmp_path),
        per_layer=bench["per_layer"],
    )
    red = spantrace.reduce_file(SPANS_TRACE)
    n = out["attempted"]
    got = {k: out["metrics"][k]["value"] for k in (
        "option_scatter_ms", "frontier_ms", "dispatch_idle_ms", "controller_self_ms")}
    assert got["option_scatter_ms"] == pytest.approx(1e3 * red["scope_s"]["option_scatter"] / n)
    assert got["dispatch_idle_ms"] == pytest.approx(1e3 * (
        red["idle_by_span"]["fused.launch"] + red["idle_by_span"]["fused.wait"]) / n)
    assert got["frontier_ms"] > 0 and got["controller_self_ms"] > 0


@pytest.mark.parametrize("where", ["no run_cell", "no trace file"])
def test_a_window_with_device_time_and_no_raw_trace_raises(tmp_path, where):
    win = harness.Window(
        rounds=1, wall_s=[], spans=[], profiles=[], segments=[], dp_sizes=[],
        fused_rounds=0, compiles=0, trace={"busy_s": 0.01}, peaks=None,
    )
    if where == "no run_cell":
        with pytest.raises(RuntimeError, match="run_cell"):
            spantrace.for_window(win)
        return

    def run_cell(trace_dir):
        return spantrace.for_window(win)

    with pytest.raises(RuntimeError, match="no .xplane.pb"):
        run_cell(str(tmp_path))
