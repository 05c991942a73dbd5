"""Program spans (``repro.core.spans``) and the fused pipeline's named
device scopes (DESIGN.md §19).

* a span: its annotation's name, arguments and nesting, its duration,
  and the annotation closed on an exception;
* the engine's round profile and the fused segments keep their keys,
  each phase being its span's duration;
* the compiled pipeline's ops carry the scope names in their
  ``op_name`` metadata.
"""

import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.cluster import ClusterSim, PowerTopology
from repro.cluster import controller as controller_mod
from repro.cluster import sim as sim_mod
from repro.cluster.controller import make_controller
from repro.core import mckp, spans, surfaces, types
from repro.core.spans import Span

PHASES = {"partition_s", "batch_s", "allocate_s", "conserve_s", "actuate_s", "measure_s"}
SEGMENTS = {"prep_s", "patch_s", "compact_s", "dispatch_s", "backtrack_s", "assembly_s"}


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each
    annotation with its arguments and the one open around it."""

    log: list = []
    open_: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, args

    def __enter__(self):
        parent = self.open_[-1].name if self.open_ else None
        self.log.append((self.name, self.args, parent))
        self.open_.append(self)

    def __exit__(self, *exc):
        assert self.open_.pop() is self
        self.exc = exc[0]


@pytest.fixture
def annotations(monkeypatch):
    monkeypatch.setattr(spans, "_annotation", _Annotation)
    monkeypatch.setattr(_Annotation, "log", [])
    monkeypatch.setattr(_Annotation, "open_", [])
    return _Annotation


class _Recorded(Span):
    """A span that keeps itself, closed, in ``closed``."""

    __slots__ = ()
    closed: list = []

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self.closed.append(self)


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(_Recorded, "closed", [])
    for mod in (sim_mod, controller_mod, mckp):
        monkeypatch.setattr(mod, "Span", _Recorded)
    return _Recorded.closed


def test_nested_spans_annotate_and_time_their_phases(annotations):
    with Span("outer", round=7) as outer:
        time.sleep(0.002)
        with Span("inner") as inner:
            time.sleep(0.004)
        with Span("inner") as inner2:
            time.sleep(0.001)
    assert annotations.log == [
        ("outer", {"round": 7}, None), ("inner", {}, "outer"), ("inner", {}, "outer"),
    ]
    assert not annotations.open_
    assert inner.seconds >= 0.004 and inner2.seconds >= 0.001
    assert outer.seconds >= 0.002 + inner.seconds + inner2.seconds


def test_a_span_times_with_no_profiler_session():
    with Span("a") as a:
        time.sleep(0.001)
    assert 0.001 <= a.seconds < 1.0


def test_span_closes_on_an_exception(annotations):
    with pytest.raises(KeyError):
        with Span("outer") as outer:
            with Span("inner"):
                raise KeyError("x")
    assert not annotations.open_
    assert outer.seconds > 0
    with Span("after"):
        pass
    assert [p for _n, _a, p in annotations.log] == [None, "outer", None]


@pytest.fixture(scope="module")
def fused_sim():
    system = types.SYSTEM_1
    apps, surfs = surfaces.build_paper_suite(system)
    n = 24
    topo = PowerTopology.uniform_tree(n, (2, 2), [1e18, 9000.0, 4000.0])
    sim = ClusterSim.build(
        system, apps, surfs, n_nodes=n, seed=1,
        initial_caps=(150.0, 150.0), topology=topo,
    )
    return sim, make_controller("ecoshift_hier", system, fused=True)


def test_round_profile_keeps_its_phases_from_the_engine_spans(fused_sim, recorded):
    sim, ctrl = fused_sim
    for r, budget in enumerate((900.0, 875.0)):
        recorded.clear()
        sim.run_round(ctrl, budget=budget, round_index=r)
        prof = sim.last_round_profile
        assert ctrl.last_solver == "fused"
        assert PHASES <= set(prof)
        assert "spans" not in prof
        assert not {k for k in prof if k.startswith("alloc_") and k not in (
            "alloc_solver", "alloc_fallback_reason")}
        by_name = {}
        for sp in recorded:
            by_name.setdefault(sp.name, []).append(sp)
        assert {"engine.round", "engine.allocate", "controller.fused_specs",
                "fused.launch", "fused.wait", "controller.allocation"} <= set(by_name)
        (rnd,) = by_name["engine.round"]
        assert rnd.args == {"round": r} and recorded[-1] is rnd
        for phase in PHASES:
            (sp,) = by_name["engine." + phase[:-2]]
            assert prof[phase] == sp.seconds
        assert sum(prof[p] for p in PHASES) <= rnd.seconds


def test_fused_segments_keep_their_keys_and_dispatch_is_launch_plus_wait(fused_sim, recorded):
    sim, ctrl = fused_sim
    sim.run_round(ctrl, budget=850.0, round_index=2)
    seg = ctrl.fused_segments()
    assert set(seg) == SEGMENTS
    by_name = {sp.name: sp.seconds for sp in recorded}
    assert seg["dispatch_s"] == by_name["fused.launch"] + by_name["fused.wait"]
    assert seg["prep_s"] == by_name["fused.prep"]
    assert seg["backtrack_s"] == by_name["fused.backtrack"]
    assert seg["assembly_s"] == by_name["fused.assembly"]
    # the spec walk is the controller's span around the fused solve
    names = [sp.name for sp in recorded]
    assert names.index("fused.assembly") < names.index("controller.fused_specs")
    assert not hasattr(ctrl.fused_stats(), "device_s")


def test_pipeline_ops_carry_the_scope_names():
    """The compiled pipeline (CPU, Pallas interpreted) names the option
    mapping (the one-hot max onto the curve and the remap min), each
    frontier wave and the leaf backtrack in its ops' ``op_name``
    metadata, the path a trace's op metadata carries."""
    L, S, K, NB, NBT = 4, 3, 4, 16, 64
    ops_, depths, under, dom_rows = mckp._tree_ops(("d", 0, tuple(range(L))), L)
    waves = mckp._tree_waves(ops_, depths, under, NB, NBT)
    run = mckp._fused_pipeline_fn.__wrapped__(
        (waves, dom_rows), L, L, S, K, NB, NBT, 1, True
    )
    text = run.lower(
        jax.ShapeDtypeStruct((S, L, K), jnp.int32),
        jax.ShapeDtypeStruct((S, L, K), jnp.float32),
        jax.ShapeDtypeStruct((L,), jnp.int32),
        jax.ShapeDtypeStruct((len(dom_rows),), jnp.int32),
    ).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for op in ("reduce_max", "reduce_min"):
        assert any("/leaf_scan/" in p and f"/option_scatter/{op}" in p for p in paths), op
    assert any(p.startswith("jit(run)/leaf_backtrack/") for p in paths)
    for w in range(len(waves)):
        assert any(p.startswith(f"jit(run)/frontier_wave{w}/") for p in paths)
    assert any(p.startswith("jit(run)/root_argmax/") for p in paths)
