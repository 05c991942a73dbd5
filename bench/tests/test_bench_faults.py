"""The comparison catches a broken timed path (CPU, tiny deployment).

Each case skips the harness's look for a chip, drives the rest of a run
with one fault planted under the controller, and sees ``correct`` come
out false.  One chip: there is no exchange between chips to leave out."""

import dataclasses
import tempfile
import time

import pytest

from bench import harness
from bench.cluster import load_json
from bench.tests import mix_json

CONFIG = load_json("tests/data/tiny_rack4.json")
LIMITS = load_json("tests/data/tiny_limits.json")


def _wrap(cell, after):
    """Route the controller's answer through ``after(alloc, n_call)``."""
    inner = cell.ctrl.allocate_hierarchical
    calls = [0]

    def allocate_hierarchical(*a, **kw):
        calls[0] += 1
        return after(inner(*a, **kw), calls[0])

    cell.ctrl.allocate_hierarchical = allocate_hierarchical


def stale_state(cell):
    """Every round returns the first round's answer unchanged."""
    first = []

    def after(alloc, _n):
        if not first:
            first.append(alloc)
        return first[0]

    _wrap(cell, after)


def half_batch(cell):
    """Half of the receivers left out (baseline caps), the mean taken
    over the rest."""
    def after(alloc, _n):
        names = sorted(alloc.caps)
        kept = set(names[::2])
        base = tuple(CONFIG["initial_caps"])
        caps = {nm: (alloc.caps[nm] if nm in kept else base) for nm in names}
        return dataclasses.replace(alloc, caps=caps)

    _wrap(cell, after)


def altered_answer(cell):
    """One receiver's caps altered where they are produced: its upgrade
    is dropped."""
    def after(alloc, _n):
        base = tuple(CONFIG["initial_caps"])
        up = sorted(nm for nm, c in alloc.caps.items() if tuple(c) != base)
        if not up:
            return alloc
        return dataclasses.replace(alloc, caps={**alloc.caps, up[0]: base})

    _wrap(cell, after)


@pytest.mark.parametrize("fault,mix", [
    (stale_state, "drift"),
    (half_batch, "churn10"),
    (altered_answer, "churn10"),
])
def test_fault_makes_run_incorrect(fault, mix):
    with tempfile.TemporaryDirectory():
        out, lines = harness.run_cell(
            CONFIG, mix_json(mix), LIMITS, seed=424242,
            seconds=0.6, trace=False, t_start=time.perf_counter(), fault=fault,
        )
    assert out["correct"] is False
    assert out["failed"] >= 1
    assert any(
        v["value"] > v["limit"] for v in out["checks"].values()
    ), out["checks"]
