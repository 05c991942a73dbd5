"""The trace reduction on a recorded chip trace (CPU).

``data/rack16_drift_12rounds.xplane.pb.gz``: a ``--trace 1`` run of
``rack16_drift`` on one TPU v5e with a 0.3 s window (12 rounds), kept
gzipped.  Its reduction is fixed, so a change to the reduction shows."""

import os

import numpy as np
import pytest

from bench import roofline, trace
from bench.cluster import BENCH_DIR

TRACE = os.path.join(BENCH_DIR, "tests", "data", "rack16_drift_12rounds.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_file(TRACE)


def test_window_busy_kernel_and_pipeline(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.313450075, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.019849877, abs=1e-9)
    assert reduced["kernel_s"] == pytest.approx(0.002911452, abs=1e-9)
    assert reduced["pipeline_s"] == pytest.approx(0.020076033, abs=1e-9)
    # the kernels run inside the pipeline, the pipeline inside the window
    assert reduced["kernel_s"] < reduced["busy_s"] <= reduced["pipeline_s"] < reduced["window_s"]


def test_breakdown(reduced):
    ops = dict(reduced["device_ops"])
    assert len(reduced["device_ops"]) == 10
    assert ops["maxplus_stage_pallas_batched.4"] == pytest.approx(0.00246597, abs=1e-9)
    assert list(ops)[0] == "fusion.63"
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) == {"run_round", "between_rounds", "ingest", "sync"}
    assert gaps["run_round"] == pytest.approx(0.288794488, abs=1e-9)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, abs=1e-9)


def test_kernel_pattern():
    assert trace.KERNEL.match(
        "%maxplus_stage_pallas_batched.4 = (f32[16,128]) custom-call(f32[16,128] %a)"
    )
    assert not trace.KERNEL.match("%fusion.63 = f32[16,16] fusion(f32[16,16] %b)")


def test_self_times_subtract_nested_ops():
    class E:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur
            self.end_ns = start + dur

    got = trace._self_times([
        E("%while.1 = () while()", 0, 100), E("%fusion.2 = f32 fusion()", 10, 30),
        E("%fusion.3 = f32 fusion()", 50, 20), E("%copy.4 = f32 copy()", 200, 5),
    ])
    assert got == {"while.1": 50, "fusion.2": 30, "fusion.3": 20, "copy.4": 5}


def test_idle_split_by_span():
    spans = [(0, 10, "ingest"), (10, 40, "run_round"), (40, 45, "sync")]
    got = trace._idle_by_span(
        np.array([[5.0, 15.0], [30.0, 50.0]]), spans, np.array([0, 10, 40])
    )
    assert got == {"ingest": 5.0, "run_round": 15.0, "sync": 5.0, "between_rounds": 5.0}


def test_peaks_table():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("no such chip")
