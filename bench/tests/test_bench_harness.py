"""A rehearsal of the benchmark on the CPU: a test-only tiny deployment
(``tests/data/tiny_rack4.json``, not a cell of ``BENCHMARK.json``) runs a
short window through the harness with Pallas interpreted."""

import json
import os
import subprocess
import sys
import tempfile
import time

import pytest

from bench import harness
from bench.cluster import BENCH_DIR, load_json
from bench.tests import mix_json

CONFIG = load_json("tests/data/tiny_rack4.json")
LIMITS = load_json("tests/data/tiny_limits.json")
ROOT = os.path.dirname(BENCH_DIR)
#: per-layer metrics a CPU run can read (the rest read a TPU trace)
HOST_METRICS = {
    "round_ms_p95", "engine_ms", "measure_ms", "controller_ms",
    "fused_host_ms", "fused_pct", "dispatch_ms", "compiles_in_window",
}


def _run(mix: str, trace: bool, seed: int = 2**31 + 99, seconds: float = 1.0):
    bench = harness.load_benchmark()
    with tempfile.TemporaryDirectory() as tdir:
        return harness.run_cell(
            CONFIG, mix_json(mix), LIMITS, seed=seed,
            seconds=seconds, trace=trace, t_start=time.perf_counter(),
            trace_dir=tdir if trace else None, per_layer=bench["per_layer"],
        )


@pytest.mark.parametrize("mix", ["churn10", "drift"])
def test_untraced_run_line(mix):
    out, lines = _run(mix, trace=False)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 5
    assert set(out["metrics"]) == {"round_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert list(out["checks"]) == list(harness.CHECKS)
    assert lines[0].startswith("setup: ") and lines[1].startswith("window: ")
    assert [ln.split(":")[0] for ln in lines[2:]] == [f"check {k}" for k in harness.CHECKS]
    json.dumps(out)


def test_traced_run_reads_each_host_metric():
    out, _ = _run("churn10", trace=True, seconds=1.5)
    assert out["correct"] is True
    assert set(out["metrics"]) == HOST_METRICS
    assert out["metrics"]["fused_pct"]["value"] == 100.0
    assert out["metrics"]["dispatch_ms"]["value"] > 0
    assert out["metrics"]["engine_ms"]["value"] > 0


def test_command_without_a_tpu_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rack16_drift",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
