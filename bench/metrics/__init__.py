"""Per-layer metric readers, one module per metric of ``BENCHMARK.json``.

Each module defines ``read(window) -> float | None`` over a
``bench.harness.Window``; ``None`` means the metric found nothing to
read in this run and is left out of the result line."""
