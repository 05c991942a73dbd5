"""On-chip benchmark of the EcoShift control round (see ``BENCHMARK.json``)."""
