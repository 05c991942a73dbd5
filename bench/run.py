"""Benchmark command: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's deployment from its seed, warms up every shape its
traffic uses, runs closed-loop control rounds for ``--seconds``, checks
the sampled rounds against the plain reference and prints one JSON line
(the last line of standard output), the compared numbers with their
limits last on standard error.  Exits nonzero, with no result line,
where JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
