#!/usr/bin/env python3
"""Run one cell of the durable fabric's chip benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the repository root, on a machine whose first JAX device is a TPU.  The
last line of standard output is the run's result as one JSON object; the
numbers that decided ``correct`` are the last lines of standard error.
Cells, metrics and bounds are in ``BENCHMARK.json``.
"""

import sys
import time
from pathlib import Path

T0 = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    age = harness.process_age_s()
    sys.exit(harness.main(t_start=time.perf_counter() - age if age is not None else T0))
