#!/usr/bin/env python3
"""Run one cell on many seeds in one process, to read its correctness
numbers (and, with ``--control``, those of the bfloat16 control: the
reference put in the program's place) without paying set-up per seed.

    python3 bench/seeds.py --workload queue.zipf5050 --seeds 1,2,3 --seconds 10 [--control]

One JSON line per seed on standard output: the seed, ``correct`` and each
number compared with its limit.  Timings from here are not the benchmark's:
every run after the first finds its programs already in the process.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import os

    from bench import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    spec = harness.load_spec()
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(spec, args.workload, seed=seed, seconds=args.seconds,
                             trace=False, control=args.control, log=lambda s: None)
        print(json.dumps({"seed": seed, "control": args.control, "correct": r["correct"],
                          "attempted": r["attempted"], "checks": r["checks"],
                          "metrics": r["metrics"]}), flush=True)
