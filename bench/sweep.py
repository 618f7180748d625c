#!/usr/bin/env python3
"""Find the serving tier's knee: the highest arrival rate whose backlog does
not grow over the window.  Runs a tier configuration under a traffic mix at
each given rate in one process and prints, per rate, sessions admitted per
second, the backlog left when the window closed and the 95th-percentile
admission wait.

    python3 bench/sweep.py --config serving_tier --traffic burst --rates 10,20,30 --seconds 20 --seed 1

The rate a cell runs at is then written into its traffic file as a number.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, tier

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    config, traffic = harness.load_parts(args.config, args.traffic)
    harness.find_device(1, require_tpu=True)
    for rate in (float(r) for r in args.rates.split(",")):
        store = Path(tempfile.mkdtemp(prefix="dfc_sweep_"))
        try:
            ctx = harness.Context(seed=args.seed, traced=False, store=store,
                                  cache_dir=harness.BENCH_DIR / ".cache", control=False)
            ctx.device_kind = None
            driver = tier.Driver(config, dict(traffic, rate_per_s=rate), ctx, lambda s: None)
            driver.setup()
            driver.window(args.seconds)
            backlog = driver._queued() + len(driver.waiting) + len(driver.unsubmitted)
            e2e = driver.end_to_end()
            print(json.dumps({"rate_per_s": rate, "sessions_per_s": e2e["sessions_per_s"],
                              "backlog_at_close": backlog, "arrived": driver.attempted,
                              "admit_p95_ms": e2e["admit_p95_ms"]}), flush=True)
        finally:
            shutil.rmtree(store, ignore_errors=True)
