#!/usr/bin/env python3
"""Find the serving tier's knee: the highest arrival rate whose backlog does
not grow over the window.  Runs a tier configuration under a traffic mix at
each given rate in one process and prints, per rate, sessions admitted per
second (``ops_per_s``), the backlog left when the window closed and the
95th-percentile admission wait of the sessions admitted by then
(``p95_ms``; a run's own ``p95_ms`` also counts those it drains after the
window), its set-up seconds and the programs compiled inside the window.

    python3 bench/sweep.py --config serving_tier --traffic burst --rates 10,20,30 --seconds 30 --seed 1,2

runs each rate with each seed, rate by rate.  The rate a cell runs at is
then written into its traffic file as a number.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
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
    ap.add_argument("--seed", required=True, help="one seed, or several separated by commas")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    config, traffic = harness.load_parts(args.config, args.traffic)
    harness.find_device(1, require_tpu=True)
    compiles = harness.CompileLog()
    seeds = [int(x) for x in args.seed.split(",")]
    for rate, seed in ((float(r), s) for r in args.rates.split(",") for s in seeds):
        store = Path(tempfile.mkdtemp(prefix="dfc_sweep_"))
        try:
            ctx = harness.Context(seed=seed, traced=False, store=store,
                                  cache_dir=harness.BENCH_DIR / ".cache", control=False)
            ctx.device_kind = None
            driver = tier.Driver(config, dict(traffic, rate_per_s=rate), ctx, lambda s: None)
            t0 = time.perf_counter()
            driver.setup()
            setup_s = time.perf_counter() - t0
            before = compiles.snapshot()[0]
            driver.window(args.seconds)
            in_window = compiles.snapshot()[0] - before
            backlog = driver._queued() + len(driver.waiting) + len(driver.unsubmitted)
            e2e = driver.end_to_end()
            print(json.dumps({"rate_per_s": rate, "seed": seed,
                              "ops_per_s": e2e["ops_per_s"],
                              "backlog_at_close": backlog, "arrived": driver.attempted,
                              "p95_ms": e2e["p95_ms"], "setup_s": setup_s,
                              "compiles_in_window": in_window,
                              "lateness": driver.lateness()}), flush=True)
        finally:
            shutil.rmtree(store, ignore_errors=True)
