"""Benchmark harness — one function per paper table/figure.

Prints ``name,value,derived`` CSV.  Figures:
  fig3a  throughput (cost-model)            bench_throughput
  fig3bc pwb/pfence per op                  bench_persistence
  fig4   combining phases per op            bench_phases
  jax    vectorized combine timings         bench_jax_combine
  ckpt   DFC-Checkpoint combining           bench_checkpoint
  shard  sharded multi-object runtime       bench_sharded (smoke grid)
  reshard  split/merge before-during-after  bench_reshard (smoke grid)
  phase_loop  fused K-phase dispatch        bench_phase_loop (smoke grid)

The bench story (what each module measures, the BENCH_*.json schema) is
documented in docs/benchmarks.md.

Every ``benchmarks/bench_*.py`` module is discovered from ONE registry
(``discover_benches``) built from the directory contents, so adding a bench
file is all it takes to get it run — the list here can no longer drift.
Contract: each bench module exposes ``main(emit)``; when ``main`` returns a
row list, the harness writes it to ``BENCH_<name>.json`` at the REPO ROOT
(never the CWD), so every entry point — ``run.py`` and each module's
``--smoke`` script mode — lands its artifact at the same deterministic
path.
"""

from __future__ import annotations

import importlib
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent  # repo root, CWD-independent


def discover_benches():
    """The single bench registry: every bench_*.py next to this file."""
    here = Path(__file__).resolve().parent
    if str(here.parent) not in sys.path:  # `python benchmarks/run.py` puts
        sys.path.insert(0, str(here.parent))  # benchmarks/ itself first
    names = sorted(  # bench_common is shared plumbing, not a bench
        p.stem for p in here.glob("bench_*.py") if p.stem != "bench_common"
    )
    return [(name, importlib.import_module(f"benchmarks.{name}")) for name in names]


def main() -> None:
    def emit(name, value, derived=""):
        print(f"{name},{value},{derived}", flush=True)

    t0 = time.time()
    for name, module in discover_benches():
        rows = module.main(emit)
        if rows:  # structured results -> deterministic repo-root artifact
            from benchmarks.bench_common import write_rows

            out = write_rows(
                _ROOT / f"BENCH_{name.removeprefix('bench_')}.json",
                rows,
                extra={"entry": "run.py", "smoke": True},
            )
            print(f"# wrote {out} ({len(rows)} configs)", file=sys.stderr)
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
