"""Tiny copies of the benchmark's configurations and traffic mixes, for CPU
tests: the committed files with their sizes cut, written under a test's
temporary directory so the harness finds them by name."""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG_CUTS = {
    "durable_queue": dict(shards=4, capacity=1024, lanes=64, threads=4, backlog=512),
    "serving_tier": dict(capacity=1024, slots=8, lanes=16),
}
TRAFFIC_CUTS = {
    "zipf5050": dict(clients=4, ops_per_client=16, warmup_phases=1),
    "crash": dict(clients=4, ops_per_client=16, warmup_phases=1),
    "hot1": dict(clients=4, ops_per_client=8, warmup_phases=1),
    "burst": dict(max_submit=4, warmup_sessions=8, rate_per_s=40),
}


def write_tiny(data_dir: Path) -> Path:
    """Tiny configs and traffic mixes under ``data_dir``; returns it."""
    for sub, cuts in (("configs", CONFIG_CUTS), ("traffic", TRAFFIC_CUTS)):
        (data_dir / sub).mkdir(parents=True, exist_ok=True)
        for name, cut in cuts.items():
            doc = json.loads((BENCH / sub / f"{name}.json").read_text())
            doc.update(cut)
            (data_dir / sub / f"{name}.json").write_text(json.dumps(doc))
    return data_dir


# cells whose drivers are built and tested here but that BENCHMARK.json does
# not hold yet (PERF.md, Open questions)
PENDING_CELLS = [
    {"name": "tier.burst", "config": "serving_tier", "traffic": "burst", "chips": 1},
    {"name": "queue.crash", "config": "durable_queue", "traffic": "crash", "chips": 1},
]


def spec():
    """BENCHMARK.json with the pending cells added."""
    from bench import harness

    doc = harness.load_spec()
    doc["workloads"] = doc["workloads"] + PENDING_CELLS
    return doc


def run_tiny(tmp_path: Path, cell: str, seconds: float = 1.0, trace: bool = False,
             control: bool = False, seed: int = 2**31 + 7):
    from bench import harness

    return harness.run_cell(
        spec(), cell, seed=seed, seconds=seconds, trace=trace,
        data_dir=write_tiny(tmp_path / "data"), require_tpu=False, control=control,
        cache_dir=tmp_path / "cache", log=lambda s: None,
    )
