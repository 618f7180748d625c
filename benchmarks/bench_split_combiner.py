"""Per-side combiners (ISSUE 8): one-lane vs two-lane persistence cost.

A split (``split_lanes=True``) queue/deque shard commits its head-side and
tail-side announcement lanes independently: a single-lane phase persists
only that side's durable record and its half of the composite epoch pair.
The one-lane slot carries its counter pair and epoch inline in one
``meta.json``, so the split layout no longer wins much: under ARRIVAL SKEW
(bursts that touch one side at a time) a queue's head lane saves the
values write of a dequeue burst and a deque ties; drained balanced traffic
fully eliminates, and there the one-lane layout's single manifest pays
less than the split layout's two lane records.

Workload, per (kind, skew) cell on a one-shard fabric:

  * ``skewed``   — a standing backlog, then alternating tail-only push
                   bursts and head-only pop bursts (each burst one phase);
  * ``drained``  — balanced push+pop phases on an empty shard (full
                   elimination; the two layouts' persist schedules match).

Each cell measures steady-state pwb/op, pfence/op and phases/s for
``split_lanes`` off vs on.  Script mode writes ``BENCH_split_combiner.json``
(see docs/benchmarks.md) and exits non-zero if either layout's pwb per
measured phase differs from ``PWB_PER_PHASE`` in any cell — the regression
gate CI runs via ``--smoke``.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from repro.checkpoint.dfc_checkpoint import SimFS
from repro.runtime.dfc_shard import ShardedDFCRuntime

_ROOT = Path(__file__).resolve().parent.parent  # repo root, CWD-independent

# pure single-side op codes per kind: (tail-side push, head-side pop)
_TAIL_PUSH = {"queue": 1, "deque": 3}   # enq / pushr
_HEAD_POP = {"queue": 2, "deque": 2}    # deq / popl

# exact steady-state pwbs per measured phase, (one-lane, two-lane), at any
# batch size: 3 announce + 1 response + 2 epoch writes, plus the slot files
# (one-lane: manifest and, when dirty, values; two-lane: the committing
# lanes' rec.json and the values they own, when dirty)
PWB_PER_PHASE = {
    ("queue", "skewed"): (8.0, 7.5),
    ("queue", "drained"): (7.0, 8.0),
    ("deque", "skewed"): (8.0, 8.0),
    ("deque", "drained"): (8.0, 10.0),
}


def _schedule(kind: str, skew: str, m: int, phases: int):
    """Phase batches (ops, params) for a one-shard fabric; ``phases`` is the
    number of MEASURED phases (warm-up and prefill are prepended)."""
    push, pop = _TAIL_PUSH[kind], _HEAD_POP[kind]
    val = iter(np.arange(1, 1 << 20, dtype=np.float64))
    out, measured = [], []
    if skew == "skewed":
        lag = 3 * m
        out.append(([push] * lag, [float(next(val)) for _ in range(lag)]))
        for i in range(2 + phases):  # 2 warm-up burst pairs
            tail = ([push] * m, [float(next(val)) for _ in range(m)])
            head = ([pop] * m, [0.0] * m)
            (measured if i >= 2 else out).extend([tail, head])
    else:  # drained: balanced phases on an empty shard, full elimination
        for i in range(2 + phases):
            batch = (
                [push] * m + [pop] * m,
                [float(next(val)) for _ in range(m)] + [0.0] * m,
            )
            (measured if i >= 2 else out).append(batch)
    return out, measured


def _drive(rt, key, batches, token0=0) -> int:
    token = token0
    for ops, params in batches:
        token += 1
        rt.announce(0, [key] * len(ops), ops, params, token=token)
        rt.combine_phase()
    return token


def _one_cell(kind: str, skew: str, m: int, phases: int, results, emit):
    lanes, capacity = 2 * m, 16 * m
    warm, measured = _schedule(kind, skew, m, phases)
    ops_measured = sum(len(b[0]) for b in measured)
    row = {
        "kind": kind,
        "skew": skew,
        "batch": m,
        "phases": len(measured),
    }
    root = Path(tempfile.mkdtemp(prefix="dfc_bench_lanes_"))
    try:
        # rep 0 compiles; best timed rep per mode, modes interleaved so
        # machine drift hits both equally
        best = {False: float("inf"), True: float("inf")}
        persist = {}
        for rep in range(3):
            for split in (False, True):
                fs = SimFS(root / f"{int(split)}_r{rep}")
                rt = ShardedDFCRuntime(
                    kind, 1, capacity, lanes, fs=fs, n_threads=1,
                    split_lanes=split,
                )
                key = rt.key_for_shard(0)
                token = _drive(rt, key, warm)
                base = dict(fs.stats)
                t0 = time.perf_counter()
                _drive(rt, key, measured, token0=token)
                dt = time.perf_counter() - t0
                if rep:
                    best[split] = min(best[split], dt)
                    persist[split] = {
                        "pwb": (fs.stats["pwb"] - base["pwb"]) / ops_measured,
                        "pfence": (fs.stats["pfence"] - base["pfence"])
                        / ops_measured,
                    }
                shutil.rmtree(root / f"{int(split)}_r{rep}",
                              ignore_errors=True)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    for split, tag in ((False, "one_lane"), (True, "two_lane")):
        row[f"{tag}_pwb_per_op"] = persist[split]["pwb"]
        row[f"{tag}_pfence_per_op"] = persist[split]["pfence"]
        row[f"{tag}_phases_per_s"] = len(measured) / best[split]
    row["pwb_ratio"] = (
        row["two_lane_pwb_per_op"] / max(row["one_lane_pwb_per_op"], 1e-9)
    )
    emit(
        f"split_lanes_{kind}_{skew}_m{m}",
        f"{row['two_lane_pwb_per_op']:.3f}",
        f"pwb/op,one_lane={row['one_lane_pwb_per_op']:.3f},"
        f"ratio={row['pwb_ratio']:.2f},"
        f"phases/s={row['two_lane_phases_per_s']:.0f}",
    )
    results.append(row)


def run(emit, smoke: bool = False):
    results = []
    m, phases = (8, 10) if smoke else (16, 40)
    for kind in ("queue", "deque"):
        for skew in ("skewed", "drained"):
            _one_cell(kind, skew, m, phases, results, emit)
    return results


def main(emit, smoke: bool = True):
    """Benchmark-harness entry point (smoke-sized by default; run.py and CI
    call this — the full grid is `python bench_split_combiner.py` without
    --smoke)."""
    return run(emit, smoke=smoke)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="seconds-scale CI subset")
    ap.add_argument("--out", default=str(_ROOT / "BENCH_split_combiner.json"),
                    help="JSON results path (defaults to the repo root)")
    args = ap.parse_args()
    rows = run(lambda n, v, d="": print(f"{n},{v},{d}", flush=True),
               smoke=args.smoke)
    try:
        from benchmarks.bench_common import write_rows
    except ImportError:
        from bench_common import write_rows
    write_rows(args.out, rows, extra={"entry": "script", "smoke": args.smoke})
    print(f"# wrote {args.out} ({len(rows)} cells)")
    # regression gate: each layout pays exactly its pinned pwbs per phase
    losers = []
    for r in rows:
        ops_per_phase = r["batch"] * (1 if r["skew"] == "skewed" else 2)
        got = tuple(round(r[f"{t}_pwb_per_op"] * ops_per_phase, 6)
                    for t in ("one_lane", "two_lane"))
        if got != PWB_PER_PHASE[(r["kind"], r["skew"])]:
            losers.append((r, got))
    if losers:
        for r, got in losers:
            print(
                f"# REGRESSION {r['kind']}/{r['skew']}: (one-lane, two-lane) "
                f"pwb/phase {got} != {PWB_PER_PHASE[(r['kind'], r['skew'])]}",
                file=sys.stderr,
            )
        sys.exit(1)
