"""Serving-style traffic over the sharded DFC runtime.

Generates a Zipf-skewed key workload (a few hot keys dominate, like any
serving tier), drives a ShardedDFCRuntime with mixed push/pop batches, and
prints per-shard load, throughput, and — in durable mode — pwb/op, the
paper's Figure-3 metric, now amortized across objects as well as ops.

PR-3 options: ``--mixed`` runs a HETEROGENEOUS fabric (stack/queue/deque
shards round-robin behind one router; op codes are drawn per key to be valid
for the target shard's kind), and ``--split-backlog N`` splits the hottest
shard crash-consistently once it has absorbed N more ops than the average —
watch the shard-load histogram flatten after the split.

ISSUE-5 options: ``--threads T`` announces each durable phase from T
concurrent announcers through the seeded ``MultiThreadDriver`` (random but
replayable announcer/combiner interleavings), and ``--depth D`` pipelines
the durable path D chains deep — together the two axes the paper's
amortization claim actually grows along.

Run:  PYTHONPATH=src python examples/serve_shards.py [--kind queue|--mixed]
      [--shards 16] [--skew 1.1] [--phases 50] [--durable] [--split-backlog N]
      [--threads 4] [--depth 3]
"""

from __future__ import annotations

import argparse
import tempfile
import time
from pathlib import Path

import numpy as np

from repro.checkpoint.dfc_checkpoint import SimFS
from repro.core.jax_dfc import STRUCTS
from repro.launch.compile_cache import use_compile_cache
from repro.runtime.announce_driver import MultiThreadDriver
from repro.runtime.dfc_shard import (
    R_OVERFLOW,
    ShardedDFCRuntime,
    zipf_keys,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kind", default="queue", choices=sorted(STRUCTS))
    ap.add_argument("--mixed", action="store_true",
                    help="heterogeneous fabric: kinds round-robin per shard")
    ap.add_argument("--shards", type=int, default=16)
    ap.add_argument("--skew", type=float, default=1.1)
    ap.add_argument("--phases", type=int, default=50)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--durable", action="store_true")
    ap.add_argument("--threads", type=int, default=1,
                    help="announcing threads per durable phase (seeded "
                         "interleaved scheduler when > 1)")
    ap.add_argument("--depth", type=int, default=0,
                    help="durable pipeline depth (0 = serial)")
    ap.add_argument("--split-backlog", type=int, default=0,
                    help="split the hottest shard once it leads the mean "
                         "op count by N (0 = never)")
    args = ap.parse_args()

    use_compile_cache()
    rng = np.random.default_rng(0)
    all_kinds = sorted(STRUCTS)
    kinds = (
        [all_kinds[s % len(all_kinds)] for s in range(args.shards)]
        if args.mixed
        else args.kind
    )
    lanes = args.batch  # worst case: every op on one shard
    capacity = args.batch * (args.phases + 1)

    fs = None
    if args.durable:
        fs = SimFS(Path(tempfile.mkdtemp(prefix="dfc_serve_")))
    rt = ShardedDFCRuntime(
        kinds, args.shards, capacity, lanes, fs=fs, n_threads=args.threads,
        n_buckets=4 * args.shards if args.split_backlog else None,
        depth=args.depth or None,
        chain=args.threads if (args.depth or 0) > 1 else 1,
    )
    drv = (
        MultiThreadDriver(rt, seed=1)
        if args.durable and args.threads > 1
        else None
    )

    n_ops = n_overflow = 0
    shard_hits = np.zeros(args.shards, np.int64)
    splits = []
    t0 = time.perf_counter()
    for phase in range(args.phases):
        keys = zipf_keys(rng, args.batch, 4096, args.skew)
        shard = rt.route_host(keys)
        opmax = np.asarray([STRUCTS[k].n_opcodes for k in rt.kinds])
        ops = rng.integers(1, opmax[shard])  # per-key draw valid for its kind
        params = rng.random(args.batch).astype(np.float32) * 100
        if args.durable and drv is not None:
            # slice the phase's batch across the announcing threads; the
            # seeded driver interleaves announce/combine actions replayably
            per = (args.batch + args.threads - 1) // args.threads
            toks = []
            for t in range(args.threads):
                sl = slice(t * per, min((t + 1) * per, args.batch))
                if sl.start >= sl.stop:
                    break
                toks.append((t, drv.submit(t, keys[sl], ops[sl], params[sl])))
            drv.run()
            kinds_out = np.concatenate([
                np.asarray(rt.read_responses(t, token=tok)["kinds"])
                for t, tok in toks
            ])
        elif args.durable:
            rt.announce(0, keys, ops, params, token=phase + 1)
            rt.combine_phase()
            rt.flush()
            kinds_out = np.asarray(rt.read_responses(0)["kinds"])
        else:
            _, kinds_out = rt.step(keys, ops, params)
            kinds_out = np.asarray(kinds_out)
        n_ops += int(np.sum(kinds_out != R_OVERFLOW))
        n_overflow += int(np.sum(kinds_out == R_OVERFLOW))
        if shard_hits.shape[0] < rt.n_shards:  # a split added shards
            shard_hits = np.concatenate(
                [shard_hits, np.zeros(rt.n_shards - shard_hits.shape[0], np.int64)]
            )
        shard_hits[: shard.max() + 1] += np.bincount(shard, minlength=shard.max() + 1)

        if args.split_backlog:
            ops_comb = np.asarray(rt.meta["ops_combined"])
            hot = int(np.argmax(ops_comb))
            if ops_comb[hot] - ops_comb.mean() > args.split_backlog:
                try:
                    new_id = rt.split_shard(hot)
                    splits.append((phase, hot, new_id))
                except ValueError:
                    pass  # shard down to one bucket
    dt = time.perf_counter() - t0
    if shard_hits.shape[0] < rt.n_shards:  # a final-phase split added shards
        shard_hits = np.concatenate(
            [shard_hits, np.zeros(rt.n_shards - shard_hits.shape[0], np.int64)]
        )

    label = "mixed" if args.mixed else args.kind
    print(f"kind={label} shards={rt.n_shards} skew={args.skew}")
    print(f"throughput: {n_ops / dt:,.0f} ops/s  ({args.phases} phases, {dt:.2f}s)")
    print(f"overflow:   {n_overflow} ops rejected (re-announce to retry)")
    hot = ", ".join(f"s{s}({rt.kinds[s][0]}):{h}" for s, h in enumerate(shard_hits))
    print(f"shard load: {hot}")
    touched = np.asarray(rt.meta["phases"])
    print(f"phases/shard: min={touched.min()} max={touched.max()}")
    for phase, donor, new_id in splits:
        print(f"split: phase {phase}: shard {donor} -> +shard {new_id}")
    if args.durable:
        print(
            f"pwb/op: {fs.stats['pwb'] / max(n_ops, 1):.3f}  "
            f"pfence/op: {fs.stats['pfence'] / max(n_ops, 1):.3f}"
        )


if __name__ == "__main__":
    main()
