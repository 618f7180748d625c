"""Pieces both drivers share: a byte-counting persistence store, the run
record the metric readers see, and the comparison of read-back contents."""

from __future__ import annotations

import collections
import types
from pathlib import Path
from typing import Dict, List, Optional


def counting_fs(root: Path, counts: Optional[collections.Counter] = None):
    """The program's SimFS (pwb = buffered write, pfence = write to the file
    system), counting pwbs, pwb bytes and pfences by tag into ``counts``,
    which survives ``crash()``."""
    from repro.checkpoint.dfc_checkpoint import FaultInjector, SimFS

    class CountingFS(SimFS):
        def __init__(self, root, injector=None, counts=None):
            super().__init__(root, injector)
            self.counts = counts if counts is not None else collections.Counter()

        def write(self, rel, data, tag=None):
            super().write(rel, data, tag)
            self.counts["pwb"] += 1
            self.counts["pwb_bytes"] += len(data)

        def fsync(self, rels=None, tag=None):
            super().fsync(rels, tag)
            self.counts["pfence"] += 1
            self.counts[f"pfence.{tag}"] += 1

        def crash(self):
            return CountingFS(self.root, FaultInjector(), self.counts)

    return CountingFS(root, counts=counts)


def warm_batches(rt, phases) -> None:
    """Run one phase of OP_NONE operations on ``rt`` per entry of ``phases``
    (the batch size of each announcing client), so the announce, gather and
    padding programs of those shapes are compiled."""
    import numpy as np

    from repro.core.jax_dfc import OP_NONE

    for token, sizes in enumerate(phases, start=1):
        for client, n in enumerate(sizes):
            rt.announce(client, np.zeros(n, np.int64), np.full(n, OP_NONE), np.zeros(n),
                        token=token)
        rt.combine_phase()


def warm_step(rt, pads) -> None:
    """Compile the fused step of ``rt``'s fabric at each padded width."""
    import jax
    import jax.numpy as jnp

    from repro.core.jax_dfc import OP_NONE
    from repro.runtime import dfc_shard

    for pad in pads:
        jax.block_until_ready(dfc_shard.hetero_multi_step(
            rt.groups, jnp.asarray(rt.table),
            jnp.zeros((1, pad), jnp.int32), jnp.full((1, pad), OP_NONE, jnp.int32),
            jnp.zeros((1, pad), jnp.float32), rt.meta,
            kinds=tuple(rt.kinds), lanes=rt.lanes, backend=rt.backend, unroll=rt.depth,
        ))


def list_mismatch(got, want) -> int:
    """Positions at which two listings differ, plus their length difference."""
    got, want = list(got), list(want)
    n = min(len(got), len(want))
    return abs(len(got) - len(want)) + sum(1 for i in range(n) if got[i] != want[i])


def record(**fields) -> types.SimpleNamespace:
    """What a per-layer metric reader gets: ``spans`` (name -> durations in
    seconds, window only), ``counters`` (persistence counts in the window),
    ``trace`` (``trace_reduce`` output) and the driver's own counts."""
    return types.SimpleNamespace(**fields)


def counter_delta(after: Dict[str, int], before: Dict[str, int]) -> Dict[str, int]:
    return {k: after[k] - before.get(k, 0) for k in after}


def pad_sizes(n: int) -> List[int]:
    """The combine's padded batch widths up to ``n`` ops (powers of two, at
    least 8), as ``combine_phase`` pads them."""
    out, p = [], 8
    while True:
        out.append(p)
        if p >= n:
            return out
        p *= 2
