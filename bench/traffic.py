"""The one traffic generator: turns a mix's parameters (``bench/traffic/*.json``)
and ``--seed`` into the operations and arrival times a driver sends.

Every seed gets the same amount of work: the same batch sizes and operation
counts, and the same multiset of arrival gaps and crash points, drawn once
from the mix's own ``pool_seed`` and put in another order by ``--seed``.  Keys
and the order of operations inside a batch are drawn from ``--seed``.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench.reference import semantics as ref

F32_EXACT = 1 << 24  # integers below this are exact in float32


class ClosedLoop:
    """Batches for ``clients`` clients that each announce ``ops_per_client``
    operations per phase, in the proportions of ``mix`` (op name -> share),
    with Zipf keys over ``keys.universe`` (only keys routing to
    ``keys.shards``, when given).  Parameters of writing operations are unique
    integers counting up from ``first_value``."""

    def __init__(self, traffic: Dict, config: Dict, seed: int, first_value: int):
        self.rng = np.random.default_rng(seed)
        self.kind = config["kind"]
        self.clients = int(traffic["clients"])
        self.per_client = int(traffic["ops_per_client"])
        names = ref.OP_NAMES[self.kind]
        counts = {names[op]: int(round(share * self.per_client))
                  for op, share in traffic["mix"].items()}
        if sum(counts.values()) != self.per_client:
            raise ValueError(f"mix {traffic['mix']} does not split {self.per_client} ops")
        self.op_multiset = np.repeat(list(counts), list(counts.values())).astype(np.int64)
        self.writes = np.asarray(sorted(ref.WRITES[self.kind]), np.int64)
        keys = traffic["keys"]
        shards = keys.get("shards")
        universe = int(keys["universe"])
        self.universe = (
            np.arange(universe, dtype=np.int64) if shards is None
            else ref.keys_for_shards(shards, int(config["shards"]), universe)
        )
        self.cdf = ref.zipf_cdf(universe, float(keys.get("skew", 0.0)))
        self.next_value = int(first_value)

    def phase(self) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """One phase: (keys, ops, params) per client, client order."""
        out = []
        for _ in range(self.clients):
            ops = self.rng.permutation(self.op_multiset)
            keys = self.universe[ref.zipf_draw(self.rng, self.cdf, self.per_client)]
            is_write = np.isin(ops, self.writes)
            n = int(is_write.sum())
            if self.next_value + n >= F32_EXACT:
                raise ValueError("payload values would pass 2^24, where float32 "
                                 "stops holding every integer")
            params = np.zeros(self.per_client, np.float64)
            params[is_write] = np.arange(self.next_value, self.next_value + n)
            self.next_value += n
            out.append((keys, ops, params))
        return out


class Pool:
    """A fixed multiset of numbers drawn once from ``pool_seed``, handed out
    in an order drawn from ``--seed`` (a new order each time it runs out)."""

    def __init__(self, values: np.ndarray, seed: int):
        self.values = np.asarray(values)
        self.rng = np.random.default_rng(seed)
        self.order: List[int] = []

    def take(self) -> float:
        if not self.order:
            self.order = list(self.rng.permutation(len(self.values)))
        return float(self.values[self.order.pop()])


def crash_points(traffic: Dict, seed: int) -> Pool:
    """Where a crash phase crashes, as a fraction of a phase's persistence
    ops: ``crash_points`` evenly spaced fractions, in a seeded order."""
    n = int(traffic["crash_points"])
    return Pool((np.arange(n) + 0.5) / n, seed)


def arrival_times(traffic: Dict, seed: int, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop at
    ``rate_per_s``: round(rate * seconds) arrivals whose gaps are Gamma with
    shape ``gap_shape`` (below 1 is burstier than Poisson), drawn once from
    ``pool_seed``, scaled to fill the window and put in a seeded order."""
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    shape = float(traffic["gap_shape"])
    gaps = np.random.default_rng(int(traffic["pool_seed"])).gamma(shape, 1.0, n)
    gaps = np.random.default_rng(seed).permutation(gaps * (seconds / gaps.sum()))
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
