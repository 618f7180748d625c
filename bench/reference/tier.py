"""The serving tier's layout, as its reference knows it.

The tier runs ``n_queues`` request queues, one stack of free decode slots
and one map of per-session state on one fabric.  Bucket 0 of the routing
table goes to the slot stack, every fourth bucket after it to the session
map, the rest round-robin to the request queues; there are four buckets per
shard.  A session's state entry sits at the first key of its window
``[sid * 64, sid * 64 + 64)`` that routes to the session map, and holds
``slot * 8 + stage`` for a session of the lowest class.
"""

from __future__ import annotations

import numpy as np

from bench.reference.semantics import route

KEY_STRIDE = 64
STAGE_DOM = 8
QUEUED, ADMITTED, SERVED = 1, 2, 3


def kinds(n_queues: int):
    return ["queue"] * n_queues + ["stack", "map"]


def table(n_queues: int) -> np.ndarray:
    pool, smap = n_queues, n_queues + 1
    n_buckets = 4 * (n_queues + 2)
    return np.asarray(
        [pool] + [smap if b % 4 == 1 else b % n_queues for b in range(1, n_buckets)],
        np.int64,
    )


def state_key(sid: int, n_queues: int) -> int:
    """The session map key of ``sid``'s state entry."""
    cand = np.arange(sid * KEY_STRIDE, (sid + 1) * KEY_STRIDE, dtype=np.int64)
    hit = np.nonzero(route(cand, n_queues + 2, table(n_queues)) == n_queues + 1)[0]
    return int(cand[hit[0]])
