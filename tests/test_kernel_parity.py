"""Sharded Pallas kernels (interpret mode on the CPU) vs the vmapped jnp
combine vs the kernels' pure-jnp twin, over many shards at once.

The single-object sweeps in ``test_jax_dfc`` / ``test_jax_queue_deque`` /
``test_map_shard`` run the kernels one shard at a time; here every grid
instance reads its own committed size from SMEM and its own lane rows, so
shard counts that are not a multiple of 8, lane counts below and at a full
128-lane row, and per-shard sizes that differ are all covered.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.jax_dfc import STRUCTS, init_sharded
from repro.kernels.dfc_reduce.ops import _one_sharded_combine

jax.config.update("jax_platform_name", "cpu")

BACKENDS = ("jnp", "ref", "pallas")


def _same(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _drive(kind, n_shards, cap, lanes, n_phases, seed):
    """Run the same random phases through every backend from one state,
    checking state, responses and kinds after every phase."""
    rng = np.random.default_rng(seed)
    n_ops = STRUCTS[kind].n_opcodes
    state = init_sharded(kind, n_shards, cap)
    value = 1.0
    for _ in range(n_phases):
        # per-shard insert bias, so committed sizes drift apart
        bias = rng.random(n_shards)[:, None]
        ops = np.where(
            rng.random((n_shards, lanes)) < bias,
            1,
            rng.integers(0, n_ops, (n_shards, lanes)),
        ).astype(np.int32)
        params = (value + np.arange(n_shards * lanes)).reshape(
            n_shards, lanes
        ).astype(np.float32)
        value += n_shards * lanes
        keys = rng.integers(0, 3 * cap, (n_shards, lanes)).astype(np.int32)
        outs = {
            b: _one_sharded_combine(
                kind, b, state, jnp.asarray(ops), jnp.asarray(params),
                keys=jnp.asarray(keys),
            )
            for b in BACKENDS
        }
        for b in ("ref", "pallas"):
            _same(outs[b], outs["jnp"])
        state = outs["jnp"][0]
    return state


@pytest.mark.parametrize("kind", ["stack", "queue", "deque"])
@pytest.mark.parametrize("n_shards,lanes", [(5, 8), (3, 24), (9, 128)])
def test_ring_kernels_match_jnp(kind, n_shards, lanes):
    _drive(kind, n_shards, 4 * lanes + 16, lanes, n_phases=4, seed=lanes)


@pytest.mark.parametrize("cap", [64, 256])
def test_map_kernel_matches_jnp(cap):
    """cap=64 probes one row of 64 slots; cap=256 the [2, 128] row view."""
    state = _drive("map", 3, cap, 16, n_phases=5, seed=cap)
    assert int(np.asarray(state.occupied).sum()) > 0
