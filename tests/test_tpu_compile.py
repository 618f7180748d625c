"""The fabric's main-path programs compile for one TPU v5e chip.

Each test compiles at deployment widths against a DESCRIBED ``v5e:2x2``
topology — no chip attached, nothing runs — so the chip's own compiler
judges what interpret mode cannot: block tiling, the Mosaic lowering of
every op in the Pallas kernels, and whether a program fits the device.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.  The persistent compilation cache is switched off around these
compiles (a TPU program cannot be read back from it without a chip).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.jax_dfc import init_sharded
from repro.kernels.dfc_reduce import kernel as dfc_kernel
from repro.runtime import dfc_shard

HBM_BYTES = 16 * 2**30  # one v5e chip
RING_SHARDS, LANES = 64, 256  # durable work queue: 64 shards x 256 lanes
CAPACITY = 65_536  # slots per shard (a map shard: 8,192 buckets of 8)
MAP_SHARDS = 16
K_PHASES, BATCH = 8, 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kind", ["stack", "queue", "deque"])
def test_ring_grid_kernel_compiles(one_chip, kind):
    call, n_windows = {
        "stack": (dfc_kernel.dfc_reduce_grid_call, 1),
        "queue": (dfc_kernel.dfc_queue_reduce_grid_call, 1),
        "deque": (dfc_kernel.dfc_deque_reduce_grid_call, 2),
    }[kind]
    rows = (RING_SHARDS, LANES)
    args = (
        [_spec(one_chip, rows, jnp.int32), _spec(one_chip, rows, jnp.float32)]
        + [_spec(one_chip, rows, jnp.float32)] * n_windows
        + [_spec(one_chip, (RING_SHARDS,), jnp.int32)]
    )
    compiled = (
        jax.jit(functools.partial(call, interpret=False)).lower(*args).compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_map_grid_kernel_compiles(one_chip):
    table = (MAP_SHARDS, CAPACITY)
    lanes = (MAP_SHARDS, LANES)
    args = [
        _spec(one_chip, table, jnp.int32),
        _spec(one_chip, table, jnp.float32),
        _spec(one_chip, table, jnp.int32),
        _spec(one_chip, (MAP_SHARDS,), jnp.int32),
        _spec(one_chip, lanes, jnp.int32),
        _spec(one_chip, lanes, jnp.int32),
        _spec(one_chip, lanes, jnp.float32),
    ]
    compiled = (
        jax.jit(
            functools.partial(
                dfc_kernel.dfc_map_reduce_grid_call, interpret=False
            )
        )
        .lower(*args)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_donated_phase_loop_compiles_and_fits(one_chip, monkeypatch, backend):
    """The fused K-phase program the chip runs (group buffers donated) for
    64 queue shards x 65,536 slots at K=8 phases of 1,024 ops."""
    # the described chip is not the default backend: steer the kernels off
    # the interpreter the CPU platform would pick
    monkeypatch.setattr(dfc_kernel, "default_interpret", lambda: False)
    kinds = ("queue",) * RING_SHARDS
    groups = {
        "queue": jax.eval_shape(
            lambda: init_sharded("queue", RING_SHARDS, CAPACITY)
        )
    }
    meta = jax.eval_shape(lambda: dfc_shard._init_meta(kinds))
    phase = (K_PHASES, BATCH)
    compiled = dfc_shard._phase_loop_step_donated.lower(
        _on(one_chip, groups),
        _spec(one_chip, (RING_SHARDS,), jnp.int32),
        _spec(one_chip, phase, jnp.int32),
        _spec(one_chip, phase, jnp.int32),
        _spec(one_chip, phase, jnp.float32),
        _on(one_chip, meta),
        kinds=kinds, lanes=LANES, backend=backend, unroll=1,
    ).compile()
    mem = compiled.memory_analysis()
    need = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert need < HBM_BYTES, need
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")


def test_mixed_fabric_combine_phase_compiles(one_chip, monkeypatch):
    """One durable combine-phase dispatch (``hetero_multi_step``) over a
    mixed fabric of 16 stack, 16 queue, 16 deque and 16 map shards, every
    kind group on its Pallas kernel."""
    monkeypatch.setattr(dfc_kernel, "default_interpret", lambda: False)
    kinds = sum(
        (("stack",) * 16, ("queue",) * 16, ("deque",) * 16, ("map",) * 16), ()
    )
    groups = {
        k: jax.eval_shape(lambda k=k: init_sharded(k, 16, CAPACITY))
        for k in ("stack", "queue", "deque", "map")
    }
    meta = jax.eval_shape(lambda: dfc_shard._init_meta(kinds))
    batch = (1, BATCH)
    compiled = dfc_shard.hetero_multi_step.lower(
        _on(one_chip, groups),
        _spec(one_chip, (len(kinds),), jnp.int32),
        _spec(one_chip, batch, jnp.int32),
        _spec(one_chip, batch, jnp.int32),
        _spec(one_chip, batch, jnp.float32),
        _on(one_chip, meta),
        kinds=kinds, lanes=LANES, backend="pallas", unroll=1,
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 4
