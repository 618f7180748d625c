"""The benchmark's own reference agrees with the program's sequential oracle
and router on seeded mixed schedules."""

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from bench.reference import semantics as ref
from bench.reference import tier as tref

KINDS = ["stack", "queue", "deque", "map"] * 2


@pytest.mark.parametrize("seed", [0, 1, 2, 2**31 + 5])
def test_reference_matches_sequential_hetero_reference(seed):
    from repro.core.jax_dfc import STRUCTS
    from repro.runtime.dfc_shard import sequential_hetero_reference

    rng = np.random.default_rng(seed)
    lanes, capacity = 6, 64
    table = rng.integers(0, len(KINDS), 32)
    mine = ref.Fabric(KINDS, lanes, capacity, table=table)
    theirs = [{} if k == "map" else [] for k in KINDS]
    value = 1
    for _ in range(40):
        n = int(rng.integers(1, 60))
        keys = rng.integers(0, 200, n)
        shard = ref.route(keys, len(KINDS), table)
        ops = np.asarray([rng.integers(0, STRUCTS[KINDS[s]].n_opcodes) for s in shard])
        params = np.arange(value, value + n, dtype=np.float64)
        cas = ops == ref.OP_MAP_CAS
        params[cas] = rng.integers(0, 8, cas.sum()) * ref.CAS_DOM + rng.integers(0, 8, cas.sum())
        value += n
        want = sequential_hetero_reference(
            KINDS, theirs, list(keys), list(ops), list(params), lanes,
            table=table, capacity=capacity)
        got = mine.phase(keys, ops, params)
        assert ref.mismatches(*got, *want) == 0
    for s, k in enumerate(KINDS):
        want = (sorted((int(a), float(b)) for a, b in theirs[s].items()) if k == "map"
                else [float(v) for v in theirs[s]])
        assert mine.contents(s) == want


def test_router_and_zipf_match_the_program():
    from repro.core.jax_dfc import map_bucket_host
    from repro.runtime.dfc_shard import route_keys_host

    keys = np.random.default_rng(3).integers(0, 2**31 - 1, 5000)
    for n in (1, 6, 64):
        assert np.array_equal(ref.route(keys, n), route_keys_host(keys, n))
    table = np.arange(24) % 6
    assert np.array_equal(ref.route(keys, 6, table), route_keys_host(keys, 6, table))
    assert np.array_equal(ref.map_bucket(keys, 128), map_bucket_host(keys, 128))
    cdf = ref.zipf_cdf(1000, 0.99)
    draws = ref.zipf_draw(np.random.default_rng(0), cdf, 20000)
    assert draws.min() >= 0 and draws.max() < 1000
    assert np.mean(draws == 0) == pytest.approx(1 / np.sum(np.arange(1, 1001) ** -0.99), rel=0.1)


def test_tier_layout_matches_the_program():
    from repro.launch.serve import RequestQueueTier

    tier = RequestQueueTier(n_queues=4, slots=2, capacity=64, lanes=8)
    assert np.array_equal(tref.table(4), tier.rt.table)
    for sid in (1, 77, 4095):
        assert tref.state_key(sid, 4) == tier.session_map_key(sid)


def test_bfloat16_reference_rounds_payloads():
    fab = ref.Fabric(["queue"], 4, 64, dtype=ref.bfloat16())
    fab.phase([0, 0], [ref.OP_ENQ, ref.OP_ENQ], [257.0, 3.0])
    assert fab.contents(0) == [256.0, 3.0]
