"""The durable shard slot's layout: one ``meta.json`` manifest per slot with
the small leaves (``ends``, ``size``, ``count``, ``epoch``) inline as raw
bytes, and a ``leaf_{i}.npy`` file only for each capacity-sized leaf.

Checked for every unsplit kind: which files a touched shard writes, that
inline leaves come back from ``recover`` bit for bit, that a slot written in
the older all-files form still recovers (and its stale files are collected),
that a crash at any persistence op of a phase leaves each shard wholly old
or wholly new, and what ``slot_leaves_inlined`` counts."""

import base64
import io
import json

import numpy as np
import pytest

import jax

from repro.checkpoint.dfc_checkpoint import CrashNow, FaultInjector, SimFS
from repro.core.jax_dfc import (
    OP_DEQ,
    OP_ENQ,
    OP_MAP_INSERT,
    OP_MAP_LOOKUP,
    OP_POP,
    OP_POPL,
    OP_PUSH,
    OP_PUSHR,
)
from repro.obs import FabricObserver
from repro.runtime.dfc_shard import ShardedDFCRuntime

jax.config.update("jax_platform_name", "cpu")

S, CAP, LANES, B = 2, 64, 8, 4
KINDS = ["stack", "queue", "deque", "map"]
# (write op, op that leaves the table arrays as they are) per kind
OPS = {
    "stack": (OP_PUSH, OP_POP),
    "queue": (OP_ENQ, OP_DEQ),
    "deque": (OP_PUSHR, OP_POPL),
    "map": (OP_MAP_INSERT, OP_MAP_LOOKUP),
}


def _fabric(fs, kind, obs=None):
    return ShardedDFCRuntime(kind, S, CAP, LANES, fs=fs, n_threads=1, obs=obs)


def _recover(fs, kind):
    rt, _ = ShardedDFCRuntime.recover(
        fs, kind=kind, n_shards=S, capacity=CAP, lanes=LANES, n_threads=1
    )
    return rt


def _keys(rt, s, n):
    """``n`` distinct keys that route to shard ``s`` (map keys must differ)."""
    out, k = [], 0
    for _ in range(n):
        k = rt.key_for_shard(s, k)
        out.append(k)
        k += 1
    return out


def _phase(rt, kind, token, write, shards=(0,)):
    """One announce + combine of ``B`` ops on each of ``shards``: writes
    (values ``100 * token + i``) or table-preserving ops."""
    op = OPS[kind][0 if write else 1]
    keys, params = [], []
    for s in shards:
        keys += _keys(rt, s, B)
        params += [float(100 * token + i) for i in range(B)]
    rt.announce(0, keys, [op] * len(keys), params if write else [0.0] * len(keys),
                token=token)
    rt.combine_phase()


def _leaves(rt, s):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(rt._shard_state(s))]


def _is_file_leaf(arr):
    return arr.ndim > 0 and arr.shape[0] == CAP


def _assert_same_leaves(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def _durable_leaf(fs, slot, i):
    raw = fs.read_durable(f"{slot}/leaf_{i}.npy")
    return None if raw is None else np.load(io.BytesIO(raw))


@pytest.mark.parametrize("kind", KINDS)
def test_touched_shard_writes_manifest_and_changed_capacity_leaves(tmp_path, kind):
    """A touched shard's slot writes are exactly ``meta.json`` plus each
    capacity-sized leaf whose bytes differ from the slot's durable copy; an
    untouched shard writes nothing."""
    fs = SimFS(tmp_path)
    rt = _fabric(fs, kind)
    log = []
    real_write = fs.write

    def spy(rel, data, tag=None):
        log.append(rel)
        real_write(rel, data, tag=tag)

    fs.write = spy
    schedule = [True, False, False, True, False]  # write, keep, keep, write, keep
    n_file_leaves = sum(_is_file_leaf(a) for a in _leaves(rt, 0))
    n_changed = []
    for token, write in enumerate(schedule, start=1):
        slot = rt._slot_dir(0, int(rt.shard_epochs()[0]), nxt=True)
        before = [_durable_leaf(fs, slot, i) for i in range(len(_leaves(rt, 0)))]
        log.clear()
        _phase(rt, kind, token, write)
        after = _leaves(rt, 0)
        changed = {
            f"{slot}/leaf_{i}.npy"
            for i, a in enumerate(after)
            if _is_file_leaf(a) and (before[i] is None or before[i].tobytes() != a.tobytes())
        }
        slot_writes = [r for r in log if r.startswith("shard_")]
        assert sorted(r for r in slot_writes if "/slot" in r) == sorted(
            {f"{slot}/meta.json"} | changed
        ), (token, slot_writes)
        assert not any(r.startswith("shard_1/") for r in log)
        n_changed.append(len(changed))
    # the schedule has phases that write capacity leaves and phases that
    # elide every one of them
    assert n_file_leaves == (3 if kind == "map" else 1)
    assert 0 in n_changed and max(n_changed) > 0


@pytest.mark.parametrize("kind", KINDS)
def test_inline_leaves_recover_bit_identical(tmp_path, kind):
    """Every leaf, inline or file, comes back from ``recover`` with the same
    dtype, shape and bytes; the manifest holds the small ones inline."""
    fs = SimFS(tmp_path)
    rt = _fabric(fs, kind)
    for token, write in enumerate([True, True, False], start=1):
        _phase(rt, kind, token, write, shards=(0, 1))
    want = [_leaves(rt, s) for s in range(S)]
    for s in range(S):
        slot = rt._slot_dir(s, int(rt.shard_epochs()[s]), nxt=False)
        meta = json.loads(fs.read_durable(f"{slot}/meta.json"))
        for entry, leaf in zip(meta["leaves"], want[s]):
            assert ("file" in entry) == _is_file_leaf(leaf)
            assert ("data" in entry) != ("file" in entry)
            assert entry["dtype"] == str(leaf.dtype) and entry["shape"] == list(leaf.shape)
    rt2 = _recover(fs.crash(), kind)
    for s in range(S):
        _assert_same_leaves(_leaves(rt2, s), want[s])
        assert rt2.shard_contents(s) == rt.shard_contents(s)


def _to_old_layout(fs, slot):
    """Rewrite a durable slot in the older form: every leaf a ``.npy`` file."""
    meta = json.loads(fs.read_durable(f"{slot}/meta.json"))
    for i, entry in enumerate(meta["leaves"]):
        if "data" in entry:
            arr = np.frombuffer(base64.b64decode(entry.pop("data")),
                                dtype=entry["dtype"]).reshape(entry["shape"])
            buf = io.BytesIO()
            np.save(buf, arr)
            fs.write(f"{slot}/leaf_{i}.npy", buf.getvalue())
            entry["file"] = f"leaf_{i}.npy"
    fs.write(f"{slot}/meta.json", json.dumps(meta).encode())
    fs.fsync()


@pytest.mark.parametrize("kind", KINDS)
def test_old_layout_slot_still_recovers(tmp_path, kind):
    """A store whose active slots list every leaf as a file recovers to the
    same state; once new phases rewrite both slots, the next recovery
    collects the old form's stale small-leaf files."""
    fs = SimFS(tmp_path)
    rt = _fabric(fs, kind)
    for token, write in enumerate([True, True, False], start=1):
        _phase(rt, kind, token, write, shards=(0, 1))
    want = [_leaves(rt, s) for s in range(S)]
    for s in range(S):
        for nxt in (False, True):
            _to_old_layout(fs, rt._slot_dir(s, int(rt.shard_epochs()[s]), nxt=nxt))
    small = [i for i, a in enumerate(want[0]) if not _is_file_leaf(a)]
    assert small

    rt2 = _recover(fs.crash(), kind)
    for s in range(S):
        _assert_same_leaves(_leaves(rt2, s), want[s])
    for token, write in enumerate([True, False], start=10):
        _phase(rt2, kind, token, write, shards=(0, 1))
    want = [_leaves(rt2, s) for s in range(S)]
    fs3 = rt2.fs.crash()
    for s in range(S):  # stale files of the old form are still on the store
        assert fs3.exists(f"shard_{s}/slot0/leaf_{small[0]}.npy") or fs3.exists(
            f"shard_{s}/slot1/leaf_{small[0]}.npy")
    rt3 = _recover(fs3, kind)
    for s in range(S):
        _assert_same_leaves(_leaves(rt3, s), want[s])
        for p in (0, 1):
            for i in small:
                assert not fs3.exists(f"shard_{s}/slot{p}/leaf_{i}.npy")


def _run_to_crash(root, kind, crash_at):
    """Two warm phases on both shards, then a third (a write phase) with a
    crash at its ``crash_at``-th persistence op (``None``: no crash)."""
    inj = FaultInjector()
    fs = SimFS(root, inj)
    rt = _fabric(fs, kind)
    _phase(rt, kind, 1, True, shards=(0, 1))
    _phase(rt, kind, 2, False, shards=(0, 1))
    old = [_leaves(rt, s) for s in range(S)]
    start = inj.count
    if crash_at is not None:
        inj.crash_at = start + crash_at
    try:
        _phase(rt, kind, 3, True, shards=(0, 1))
    except CrashNow:
        return True, old, None, fs, inj.count - start
    return False, old, [_leaves(rt, s) for s in range(S)], fs, inj.count - start


@pytest.mark.parametrize("kind", KINDS)
def test_crash_at_every_persistence_op_of_a_phase(tmp_path, kind):
    """A crash at each persistence op of one phase (announce, slot writes,
    responses, the phase pfence, each shard's epoch commit) recovers every
    shard wholly at its old or wholly at its new state, leaf for leaf."""
    crashed, old, new, _, total = _run_to_crash(tmp_path / "dry", kind, None)
    assert not crashed and total > 10
    seen = set()
    for k in range(1, total + 1):
        crashed, old_k, _, fs, _ = _run_to_crash(tmp_path / f"k{k}", kind, k)
        assert crashed
        _assert_same_leaves(old_k[0], old[0])
        rt2 = _recover(fs.crash(), kind)
        for s in range(S):
            got = _leaves(rt2, s)
            is_new = all(g.tobytes() == n.tobytes() for g, n in zip(got, new[s]))
            if not is_new:
                _assert_same_leaves(got, old[s])
            seen.add(is_new)
    assert seen == {False, True}


@pytest.mark.parametrize("kind", KINDS)
def test_slot_leaves_inlined_counts_touched_shards_small_leaves(tmp_path, kind):
    """``slot_leaves_inlined`` (per shard, observer counter) adds the small
    leaves of every touched shard, phase by phase; written + elided +
    inlined covers every leaf of every touched shard."""
    obs = FabricObserver()
    rt = _fabric(SimFS(tmp_path), kind, obs=obs)
    leaves = _leaves(rt, 0)
    n_small = sum(not _is_file_leaf(a) for a in leaves)
    assert n_small == 2
    touched = {0: 0, 1: 0}
    for token, (write, shards) in enumerate(
        [(True, (0, 1)), (False, (0,)), (True, (1,)), (False, (0, 1))], start=1
    ):
        _phase(rt, kind, token, write, shards=shards)
        for s in shards:
            touched[s] += 1
    counters = obs.metrics.snapshot()["counters"]
    for s in range(S):
        assert counters[f"slot_leaves_inlined{{shard={s}}}"] == touched[s] * n_small
        total = sum(counters.get(f"slot_leaves_{c}{{shard={s}}}", 0)
                    for c in ("written", "elided", "inlined"))
        assert total == touched[s] * len(leaves)
