"""Sharded multi-object DFC runtime: one announcement fabric, many objects.

The paper's Figure-3 result is that flat combining amortizes the expensive
persistence instructions (pwb/pfence) across every op announced in a phase
(Algorithm 2's REDUCE + the single pfence of line 80).  This runtime
amortizes across *objects* too, the way a serving tier shards traffic:
``n_shards`` DFC structures — since PR 3 a MIXED population of stacks,
queues and deques — live behind ONE announcement fabric, a key->shard router
buckets each announced batch into per-shard op lists, and a fused dispatch
runs every shard's combining phase grouped BY KIND (``vmap`` per kind for
the jnp backend, one Pallas grid per kind — program instance = shard — for
the kernel backends; see ``dfc_hetero_combine_step``).

Paper mechanisms reused at fabric scale (citations follow the repo
convention: Algorithm/Figure/line numbers of arXiv:2012.12868):

  * announce (Alg. 1 lines 2-12): per-thread double-buffered announcement
    records (``ann{0,1}`` + a 2-bit ``valid`` selector, MSB published last),
  * combine + single pfence (Alg. 2, line 80): one durable phase persists
    every touched shard's new state and every combined response, then
    pfences ONCE,
  * two-increment epoch commit (Alg. 1 lines 81-83): per SHARD — persist
    cEpoch=v+1, publish v+2 unsynced; recovery rounds odd up to even
    (lines 28-30),
  * detectability (§1, Alg. 1 lines 26-43): recovery reports, per thread and
    per op, whether the op took effect and with which response,
  * recovery GC (§4): unreachable slot files of interrupted phases are
    deleted, like the paper's volatile-bitmap node reclamation.

State layout (see ``repro.core.jax_dfc.init_sharded``): shards of the same
kind form one stacked pytree (leading shard axis on every leaf), and the
fabric is a ``{kind: stacked_state}`` group dict.  Crucially ``epoch[S]`` is
per shard: shards commit independently; a combine phase only advances the
epoch of shards that actually received ops, so persistence work scales with
touched shards, not with ``n_shards``.

Routing (PR 3: now table-driven and re-shardable): a key hashes to a BUCKET
(multiplicative hashing, ``key * 2654435761``), and an ``i32[n_buckets]``
routing table maps buckets to shards.  The default table is the identity
(``bucket % n_shards`` with ``n_buckets == n_shards``) — bit-identical to
the PR-2 router.  The lane of an op within its shard is its *batch-order
rank* among the ops routed there (an exclusive prefix sum over the shard
one-hot matrix).  Both are order-preserving and independent of array layout
or backend, so the routed per-shard op lists — and therefore the combined
linearization — are bit-identical across jnp / Pallas backends and across
host replays: the flat batch order IS the announcement order.  Overflowing
ops (rank >= lanes) are cleanly rejected with ``R_OVERFLOW`` before touching
any shard, so one hot shard can never corrupt a neighbor.

Dynamic resharding (``split_shard`` / ``merge_shards``): the routing table
itself is a persistent object committed with the SAME two-increment protocol
as the shards (``routing/rEpoch``; double-buffered ``routing/slot{0,1}``
records picked by epoch parity).  A reshard is a mini-transaction:

  1. drain ready announcements (one ordinary combine phase),
  2. checkpoint the donor shard via ``DFCCheckpointManager.combine_structure``
     (a detectable typed snapshot under ``reshard/ckpt``, same SimFS so fault
     sweeps tick through it),
  3. persist a reshard INTENT record, pfence,
  4. pwb the post-reshard shard states into their inactive slots (merge
     only) and the new routing record into the inactive routing slot, ONE
     pfence,
  5. commit ``rEpoch`` with the two-increment protocol — THE commit point,
  6. roll the touched shards' cEpochs forward (merge only), drop the intent.

A crash before step 5's first fsync aborts the reshard (old routing + old
shard states; the per-shard GC reclaims the orphaned slot writes); a crash
after it commits (recovery rolls shard cEpochs forward from the intent).
Either way detectability verdicts recorded before the reshard stay valid —
they name (shard, target-epoch) pairs, and shard ids are never reused.
In-flight announcements that missed the drain are reported not-applied and
can be replayed with ``replay_pending``, giving exactly-once semantics per
op across reshards and crashes.

Pipelined durable path (ISSUE 4, after Fatourou et al. 2021/2024: overlap
the combiner's durable writes with the collection of the next batch):

  * device-side announcement queues — ``announce`` lands each batch's
    payload in a preallocated jnp ring (``repro.core.jax_dfc.AnnounceRing``)
    so combining phases consume device arrays directly; SimFS keeps only the
    compact durable mirror recovery needs, off the hot path,
  * depth-D pipelining (``depth=D``; the legacy ``pipeline=True`` flag is
    ``depth=2``, ISSUE 5 generalizes the ISSUE-4 two-stage special case) —
    ``combine_phase`` DISPATCHES the device combine for the newly collected
    chain (stage 1), then retires the OLDEST dispatched chains — persist +
    pfence + per-shard epoch commits, strictly in commit order — until at
    most D-1 remain in flight (stage 2) while the device works; ``flush``
    retires the rest.  Every in-flight chain carries its own per-batch
    epochs, and a thread's double-buffered announcement records bound it to
    two outstanding batches: ``announce`` force-retires chains (still in
    commit order) before reclaiming a slot whose batch is un-retired, so
    deep pipelines keep serial-identical pwb/pfence counts.  The
    two-increment commit still gates visibility: an in-flight chain that
    never retires is reported not-applied by ``recover`` (which also
    resolves a thread's OLDER announcement slot — the predecessor batch k
    whose successor k+1 was already announced — and ``replay_pending``
    replays it first),
  * multi-batch chaining (``chain=N``) — up to N ready batches combine in
    ONE fused dispatch (``dfc_sharded_multi_combine_step``: a ``lax.scan``
    over the batch axis, vmap or Pallas grid per kind) but persist and
    commit batch-by-batch, so pwb/pfence counts match that many serial
    phases exactly,
  * dirty-leaf persist elision — a slot leaf whose bytes already sit
    durably in that slot is not re-written (the paper's dirty-word
    tracking at leaf granularity); the slot manifest still lists it.

Persistence layout (``SimFS``-backed, pwb=write / pfence=fsync):

  tAnn/thread_{t}/ann{0,1}.json   double-buffered announcements + valid
  shard_{s}/slot{0,1}/...         alternating state slots, picked by parity
  shard_{s}/cEpoch                per-shard two-increment commit
  routing/slot{0,1}.json          alternating routing records
  routing/rEpoch                  routing-epoch two-increment commit
  reshard/intent.json             reshard transaction record
  reshard/ckpt/...                donor snapshots (DFCCheckpointManager)
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import io
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint.dfc_checkpoint import BOT, DFCCheckpointManager, SimFS
from repro.obs import (
    EV_ANNOUNCE,
    EV_DISPATCH,
    EV_DRAIN,
    EV_EPOCH,
    EV_RECOVER,
    EV_RESHARD,
    EV_RETIRE,
    EV_VERDICT,
    NULL_OBS,
)
from repro.core.jax_dfc import (
    KIND_CODES,
    LANE_HEAD,
    LANE_NONE,
    LANE_TAIL,
    OP_NONE,
    PhaseIntents,
    R_NONE,
    STRUCTS,
    init_announce_ring,
    lane_of_ops_host,
    init_sharded,
    ring_announce,
    ring_announce_phases,
    ring_drain,
    ring_drain_phases,
    ring_has_room,
    shard_slice,
    stack_shards,
    state_from_contents,
)
from repro.kernels.dfc_reduce.ops import (
    _one_sharded_combine,
    dfc_hetero_combine_step,
    dfc_hetero_multi_combine_step,
    dfc_hetero_multi_phase_step,
)

# runtime-level response kind: op rejected because its shard's announcement
# lanes were full this phase — never applied, safe to re-announce.
R_OVERFLOW = 4

# ---------------------------------------------------------------------------
# Per-side combiners (ISSUE 8, after Persistent Software Combining 2107.03492
# and Highly-Efficient Persistent FIFO Queues 2402.17674): with
# ``split_lanes=True`` every queue/deque shard commits through TWO
# announcement lanes — a HEAD lane (consuming side: OP_DEQ / OP_POPL,
# plus OP_PUSHL which also lives on the deque's left end) and a TAIL lane
# (producing side: OP_ENQ / OP_PUSHR / OP_POPR) — each with its own durable
# record, its own epoch, and its own one-pfence-per-phase commit, so
# opposite-side traffic never shares a persistence barrier:
#
#   shard_{s}/laneH{0,1}/rec.json [+ values.npy]   head-lane slots
#   shard_{s}/laneT{0,1}/rec.json + values.npy     tail-lane slots
#   shard_{s}/cEpoch = "[eH, eT]"                  composite epoch pair
#
# Each lane's slot parity follows ITS OWN epoch; the composite cEpoch file
# makes the pair atomic (SimFS file writes are all-or-nothing), which is what
# the drained-queue HANDOFF commit relies on: a phase that mixes both sides —
# or a head-side phase that drains the queue to empty, i.e. the moment the
# head lane's pops catch the tail lane's pushes — commits BOTH lanes in one
# two-increment step ([eH+1, eT+1] -> fsync -> [eH+2, eT+2]), the same
# discipline resharding uses, so recovery resolves a crash on either side of
# it (before the fsync: both lanes roll back together; after: both round up).
#
# ``values`` ownership per lane: the queue's head lane never writes values
# (pops only advance the head counter), so its record is a single tiny JSON —
# that asymmetry is the pwb/op win the jitter test pins.  The deque's LEFT
# side pushes into values too, so both deque lane records carry values (with
# dirty-leaf elision); recovery picks the values of the lane whose record
# carries the larger ``phases`` counter (a per-shard commit sequence number),
# which is the chronologically last committed copy.
_LANE_WRITES_VALUES = {"queue": (False, True), "deque": (True, True)}
_LANE_TAGS = ("H", "T")  # indexed by LANE_HEAD / LANE_TAIL


class StaleTokenError(LookupError):
    """``read_responses(thread, token)`` named a batch whose durable response
    record no longer exists: the double-buffered announcement slots retain
    only a thread's last two batches, and ``token`` predates both.  Distinct
    from the ``None`` return (batch announced but not yet retired) so a
    caller polling an overwritten token fails loudly instead of spinning —
    read a batch's responses before announcing two successors, or keep your
    own copy."""

_HASH_MULT = 2654435761  # Knuth multiplicative hashing constant


# ===================================================================== router
def shard_of_keys(keys, n_shards: int):
    """bucket(key): multiplicative hash, identical on host and device.

    With the identity routing table (the default) bucket == shard, which is
    why this keeps its historical name; table-driven fabrics compose it with
    a table lookup (see ``route_batch``).
    """
    k = jnp.asarray(keys).astype(jnp.uint32)
    h = k * jnp.uint32(_HASH_MULT)
    h = h ^ (h >> jnp.uint32(16))
    return (h % jnp.uint32(n_shards)).astype(jnp.int32)


def shard_of_keys_host(keys, n_shards: int) -> np.ndarray:
    """NumPy twin of ``shard_of_keys`` for oracles and drivers."""
    k = np.asarray(keys).astype(np.uint32)
    h = k * np.uint32(_HASH_MULT)
    h = h ^ (h >> np.uint32(16))
    return (h % np.uint32(n_shards)).astype(np.int32)


def route_keys_host(keys, n_shards: int, table=None) -> np.ndarray:
    """Host routing: bucket hash + optional table lookup (oracle twin of the
    device path in ``route_batch``)."""
    if table is None:
        return shard_of_keys_host(keys, n_shards)
    table = np.asarray(table)
    return table[shard_of_keys_host(keys, len(table))].astype(np.int32)


def zipf_keys(rng, n: int, universe: int, skew: float) -> np.ndarray:
    """Zipfian key draw over a finite universe (skew=0 -> uniform) — the
    serving-style workload used by the traffic driver and benchmarks."""
    ranks = np.arange(1, universe + 1, dtype=np.float64)
    p = ranks ** (-skew) if skew > 0 else np.ones(universe)
    p /= p.sum()
    return rng.choice(universe, size=n, p=p)


def weighted_cycle(weights) -> List[int]:
    """The deterministic weighted-round-robin cycle over priority classes.

    Class ``c`` (higher = more urgent) appears ``weights[c]`` times; classes
    are laid out highest-first with each class's slots CONTIGUOUS, so within
    one cycle the urgent classes drain their whole credit burst before the
    next class starts, and the lowest class's credits sit at the cycle's
    tail.  The contiguity is what makes the starvation bound of
    :func:`weighted_dequeue_plan` tight: between two credits of class ``c``
    there are exactly ``sum(weights) - weights[c]`` foreign credits.
    """
    ws = [int(w) for w in weights]
    if not ws or any(w < 1 for w in ws):
        raise ValueError(f"class weights must all be >= 1, got {list(weights)}")
    cyc: List[int] = []
    for c in range(len(ws) - 1, -1, -1):
        cyc.extend([c] * ws[c])
    return cyc


def weighted_dequeue_plan(
    backlogs, weights, n: int, cursor: int = 0
) -> Tuple[List[int], int]:
    """Plan ``n`` dequeues across per-class shards by weighted round-robin.

    ``backlogs[c]`` is class ``c``'s committed shard backlog, ``weights[c]``
    its per-cycle dequeue credit, ``cursor`` the persistent position in the
    weighted cycle (thread it through successive calls).  Returns
    ``(plan, new_cursor)`` where ``plan`` lists the class shard to dequeue
    for each of up to ``n`` slots.  The walk is WORK-CONSERVING: a credit
    landing on an empty class is skipped (the slot goes to the next
    backlogged class in cycle order), so the plan emits
    ``min(n, sum(backlogs))`` dequeues.

    Starvation bound (the serving tier's acceptance gate): a class that
    stays backlogged is visited at least ``weights[c]`` times per full
    cycle, and every OTHER emitted dequeue consumes one of the cycle's
    ``W - weights[c]`` foreign credits (``W = sum(weights)``; skipped
    credits emit nothing) — so between two consecutive dequeues of a
    backlogged class ``c`` at most ``W - weights[c]`` other dequeues are
    emitted, across plan-call boundaries, for ANY backlog mix.  For the
    lowest class that is the bound ``W - weights[0]``.
    """
    left = [int(b) for b in backlogs]
    cyc = weighted_cycle(weights)
    if len(left) != len(set(cyc)):
        raise ValueError(
            f"backlogs ({len(left)} classes) must parallel weights "
            f"({len(set(cyc))} classes)"
        )
    W = len(cyc)
    cursor = int(cursor) % W
    plan: List[int] = []
    while len(plan) < n and any(v > 0 for v in left):
        c = cyc[cursor]
        cursor = (cursor + 1) % W
        if left[c] > 0:
            plan.append(c)
            left[c] -= 1
    return plan, cursor


@functools.partial(jax.jit, static_argnames=("n_shards", "lanes"))
def route_batch(keys, ops, params, *, n_shards: int, lanes: int, table=None):
    """Bucket a flat announced batch into per-shard op lists.

    Returns ``(shard_ops i32[S, L], shard_params f32[S, L], shard i32[B],
    lane i32[B], ok bool[B], overflow bool[B], shard_keys i32[S, L])``.
    ``shard_keys`` mirrors ``shard_ops``: each routed op's announced key in
    its landed lane (keyed kinds — the map — interpret it; ring kinds ignore
    it).  ``table`` (``i32[n_buckets]``, bucket -> shard) routes through the
    resharding-aware table; ``None`` is
    the identity table (bucket == shard, the PR-2 behavior).  Lane assignment
    is the op's batch-order rank among ops routed to its shard (stable: an
    exclusive segment prefix sum over the shard one-hot matrix), so per-shard
    op lists preserve announcement order deterministically.  Ops ranked past
    ``lanes`` overflow: they are dropped before touching any per-shard list.
    OP_NONE lanes are never routed.
    """
    b = ops.shape[0]
    if table is None:
        shard = shard_of_keys(keys, n_shards)
    else:
        shard = table[shard_of_keys(keys, table.shape[0])]
    active = ops != OP_NONE
    s_eff = jnp.where(active, shard, n_shards)  # n_shards == routed nowhere

    # stable rank of op j within its shard: exclusive prefix sum per segment
    onehot = (s_eff[None, :] == jnp.arange(n_shards)[:, None]).astype(jnp.int32)
    rank_mat = jnp.cumsum(onehot, axis=1) - 1  # [S, B]
    lane = rank_mat[jnp.clip(s_eff, 0, n_shards - 1), jnp.arange(b)]

    ok = active & (lane < lanes)
    overflow = active & (lane >= lanes)

    # scatter into the per-shard announcement matrices; dest is injective
    # over ok lanes, so the scatter is order-independent (deterministic)
    dest = jnp.where(ok, s_eff * lanes + lane, n_shards * lanes)
    flat_ops = (
        jnp.full((n_shards * lanes,), OP_NONE, jnp.int32)
        .at[dest]
        .set(ops.astype(jnp.int32), mode="drop")
    )
    flat_params = (
        jnp.zeros((n_shards * lanes,), jnp.float32)
        .at[dest]
        .set(params.astype(jnp.float32), mode="drop")
    )
    flat_keys = (
        jnp.zeros((n_shards * lanes,), jnp.int32)
        .at[dest]
        .set(jnp.asarray(keys).astype(jnp.int32), mode="drop")
    )
    return (
        flat_ops.reshape(n_shards, lanes),
        flat_params.reshape(n_shards, lanes),
        shard,
        lane,
        ok,
        overflow,
        flat_keys.reshape(n_shards, lanes),
    )


# ============================================================ fused step (jit)
@functools.partial(
    jax.jit, static_argnames=("kind", "n_shards", "lanes", "backend")
)
def sharded_step(
    state, keys, ops, params, meta, *, kind: str, n_shards: int, lanes: int,
    backend: str = "jnp",
):
    """One fused end-to-end phase over a HOMOGENEOUS fabric (PR-2 entry
    point, kept for direct users; ``ShardedDFCRuntime`` itself now always
    goes through ``hetero_step``).

    ``meta`` is the per-shard combiner metadata ``{"phases": i32[S],
    "ops_combined": i32[S]}``; untouched shards keep their old state (and old
    epoch — no phantom phases), touched shards publish with a +2 epoch bump.
    Returns ``(new_state, new_meta, responses f32[B], kinds i32[B])`` where
    ``kinds`` uses the combine-level codes plus ``R_OVERFLOW``.
    """
    shard_ops, shard_params, shard, lane, ok, overflow, shard_keys = route_batch(
        keys, ops, params, n_shards=n_shards, lanes=lanes
    )

    combined, s_resp, s_kinds = _one_sharded_combine(
        kind, backend, state, shard_ops, shard_params, keys=shard_keys
    )

    # only shards that received ops publish; the rest keep state AND epoch
    touched = jnp.any(shard_ops != OP_NONE, axis=1)  # bool[S]

    def _select(new_leaf, old_leaf):
        t = touched.reshape((n_shards,) + (1,) * (new_leaf.ndim - 1))
        return jnp.where(t, new_leaf, old_leaf)

    new_state = jax.tree_util.tree_map(_select, combined, state)
    new_meta = dict(meta)  # carry extra columns (e.g. "kind") through
    new_meta["phases"] = meta["phases"] + touched.astype(jnp.int32)
    new_meta["ops_combined"] = meta["ops_combined"] + jnp.sum(
        (shard_ops != OP_NONE).astype(jnp.int32), axis=1
    )

    # gather responses back to flat batch order
    s = jnp.clip(shard, 0, n_shards - 1)
    ln = jnp.clip(lane, 0, lanes - 1)
    responses = jnp.where(ok, s_resp[s, ln], 0.0)
    kinds = jnp.where(ok, s_kinds[s, ln], R_NONE)
    kinds = jnp.where(overflow, R_OVERFLOW, kinds)
    return new_state, new_meta, responses, kinds


@functools.lru_cache(maxsize=None)
def _group_ids(kinds: Tuple[str, ...]) -> Dict[str, Tuple[int, ...]]:
    """Global shard ids per kind, in ascending shard order."""
    out: Dict[str, List[int]] = {}
    for s, k in enumerate(kinds):
        out.setdefault(k, []).append(s)
    return {k: tuple(v) for k, v in out.items()}


def _split_groups(x, gids, axis: int):
    """``{kind: x's rows of that kind}`` along the shard ``axis``.

    Kind groups are static, so this is a static slice per contiguous group
    (a constant-index take otherwise): scatters and gathers over the shard
    axis of a kind group crash the TPU compiler's fused-scatter emitter
    for some group shapes (4 queue shards x 128 lanes, jax 0.9.0)."""
    out = {}
    for k, ids in gids.items():
        if ids == tuple(range(ids[0], ids[-1] + 1)):
            out[k] = jax.lax.slice_in_dim(x, ids[0], ids[-1] + 1, axis=axis)
        else:
            out[k] = jnp.take(x, np.asarray(ids), axis=axis)
    return out


def _merge_groups(parts, gids, axis: int):
    """Inverse of :func:`_split_groups`: per-kind blocks back in global
    shard order along ``axis``."""
    order = [s for k in sorted(gids) for s in gids[k]]
    merged = jnp.concatenate([parts[k] for k in sorted(gids)], axis=axis)
    if order == list(range(len(order))):
        return merged
    return jnp.take(merged, np.argsort(order), axis=axis)


@functools.partial(jax.jit, static_argnames=("kinds", "lanes", "backend"))
def hetero_step(
    groups, table, keys, ops, params, meta, *, kinds: Tuple[str, ...],
    lanes: int, backend: str = "jnp",
):
    """One fused end-to-end phase over a HETEROGENEOUS fabric.

    ``groups`` maps each structure kind to its shard-stacked state;
    ``kinds`` (static) is the per-shard kind tuple and ``table`` the
    bucket->shard routing table.  The combine is STRUCTS-dispatched per kind
    group (``dfc_hetero_combine_step``): one vmap or one Pallas grid per kind
    present, program instances grouped by kind.  Op codes are interpreted by
    the TARGET shard's structure (a code-3 op is OP_PUSHR on a deque shard
    and falls through to R_NONE on a stack/queue shard).

    Returns ``(new_groups, new_meta, responses f32[B], out_kinds i32[B])``.
    """
    n_shards = len(kinds)
    shard_ops, shard_params, shard, lane, ok, overflow, shard_keys = route_batch(
        keys, ops, params, n_shards=n_shards, lanes=lanes, table=table
    )

    gids = _group_ids(kinds)
    group_ops = _split_groups(shard_ops, gids, 0)
    combined = dfc_hetero_combine_step(
        groups, group_ops, _split_groups(shard_params, gids, 0),
        backend=backend, group_keys=_split_groups(shard_keys, gids, 0),
    )

    new_groups = {}
    for k, ids in gids.items():
        new_state = combined[k][0]
        g_touched = jnp.any(group_ops[k] != OP_NONE, axis=1)

        def _select(new_leaf, old_leaf, t=g_touched, m=len(ids)):
            tt = t.reshape((m,) + (1,) * (new_leaf.ndim - 1))
            return jnp.where(tt, new_leaf, old_leaf)

        new_groups[k] = jax.tree_util.tree_map(_select, new_state, groups[k])
    resp_mat = _merge_groups({k: c[1] for k, c in combined.items()}, gids, 0)
    kind_mat = _merge_groups({k: c[2] for k, c in combined.items()}, gids, 0)

    touched = jnp.any(shard_ops != OP_NONE, axis=1)
    new_meta = dict(meta)
    new_meta["phases"] = meta["phases"] + touched.astype(jnp.int32)
    new_meta["ops_combined"] = meta["ops_combined"] + jnp.sum(
        (shard_ops != OP_NONE).astype(jnp.int32), axis=1
    )

    s = jnp.clip(shard, 0, n_shards - 1)
    ln = jnp.clip(lane, 0, lanes - 1)
    responses = jnp.where(ok, resp_mat[s, ln], 0.0)
    out_kinds = jnp.where(ok, kind_mat[s, ln], R_NONE)
    out_kinds = jnp.where(overflow, R_OVERFLOW, out_kinds)
    return new_groups, new_meta, responses, out_kinds


@functools.partial(
    jax.jit, static_argnames=("kinds", "lanes", "backend", "unroll")
)
def hetero_multi_step(
    groups, table, keys, ops, params, meta, *, kinds: Tuple[str, ...],
    lanes: int, backend: str = "jnp", unroll: int = 1,
):
    """Route + combine a CHAIN of flat batches over a heterogeneous fabric in
    ONE dispatch (the pipelined durable path's combine stage).

    ``keys`` / ``ops`` / ``params`` are ``[B, L]`` — B flat batches padded to
    a common length with ``OP_NONE`` lanes (never routed).  Each batch is
    routed independently and the B per-shard announcement matrices are
    chained through ``dfc_sharded_multi_combine_step`` per kind group: batch
    b+1 combines on top of batch b's post-combine state, exactly as B
    separate ``hetero_step`` calls would, but the chain costs one dispatch.
    All-``OP_NONE`` batches (chain padding) pass through untouched, and
    ``unroll`` (static; the caller passes its pipeline depth) unrolls the
    underlying scan that many batches per step.

    Returns ``(new_groups, new_meta, responses [B, L], out_kinds [B, L],
    states, epochs_before i32[S], epochs i32[B, S], phases_cum i32[B, S],
    ops_cum i32[B, S])`` where ``states[kind]`` carries the per-batch
    shard-stacked states (leading B axis — what the durable path persists
    per batch) and ``epochs[b]`` the per-shard epochs after batch b (each
    op's durable commit target).
    """
    n_batches = ops.shape[0]
    n_shards = len(kinds)
    routed = [
        route_batch(
            keys[i], ops[i], params[i],
            n_shards=n_shards, lanes=lanes, table=table,
        )
        for i in range(n_batches)
    ]
    shard_ops = jnp.stack([r[0] for r in routed])  # [B, S, L]
    shard_params = jnp.stack([r[1] for r in routed])
    shard_keys = jnp.stack([r[6] for r in routed])

    gids = _group_ids(kinds)
    multi = dfc_hetero_multi_combine_step(
        groups, _split_groups(shard_ops, gids, 1),
        _split_groups(shard_params, gids, 1), backend=backend, unroll=unroll,
        group_keys=_split_groups(shard_keys, gids, 1),
    )

    states = {k: m[0] for k, m in multi.items()}
    new_groups = {
        k: jax.tree_util.tree_map(lambda leaf: leaf[-1], st)
        for k, st in states.items()
    }
    resp_mat = _merge_groups({k: m[1] for k, m in multi.items()}, gids, 1)
    kind_mat = _merge_groups({k: m[2] for k, m in multi.items()}, gids, 1)
    epochs = _merge_groups({k: st.epoch for k, st in states.items()}, gids, 1)
    epochs_before = _merge_groups(
        {k: groups[k].epoch for k in gids}, gids, 0
    )

    touched = jnp.any(shard_ops != OP_NONE, axis=2)  # [B, S]
    per_batch_ops = jnp.sum((shard_ops != OP_NONE).astype(jnp.int32), axis=2)
    new_meta = dict(meta)
    new_meta["phases"] = meta["phases"] + jnp.sum(touched.astype(jnp.int32), axis=0)
    new_meta["ops_combined"] = meta["ops_combined"] + jnp.sum(per_batch_ops, axis=0)
    # cumulative per-batch counters: what batch b's slot persist must record
    phases_cum = meta["phases"][None] + jnp.cumsum(touched.astype(jnp.int32), axis=0)
    ops_cum = meta["ops_combined"][None] + jnp.cumsum(per_batch_ops, axis=0)

    shard_b = jnp.stack([r[2] for r in routed])  # [B, L]
    lane_b = jnp.stack([r[3] for r in routed])
    ok_b = jnp.stack([r[4] for r in routed])
    ovf_b = jnp.stack([r[5] for r in routed])
    s = jnp.clip(shard_b, 0, n_shards - 1)
    ln = jnp.clip(lane_b, 0, lanes - 1)
    bi = jnp.arange(n_batches)[:, None]
    responses = jnp.where(ok_b, resp_mat[bi, s, ln], 0.0)
    out_kinds = jnp.where(ok_b, kind_mat[bi, s, ln], R_NONE)
    out_kinds = jnp.where(ovf_b, R_OVERFLOW, out_kinds)
    return (
        new_groups, new_meta, responses, out_kinds,
        states, epochs_before, epochs, phases_cum, ops_cum,
    )


def _hetero_phase_loop_impl(
    groups, table, keys, ops, params, meta, *, kinds: Tuple[str, ...],
    lanes: int, backend: str = "jnp", unroll: int = 1,
):
    """Trace body of :func:`hetero_phase_loop_step` (jitted twice below —
    once with the kind-group buffers donated, once without)."""
    n_shards = len(kinds)

    def _route(k1, o1, p1):
        return route_batch(
            k1, o1, p1, n_shards=n_shards, lanes=lanes, table=table
        )

    # route ALL K phases in one vmapped pass (no per-phase dispatch)
    (
        shard_ops, shard_params, shard_b, lane_b, ok_b, ovf_b, shard_keys
    ) = jax.vmap(_route)(
        keys, ops, params
    )  # [K, S, L], [K, S, L], [K, B], [K, B], ...

    gids = _group_ids(kinds)
    multi = dfc_hetero_multi_phase_step(
        groups, _split_groups(shard_ops, gids, 1),
        _split_groups(shard_params, gids, 1),
        backend=backend, unroll=unroll,
        group_keys=_split_groups(shard_keys, gids, 1),
    )

    k_phases = ops.shape[0]
    states = {k: m[0] for k, m in multi.items()}
    new_groups = {
        k: jax.tree_util.tree_map(lambda leaf: leaf[-1], st)
        for k, st in states.items()
    }
    resp_mat = _merge_groups({k: m[1] for k, m in multi.items()}, gids, 1)
    kind_mat = _merge_groups({k: m[2] for k, m in multi.items()}, gids, 1)
    intents = {k: m[3] for k, m in multi.items()}
    epochs = _merge_groups({k: i.epoch for k, i in intents.items()}, gids, 1)
    epochs_before = _merge_groups(
        {k: groups[k].epoch for k in gids}, gids, 0
    )
    touched_all = _merge_groups(
        {k: i.touched for k, i in intents.items()}, gids, 1
    )
    # re-base the dispatch-relative cumulative counters on the fabric's
    # durable meta: row k is then exactly what phase k's slot persists
    phases_cum = meta["phases"][None] + _merge_groups(
        {k: i.phases_cum for k, i in intents.items()}, gids, 1
    )
    ops_cum = meta["ops_combined"][None] + _merge_groups(
        {k: i.ops_cum for k, i in intents.items()}, gids, 1
    )

    new_meta = dict(meta)
    new_meta["phases"] = phases_cum[-1]
    new_meta["ops_combined"] = ops_cum[-1]

    s = jnp.clip(shard_b, 0, n_shards - 1)
    ln = jnp.clip(lane_b, 0, lanes - 1)
    ki = jnp.arange(k_phases)[:, None]
    responses = jnp.where(ok_b, resp_mat[ki, s, ln], 0.0)
    out_kinds = jnp.where(ok_b, kind_mat[ki, s, ln], R_NONE)
    out_kinds = jnp.where(ovf_b, R_OVERFLOW, out_kinds)
    intents_out = PhaseIntents(
        epoch=epochs, touched=touched_all,
        phases_cum=phases_cum, ops_cum=ops_cum,
    )
    return (
        new_groups, new_meta, responses, out_kinds,
        states, epochs_before, intents_out,
    )


_PHASE_LOOP_STATICS = ("kinds", "lanes", "backend", "unroll")
_phase_loop_step_plain = jax.jit(
    _hetero_phase_loop_impl, static_argnames=_PHASE_LOOP_STATICS
)
# donated variant: the old kind-group buffers are consumed by the dispatch,
# so stacked shard state never leaves the device between phases
_phase_loop_step_donated = jax.jit(
    _hetero_phase_loop_impl,
    static_argnames=_PHASE_LOOP_STATICS,
    donate_argnums=(0,),
)


def hetero_phase_loop_step(
    groups, table, keys, ops, params, meta, *, kinds: Tuple[str, ...],
    lanes: int, backend: str = "jnp", unroll: int = 1,
    donate: Optional[bool] = None,
):
    """Route + combine K PHASES over a heterogeneous fabric in ONE dispatch,
    accumulating each phase's persist intents device-side.

    ``keys`` / ``ops`` / ``params`` are ``[K, L]`` — K per-phase flat batches
    padded to a common lane count with ``OP_NONE``.  Each phase is routed
    independently (one vmapped routing pass) and the chain is fused through
    ``dfc_hetero_multi_phase_step`` per kind group: phase k+1 combines on
    top of phase k's post-combine state, exactly as K separate
    ``hetero_step`` calls would, but the whole schedule costs one dispatch
    and the stacked shard state never leaves the device between phases
    (``donate=True`` — the default off-CPU — additionally donates the old
    group buffers to the dispatch); see ``dfc_multi_phase_step``.

    Returns ``(new_groups, new_meta, responses [K, L], out_kinds [K, L],
    states, epochs_before i32[S], intents)`` where ``states[kind]`` carries
    the per-phase shard-stacked states (leading K axis) and ``intents`` is
    the :class:`~repro.core.jax_dfc.PhaseIntents` log with the cumulative
    counters already re-based on the fabric's durable ``meta`` — everything
    the host's intent drain needs to replay the serial persistence schedule.
    """
    if donate is None:
        donate = jax.default_backend() != "cpu"
    fn = _phase_loop_step_donated if donate else _phase_loop_step_plain
    return fn(
        groups, table, keys, ops, params, meta,
        kinds=kinds, lanes=lanes, backend=backend, unroll=unroll,
    )


# ============================================================== host oracle
def sequential_hetero_reference(
    kinds, shard_lists, keys, ops, params, lanes, table=None, capacity=None
):
    """Pure-Python witness of one heterogeneous sharded phase (test oracle).

    ``kinds[s]`` names shard ``s``'s structure; ``shard_lists[s]`` is its
    Python contents, mutated in place (a dict for keyed kinds).  Returns
    (responses, kinds) in flat batch order, with overflow ops reported as
    ``R_OVERFLOW`` and untouched.  ``capacity`` bounds keyed shards so the
    oracle models bucket-full rejection the same way the device does.
    """
    n_shards = len(shard_lists)
    shard = route_keys_host(keys, n_shards, table)
    b = len(ops)
    responses = [0.0] * b
    out_kinds = [R_NONE] * b
    buckets: Dict[int, List[int]] = {}
    for j in range(b):
        if ops[j] == OP_NONE:
            continue
        s = int(shard[j])
        rank = len(buckets.setdefault(s, []))
        if rank >= lanes:
            out_kinds[j] = R_OVERFLOW
            continue
        buckets[s].append(j)
    for s, idxs in sorted(buckets.items()):
        s_ops = [ops[j] for j in idxs]
        s_par = [params[j] for j in idxs]
        spec = STRUCTS[kinds[s]]
        if spec.keyed:
            s_keys = [keys[j] for j in idxs]
            shard_lists[s], s_resp, s_kinds = spec.reference(
                shard_lists[s], s_keys, s_ops, s_par, capacity=capacity
            )
        else:
            shard_lists[s], s_resp, s_kinds = spec.reference(
                shard_lists[s], s_ops, s_par
            )
        for r, (v, k) in zip(idxs, zip(s_resp, s_kinds)):
            responses[r] = v
            out_kinds[r] = k
    return responses, out_kinds


def sequential_sharded_reference(kind, shard_lists, keys, ops, params, lanes):
    """Homogeneous wrapper of ``sequential_hetero_reference`` (PR-2 API)."""
    return sequential_hetero_reference(
        (kind,) * len(shard_lists), shard_lists, keys, ops, params, lanes
    )


# ================================================================== runtime
def _init_meta(kinds: Sequence[str]):
    n_shards = len(kinds)
    return {
        "phases": jnp.zeros((n_shards,), jnp.int32),
        "ops_combined": jnp.zeros((n_shards,), jnp.int32),
        "kind": jnp.asarray([KIND_CODES[k] for k in kinds], jnp.int32),
    }


@dataclasses.dataclass
class OpVerdict:
    """Per-op detectability verdict reported by recovery."""

    applied: bool
    kind: Optional[int] = None
    resp: Optional[float] = None
    shard: Optional[int] = None


class ShardedDFCRuntime:
    """Many persistent DFC objects — possibly of MIXED kinds — behind one
    announcement fabric, with crash-consistent dynamic resharding.

    Volatile fast path: ``step(keys, ops, params)`` — one jitted dispatch.
    Durable path: threads ``announce`` batches; ``combine_phase`` combines
    every ready announcement across all shards and commits per-shard;
    ``recover`` rebuilds the fabric (topology included) after a crash and
    reports per-thread, per-op detectability verdicts; ``replay_pending``
    re-announces exactly the not-applied ops.  Resharding:
    ``split_shard`` / ``merge_shards`` (see the module docstring for the
    commit protocol).

    ``kind`` may be a single kind name (homogeneous fabric, PR-2 behavior —
    ``rt.state`` is then the one stacked pytree) or a per-shard sequence of
    kind names (``rt.state`` is the ``{kind: stacked_state}`` group dict).

    Contract (inherited from the combine layer): per shard,
    ``capacity >= committed size + lanes``.
    """

    def __init__(
        self,
        kind: Union[str, Sequence[str]],
        n_shards: int,
        capacity: int,
        lanes: int,
        *,
        backend: str = "jnp",
        fs: Optional[SimFS] = None,
        n_threads: int = 1,
        state=None,
        meta=None,
        n_buckets: Optional[int] = None,
        table=None,
        pipeline: bool = False,
        depth: Optional[int] = None,
        chain: int = 1,
        ring_slots: int = 2048,
        split_lanes: bool = False,
        obs=None,
    ):
        kinds = [kind] * n_shards if isinstance(kind, str) else list(kind)
        if len(kinds) != n_shards:
            raise ValueError("per-shard kind list must have n_shards entries")
        for k in kinds:
            if k not in STRUCTS:
                raise ValueError(f"unknown structure kind {k!r}")
        if lanes > capacity:
            raise ValueError("lanes must be <= per-shard capacity")
        self.kinds = kinds
        self.kind = kinds[0] if len(set(kinds)) == 1 else "mixed"
        self.n_shards = n_shards
        self.capacity = capacity
        self.lanes = lanes
        self.backend = backend
        self.fs = fs
        self.n_threads = n_threads
        self.n_buckets = int(n_buckets) if n_buckets is not None else n_shards
        if self.n_buckets < n_shards:
            raise ValueError("n_buckets must be >= n_shards")
        self.table = np.asarray(
            np.arange(self.n_buckets) % n_shards if table is None else table,
            np.int32,
        )
        if self.table.shape != (self.n_buckets,):
            raise ValueError("table must have n_buckets entries")
        self.r_epoch = 0  # routing epoch (even at rest)
        self._reshard_seq = 0
        # per-side combiners (ISSUE 8): when enabled, queue/deque shards
        # commit through independent head/tail lanes.  ``lane_epochs`` is the
        # host mirror of each split shard's committed ``[eH, eT]`` pair (even
        # at rest), advanced strictly in commit order by the retire/drain
        # paths; the device epoch stays free-running (+2 per touched phase)
        # and recovery rebuilds it as eH + eT.
        self.split_lanes = bool(split_lanes)
        self.lane_epochs: Dict[int, List[int]] = {}
        # --- pipelined durable path (ISSUE 4/5): device-side announcement
        # ring, a depth-D ring of in-flight chains, dirty-leaf persist elision.
        # ``depth`` is the pipeline depth: a combine_phase dispatches a fresh
        # chain and keeps up to depth-1 dispatched chains UN-retired (their
        # persists/commits deferred), so the device may be combining chain
        # k+D-1 while chain k's durable writes drain.  depth=1 is the serial
        # path; the legacy ``pipeline=True`` flag is depth=2 (the ISSUE-4
        # two-stage special case, now just a depth setting).
        if depth is None:
            depth = 2 if pipeline else 1
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.depth = int(depth)
        self.pipeline = self.depth > 1
        self.chain = max(1, int(chain))
        self.ring = init_announce_ring(ring_slots) if fs is not None else None
        self._ring_tail = 0  # host mirror of the ring's absolute tail
        self._ring_spans: Dict[int, Tuple[int, int]] = {}  # thread -> (start, n)
        self._live: Dict[int, Dict[str, Any]] = {}  # thread -> announcement rec
        # host mirror of each announcement slot's token — what the depth
        # guard in ``announce`` consults, so the hot path never re-reads the
        # durable record it is about to overwrite
        self._slot_tokens: Dict[Tuple[int, int], int] = {}
        # dispatched-but-unretired chains, oldest first (retire = commit
        # order); a deque so the three oldest-first drains (announce's depth
        # guard, combine_phase stage 2, flush) pop in O(1) instead of the
        # O(D) head-pop of a list — flush was O(D^2) per call and runs
        # inside _drain() before every reshard
        self._inflight: "collections.deque[Dict[str, Any]]" = collections.deque()
        # (thread, token) groups of the most recent dispatch, one tuple per
        # chained batch — the linearization witness drivers/oracles replay
        # (announcements grouped into one batch combine as ONE phase)
        self.last_dispatch: List[Tuple[Tuple[int, int], ...]] = []
        self._elide: Dict[str, bytes] = {}  # rel path -> durable leaf digest
        self._elide_pending: Dict[str, bytes] = {}
        if state is None:
            self.groups = {
                k: init_sharded(k, len(ids), capacity)
                for k, ids in _group_ids(tuple(kinds)).items()
            }
        else:
            self.state = state
        self.meta = _init_meta(kinds) if meta is None else meta
        # Observability (repro.obs): disabled no-op observer by default.  A
        # live observer is shared with the SimFS so persistence hooks, span
        # events, and metrics land in ONE timeline; the hooks run after the
        # counters/injector/durable work, so tracing cannot perturb the
        # protocol (the obs parity test pins this).
        self.obs = obs if obs is not None else NULL_OBS
        if fs is not None and self.obs.enabled:
            fs.obs = self.obs
            self.obs.event(
                "topology",
                kinds=list(kinds),
                n_shards=n_shards,
                n_buckets=self.n_buckets,
                capacity=capacity,
                lanes=lanes,
                depth=self.depth,
                chain=self.chain,
                split_lanes=self.split_lanes,
            )

    # ----------------------------------------------------- state as groups
    @property
    def state(self):
        """Single stacked pytree for homogeneous fabrics (PR-2 API), the
        ``{kind: stacked_state}`` group dict otherwise."""
        if len(self.groups) == 1:
            return next(iter(self.groups.values()))
        return self.groups

    @state.setter
    def state(self, value):
        if isinstance(value, dict):
            self.groups = dict(value)
        else:
            self.groups = {self.kinds[0]: value}

    def _row(self, s: int) -> int:
        """Local row of global shard ``s`` inside its kind group."""
        return _group_ids(tuple(self.kinds))[self.kinds[s]].index(s)

    def _shard_state(self, s: int):
        return shard_slice(self.groups[self.kinds[s]], self._row(s))

    def _set_shard_state(self, s: int, one) -> None:
        k, r = self.kinds[s], self._row(s)
        self.groups[k] = jax.tree_util.tree_map(
            lambda leaf, v: leaf.at[r].set(v), self.groups[k], one
        )

    def shard_epochs(self) -> np.ndarray:
        """Per-global-shard epochs gathered from the kind groups."""
        out = np.zeros((self.n_shards,), np.int64)
        for k, ids in _group_ids(tuple(self.kinds)).items():
            out[np.asarray(ids)] = np.asarray(self.groups[k].epoch)
        return out

    # ------------------------------------------------------------- routing
    def route(self, keys, ops, params):
        return route_batch(
            jnp.asarray(keys),
            jnp.asarray(ops, jnp.int32),
            jnp.asarray(params, jnp.float32),
            n_shards=self.n_shards,
            lanes=self.lanes,
            table=jnp.asarray(self.table),
        )

    def route_host(self, keys) -> np.ndarray:
        return route_keys_host(keys, self.n_shards, self.table)

    def key_for_shard(self, s: int, start: int = 0) -> int:
        """Smallest key >= ``start`` that routes to shard ``s`` under the
        current table (host-side search; drivers use it to address a specific
        shard, e.g. to drain one request queue)."""
        for base in range(start, start + (1 << 22), 4096):
            cand = np.arange(base, base + 4096, dtype=np.int64)
            hit = np.nonzero(self.route_host(cand) == s)[0]
            if hit.size:
                return int(cand[hit[0]])
        raise ValueError(f"no key routes to shard {s} (unrouted shard?)")

    # ------------------------------------------------------- volatile path
    def step(self, keys, ops, params):
        """One fused phase over a flat batch; returns (responses, kinds)."""
        self.groups, self.meta, resp, kinds = hetero_step(
            self.groups,
            jnp.asarray(self.table),
            jnp.asarray(keys),
            jnp.asarray(ops, jnp.int32),
            jnp.asarray(params, jnp.float32),
            self.meta,
            kinds=tuple(self.kinds),
            lanes=self.lanes,
            backend=self.backend,
        )
        return resp, kinds

    # -------------------------------------------------------- announcements
    def _ann_path(self, t: int, slot: int) -> str:
        return f"tAnn/thread_{t}/ann{slot}.json"

    def _valid_path(self, t: int) -> str:
        return f"tAnn/thread_{t}/valid"

    def _read_valid(self, t: int) -> int:
        raw = self.fs.read(self._valid_path(t))
        return int(raw.decode()) if raw else 0

    def _read_ann(self, t: int, slot: int) -> Dict[str, Any]:
        raw = self.fs.read(self._ann_path(t, slot))
        return json.loads(raw.decode()) if raw else {"val": BOT, "token": -1}

    def announce(self, thread: int, keys, ops, params, token: int) -> None:
        """Thread-side announcement (paper lines 2-12): double-buffered
        record + valid selector, parallel pwb/pfence, MSB publish.

        The payload additionally lands in the device-side announcement ring
        (``AnnounceRing``), so combining phases consume device arrays
        directly; SimFS keeps only the compact durable mirror below, which is
        what recovery and replay read back.

        Contract: per-thread ``token``s must be monotonically increasing —
        recovery uses token order to tell an in-flight PREDECESSOR in the
        older announcement slot (pipelined path) from an unpublished
        successor whose announce crashed before the valid flip.

        Depth guard: the double-buffered records bound a thread to TWO
        outstanding batches.  At depth > 2 the slot this announcement reuses
        may still belong to a dispatched-but-unretired chain; retiring chains
        in commit order until that batch's responses are durable keeps the
        protocol identical to the serial schedule (same pwbs/pfences, merely
        re-timed), so deep pipelines never clobber an un-persisted response.
        """
        valid = self._read_valid(thread)
        n_op = 1 - (valid & 1)
        if self._inflight:
            old_tok = self._slot_tokens.get((thread, n_op), -1)
            while old_tok >= 0 and self._chain_holding(thread, old_tok) is not None:
                self._retire(self._inflight.popleft())
        n_op, ann = self._announce_durable(thread, token, keys, ops, params)
        self._register_live(thread, n_op, token, ann["keys"], ann["ops"], ann["params"])

    def _announce_durable(
        self, thread: int, token: int, keys, ops, params
    ) -> Tuple[int, Dict[str, Any]]:
        """The announce protocol's durable writes alone (paper lines 2-12):
        record into the inactive slot, pfence, valid flip, pfence, MSB
        publish — 3 pwb + 2 pfence, shared verbatim by ``announce`` and the
        fused phase loop's intent drain so the two paths cannot drift.
        Returns ``(slot, record)``."""
        valid = self._read_valid(thread)
        n_op = 1 - (valid & 1)
        ann = {
            "token": token,
            "keys": [int(k) for k in np.asarray(keys)],
            "ops": [int(o) for o in np.asarray(ops)],
            "params": [float(p) for p in np.asarray(params)],
            "val": BOT,
        }
        self.fs.write(
            self._ann_path(thread, n_op), json.dumps(ann).encode(), tag="announce"
        )
        self.fs.fsync([self._ann_path(thread, n_op)], tag="announce")
        self.fs.write(self._valid_path(thread), str(n_op).encode(), tag="announce")
        self.fs.fsync([self._valid_path(thread)], tag="announce")
        self.fs.write(
            self._valid_path(thread), str(2 | n_op).encode(), tag="announce"
        )  # MSB
        if self.obs.enabled:
            self.obs.event(
                EV_ANNOUNCE,
                thread=thread,
                token=token,
                slot=n_op,
                n=len(ann["ops"]),
            )
        return n_op, ann

    def _register_live(
        self, thread: int, slot: int, token: int, keys, ops, params
    ) -> Dict[str, Any]:
        """Track a live (announced, not yet combined) batch: host metadata
        for routing/retire plus a device-ring span for the combine payload.
        When the ring has no room for the span the payload stays host-side
        (``ring_start=None``) and the combine falls back to a host upload —
        the protocol is unaffected, only the fast path."""
        keys = np.asarray(keys, np.int64)
        ops = np.asarray(ops, np.int32)
        params = np.asarray(params, np.float32)
        n = int(ops.shape[0])
        start = None
        if self.ring is not None and n:
            slots = int(self.ring.keys.shape[0])
            spans = [v for t, v in self._ring_spans.items() if t != thread]
            oldest = min((s0 for s0, _ in spans), default=self._ring_tail)
            if ring_has_room(slots, self._ring_tail, oldest, n):
                # split-lane fabrics annotate each ring slot with its op's
                # announcement lane (head/tail by target-shard structure),
                # so lane-filtered drains (``ring_drain(..., lane=...)``)
                # can feed a per-side combine dispatch straight off device
                lane_col = (
                    jnp.asarray(self._op_lanes_host(ops, self.route_host(keys)))
                    if self.split_lanes
                    else None
                )
                self.ring = ring_announce(
                    self.ring,
                    jnp.asarray(keys.astype(np.int32)),
                    jnp.asarray(ops),
                    jnp.asarray(params),
                    lane_col,
                )
                start = self._ring_tail
                self._ring_tail += n
                self._ring_spans[thread] = (start, n)
            else:
                self._ring_spans.pop(thread, None)
        rec = {
            "token": int(token), "slot": int(slot), "n": n,
            "keys": keys, "ops": ops, "params": params, "ring_start": start,
        }
        self._live[thread] = rec
        self._slot_tokens[(thread, int(slot))] = int(token)
        return rec

    def ready_announcements(self) -> List[int]:
        out = []
        for t in range(self.n_threads):
            v = self._read_valid(t)
            if (v >> 1) & 1:
                ann = self._read_ann(t, v & 1)
                if ann.get("val") is BOT and ann.get("token", -1) >= 0:
                    out.append(t)
        return out

    # ------------------------------------------------------ durable layout
    def _epoch_path(self, s: int) -> str:
        return f"shard_{s}/cEpoch"

    def _slot_dir(self, s: int, epoch: int, nxt: bool) -> str:
        return f"shard_{s}/slot{(epoch // 2 + (1 if nxt else 0)) % 2}"

    def _read_shard_epoch(self, s: int) -> int:
        raw = self.fs.read(self._epoch_path(s))
        return int(raw.decode()) if raw else 0

    def _persist_shard(
        self, s: int, epoch_target: int, state=None, counters=None
    ) -> List[str]:
        """pwb shard ``s``'s post-combine (or explicitly given) state into
        its inactive slot.

        Dirty-leaf elision (the paper's dirty-word tracking, at leaf
        granularity): a leaf whose bytes are identical to what this slot
        already holds DURABLY is skipped — its file is still listed in the
        slot manifest and still readable at recovery, so crash consistency
        is unchanged, but a combining phase that only moved root counters
        (e.g. a fully-eliminating stack batch, or a queue batch served
        entirely from the committed ring window) stops re-persisting the
        whole ``values`` array.  Digests are promoted into the elision cache
        only after the phase's pfence (``_promote_elision``).
        """
        one = self._shard_state(s) if state is None else state
        slot = self._slot_dir(s, epoch_target - 2, nxt=True)
        leaves, _ = jax.tree_util.tree_flatten(one)
        files = []
        if counters is None:
            counters = (
                int(self.meta["phases"][s]),
                int(self.meta["ops_combined"][s]),
            )
        meta = {
            "kind": self.kinds[s],
            "epoch": epoch_target,
            "leaves": [],
            "phases": int(counters[0]),
            "ops_combined": int(counters[1]),
        }
        for i, leaf in enumerate(leaves):
            arr = np.asarray(leaf)
            buf = io.BytesIO()
            np.save(buf, arr)
            data = buf.getvalue()
            rel = f"{slot}/leaf_{i}.npy"
            digest = hashlib.blake2b(data, digest_size=16).digest()
            if self._elide.get(rel) != digest:
                self.fs.write(rel, data, tag="slot")
                files.append(rel)
                self._elide_pending[rel] = digest
                self.obs.metrics.counter("elision_miss", shard=s)
            else:
                self.obs.metrics.counter("elision_hit", shard=s)
            meta["leaves"].append(
                {"file": f"leaf_{i}.npy", "shape": list(arr.shape), "dtype": str(arr.dtype)}
            )
        rel = f"{slot}/meta.json"
        self.fs.write(rel, json.dumps(meta).encode(), tag="slot")
        files.append(rel)
        return files

    def _promote_elision(self) -> None:
        """Move leaf digests written since the last pfence into the elision
        cache — they are durable now, so a future identical write may skip."""
        self._elide.update(self._elide_pending)
        self._elide_pending.clear()

    # ------------------------------------------- per-side lanes (ISSUE 8)
    def _is_split(self, s: int) -> bool:
        """Whether shard ``s`` commits through independent head/tail lanes."""
        return self.split_lanes and STRUCTS[self.kinds[s]].lane_splittable

    def _lane_epoch_pair(self, s: int) -> List[int]:
        """Host mirror of split shard ``s``'s committed ``[eH, eT]``."""
        return self.lane_epochs.setdefault(s, [0, 0])

    def _op_lanes_host(self, ops, shards) -> np.ndarray:
        """Per-op announcement lane (LANE_HEAD/LANE_TAIL, LANE_NONE for ops
        on unsplit shards): an op's lane is defined by its TARGET shard's
        structure, so the same op code can be head-side on one shard and
        tail-side on another in a mixed fabric."""
        ops = np.asarray(ops, np.int32)
        shards = np.asarray(shards)
        out = np.full(ops.shape, LANE_NONE, np.int32)
        for j in range(ops.shape[0]):
            s = int(shards[j]) if j < shards.shape[0] else -1
            if ops[j] != OP_NONE and 0 <= s < self.n_shards and self._is_split(s):
                out[j] = int(lane_of_ops_host(self.kinds[s], ops[j : j + 1])[0])
        return out

    def _lane_slot_dir(self, s: int, lane: int, lane_epoch: int, nxt: bool) -> str:
        """A lane's alternating slot dir, parity from ITS OWN epoch."""
        p = (lane_epoch // 2 + (1 if nxt else 0)) % 2
        return f"shard_{s}/lane{_LANE_TAGS[lane]}{p}"

    def _read_lane_epochs(self, s: int) -> List[int]:
        """Durable ``[eH, eT]`` of a split shard (``[0, 0]`` if it never
        committed).  The composite pair lives in ONE cEpoch file so the
        handoff commit can advance both lanes atomically."""
        raw = self.fs.read(self._epoch_path(s))
        if not raw:
            return [0, 0]
        txt = raw.decode()
        if txt.lstrip().startswith("["):
            e = json.loads(txt)
            return [int(e[0]), int(e[1])]
        return [0, int(txt)]  # pre-split history: all commits were one-lane

    def _lane_mode(
        self, s: int, ops_host, kinds_host, shard_host, post_state
    ) -> str:
        """Classify one batch's phase on split shard ``s``: ``"head"`` /
        ``"tail"`` (single-side — only that lane's epoch advances) or
        ``"handoff"`` (both lanes commit atomically).

        Handoff triggers when the batch mixes both sides, and ALSO when a
        head-side phase leaves the structure DRAINED (head counter == tail
        counter): that is the moment the head lane's pops have consumed
        everything the tail lane ever published — the lanes are synchronized
        by construction, and committing both epochs here gives recovery one
        crash-consistent point to resolve either side against (the
        drained-queue handoff of arXiv 2107.03492 / 2402.17674).
        """
        ops_a = np.asarray(ops_host, np.int32)
        kinds_a = np.asarray(kinds_host)[: ops_a.shape[0]]
        sel = (
            (np.asarray(shard_host) == s)
            & (ops_a != OP_NONE)
            & (kinds_a != R_OVERFLOW)
        )
        lanes = lane_of_ops_host(self.kinds[s], ops_a[sel])
        has_h = bool(np.any(lanes == LANE_HEAD))
        has_t = bool(np.any(lanes == LANE_TAIL))
        if has_h and has_t:
            return "handoff"
        ends = np.asarray(post_state.ends)
        active = (int(post_state.epoch) // 2) % 2
        drained = int(ends[active][0]) == int(ends[active][1])
        if has_h and drained:
            return "handoff"
        return "head" if has_h else "tail"

    def _persist_split_shard(
        self, s: int, mode: str, lane_targets: Sequence[int], state, counters
    ) -> List[str]:
        """pwb split shard ``s``'s post-phase lane record(s) into their
        inactive lane slots (the split twin of ``_persist_shard``).

        Only the committing lane(s) write: a head-side queue phase writes ONE
        tiny ``rec.json`` — no values leaf, no ends leaf, no epoch leaf —
        which is where the two-lane pwb/op win comes from.  Lanes that own
        values writes (``_LANE_WRITES_VALUES``) persist ``values.npy`` with
        the same dirty-leaf digest elision as the one-lane path, so a phase
        that only moved counters (drained elimination, window-served pops)
        costs no values pwb in either layout.
        """
        one = state if state is not None else self._shard_state(s)
        kind = self.kinds[s]
        ends = np.asarray(one.ends)
        active = (int(one.epoch) // 2) % 2
        ctr = (int(ends[active][0]), int(ends[active][1]))  # (head, tail)
        if counters is None:
            counters = (
                int(self.meta["phases"][s]),
                int(self.meta["ops_combined"][s]),
            )
        commit_lanes = {
            "head": (LANE_HEAD,),
            "tail": (LANE_TAIL,),
            "handoff": (LANE_HEAD, LANE_TAIL),
        }[mode]
        files: List[str] = []
        for lane in commit_lanes:
            target = int(lane_targets[lane])
            sdir = self._lane_slot_dir(s, lane, target - 2, nxt=True)
            if _LANE_WRITES_VALUES[kind][lane]:
                arr = np.asarray(one.values)
                buf = io.BytesIO()
                np.save(buf, arr)
                data = buf.getvalue()
                rel = f"{sdir}/values.npy"
                digest = hashlib.blake2b(data, digest_size=16).digest()
                if self._elide.get(rel) != digest:
                    self.fs.write(rel, data, tag="slot")
                    files.append(rel)
                    self._elide_pending[rel] = digest
                    self.obs.metrics.counter("elision_miss", shard=s)
                else:
                    self.obs.metrics.counter("elision_hit", shard=s)
            rec = {
                "kind": kind,
                "lane": _LANE_TAGS[lane],
                "epoch": target,
                "ctr": ctr[lane],
                "phases": int(counters[0]),
                "ops_combined": int(counters[1]),
            }
            rel = f"{sdir}/rec.json"
            self.fs.write(rel, json.dumps(rec).encode(), tag="slot")
            files.append(rel)
        return files

    def _commit_lane_epochs(
        self, s: int, mode: str, lane_targets: Sequence[int]
    ) -> None:
        """Two-increment commit of a split shard's composite epoch pair:
        write the pair with the advancing lane(s) odd, fsync (THE commit
        point), publish the even pair unsynced.  Because the pair shares one
        file, a handoff's two lanes commit or roll back together — recovery
        rounds odd components up independently but a crash can never land
        between them."""
        tH, tT = int(lane_targets[LANE_HEAD]), int(lane_targets[LANE_TAIL])
        adv_h = mode in ("head", "handoff")
        adv_t = mode in ("tail", "handoff")
        odd = [tH - 1 if adv_h else tH, tT - 1 if adv_t else tT]
        path = self._epoch_path(s)
        self.fs.write(path, json.dumps(odd).encode(), tag="epoch")
        self.fs.fsync([path], tag="epoch")
        self.fs.write(path, json.dumps([tH, tT]).encode(), tag="epoch")
        self.lane_epochs[s] = [tH, tT]
        self.obs.event(
            EV_EPOCH, shard=s, epoch=tH + tT, lanes=[tH, tT], mode=mode
        )

    def _plan_lane_commit(
        self, s: int, ops_host, kinds_host, shard_host, post_state
    ) -> Tuple[str, List[int]]:
        """One touched split shard's commit plan for one phase:
        ``(mode, [eH', eT'])`` where the advancing lane(s) are the current
        mirror + 2 and the quiescent lane keeps its committed epoch (so
        per-op verdict targets on the quiescent lane are already met)."""
        mode = self._lane_mode(s, ops_host, kinds_host, shard_host, post_state)
        eH, eT = self._lane_epoch_pair(s)
        tH = eH + 2 if mode in ("head", "handoff") else eH
        tT = eT + 2 if mode in ("tail", "handoff") else eT
        return mode, [tH, tT]

    def _lane_targets_per_op(
        self, ops_host, shard_host, plans: Dict[int, Tuple[str, List[int]]],
        fallback_targets,
    ) -> Tuple[List[int], List[int]]:
        """Per-op ``(targets, lanes)`` for the durable response record.  An
        op on a split shard targets ITS LANE's post-phase epoch (quiescent
        lane ops of an untouched/other-side shard target the already
        committed value); unsplit ops keep the scalar device-epoch target
        with lane ``LANE_NONE``."""
        ops_a = np.asarray(ops_host, np.int32)
        shards_a = np.asarray(shard_host)
        lanes = self._op_lanes_host(ops_a, shards_a)
        targets: List[int] = []
        for j in range(ops_a.shape[0]):
            s = int(shards_a[j])
            if lanes[j] == LANE_NONE:
                targets.append(int(fallback_targets[j]))
            else:
                pair = (
                    plans[s][1] if s in plans else self._lane_epoch_pair(s)
                )
                targets.append(int(pair[lanes[j]]))
        return targets, [int(x) for x in lanes]

    def lane_stats(self) -> Optional[Dict[str, Any]]:
        """Per-lane observability snapshot (``None`` when lanes are off):
        committed ``[eH, eT]`` per split shard plus the per-lane BACKLOG —
        announced-but-uncombined ops bucketed by (shard, lane) — consumed by
        ``obs.observe_fabric`` and ``tools/fabric_top.py``."""
        if not self.split_lanes:
            return None
        epochs = {}
        for s in range(self.n_shards):
            if self._is_split(s):
                epochs[s] = list(self._lane_epoch_pair(s))
        backlog: Dict[int, List[int]] = {s: [0, 0] for s in epochs}
        if self.fs is not None:
            for t in self.ready_announcements():
                rec = self._live.get(t)
                if rec is None:
                    continue
                shards = self.route_host(rec["keys"])
                lanes = self._op_lanes_host(rec["ops"], shards)
                for j in range(lanes.shape[0]):
                    if lanes[j] != LANE_NONE:
                        backlog[int(shards[j])][int(lanes[j])] += 1
        return {"epochs": epochs, "backlog": backlog}

    # ------------------------------------------------- durable routing layout
    _REPOCH_PATH = "routing/rEpoch"
    _INTENT_PATH = "reshard/intent.json"

    def _routing_slot(self, repoch: int, nxt: bool) -> str:
        return f"routing/slot{(repoch // 2 + (1 if nxt else 0)) % 2}.json"

    def _routing_record(self, target: int, table, kinds) -> Dict[str, Any]:
        return {
            "epoch": target,
            "table": [int(x) for x in table],
            "kinds": list(kinds),
            "n_shards": len(kinds),
            "n_buckets": self.n_buckets,
            "capacity": self.capacity,
            "lanes": self.lanes,
            "split_lanes": self.split_lanes,
        }

    # --------------------------------------------------------- combine phase
    def _chain_holding(self, thread: int, token: int) -> Optional[Dict[str, Any]]:
        """The in-flight chain that dispatched (thread, token), if any."""
        for fl in self._inflight:
            for info in fl["batches"]:
                for seg in info["threads"]:
                    if seg["thread"] == thread and seg["token"] == token:
                        return fl
        return None

    def _collect_ready(self) -> List[Tuple[int, Dict[str, Any]]]:
        """Ready announcements as (thread, live-record) pairs, in thread
        order, excluding batches already dispatched into the pipeline."""
        inflight = set()
        for fl in self._inflight:
            for info in fl["batches"]:
                for seg in info["threads"]:
                    inflight.add((seg["thread"], seg["token"]))
        out = []
        for t in self.ready_announcements():
            rec = self._live.get(t)
            v = self._read_valid(t)
            if rec is None or rec["slot"] != (v & 1):
                # announced before this runtime object existed (or by another
                # writer): rebuild the live record from the durable mirror
                ann = self._read_ann(t, v & 1)
                rec = self._register_live(
                    t, v & 1, ann["token"], ann["keys"], ann["ops"], ann["params"]
                )
            if (t, rec["token"]) in inflight:
                continue
            out.append((t, rec))
        return out

    def _payload_view(self, rec: Dict[str, Any]):
        """A live batch's payload as device arrays: straight out of the
        announcement ring when the span landed there, host upload otherwise."""
        if rec["ring_start"] is not None:
            return ring_drain(self.ring, rec["ring_start"], rec["n"])
        return (
            jnp.asarray(rec["keys"].astype(np.int32)),
            jnp.asarray(rec["ops"]),
            jnp.asarray(rec["params"]),
        )

    def combine_phase(self) -> List[int]:
        """One durable combining phase over every ready announcement.

        Serial mode (``pipeline=False``, the default): concatenates the
        announced batches (announcement order = thread id order — the
        combiner's walk over the announcement array), runs the fused device
        step on the ring-resident payload, persists every touched shard into
        its inactive slot, writes responses + per-op commit targets into the
        combined announcements, pfences ONCE (paper line 80), then commits
        each touched shard's epoch with the two-increment protocol (lines
        81-83).  Returns the combined thread ids.

        Pipelined mode (``depth > 1``; the legacy ``pipeline=True`` is
        depth=2): stage 1 DISPATCHES the device combine for the freshly
        collected chain and appends it to the in-flight ring; stage 2
        retires the OLDEST chains — persist + pfence + per-shard epoch
        commits, strictly in commit order — until at most ``depth - 1``
        dispatched chains remain un-retired, so persistence of chain k
        overlaps the device combine of chains k+1..k+depth-1.  A chain's
        responses become durable only when it retires (a later
        ``combine_phase``, an ``announce`` reclaiming its slot, or an
        explicit ``flush``); the two-increment epoch commit still gates
        visibility, so recovery semantics are unchanged at every depth.

        With ``chain > 1``, each ready thread's announcement becomes its own
        batch (the tail group absorbs the remainder; the chain is PADDED to
        exactly ``chain`` batches with all-``OP_NONE`` pass-through batches,
        so every dispatch of the fabric shares one compiled program per lane
        width however many announcers were ready) and the whole chain is
        combined in ONE fused dispatch (``dfc_sharded_multi_combine_step``,
        scan unrolled by ``depth``) but persisted and committed
        batch-by-batch, exactly like that many serial phases — padding
        batches touch no shard and cost no persistence op.
        """
        assert self.fs is not None, "combine_phase needs a SimFS"
        ready = self._collect_ready()
        if not ready:
            self.flush()
            return []

        if self.chain > 1:
            groups = [[r] for r in ready[: self.chain - 1]]
            tail = list(ready[self.chain - 1:])
            if tail:  # fewer ready than chain: no (empty) tail batch
                groups.append(tail)
            # depth-aware dispatch: pad to the chain's full batch count with
            # pass-through batches so the compiled scan shape is fixed
            groups += [[] for _ in range(self.chain - len(groups))]
        else:
            groups = [ready]

        maxlen = max(sum(rec["n"] for _, rec in g) for g in groups)
        pad = max(8, 1 << max(0, (maxlen - 1)).bit_length())
        dev_keys, dev_ops, dev_params, batches = [], [], [], []
        for g in groups:
            karrs, oarrs, parrs, segs, off = [], [], [], [], 0
            for t, rec in g:
                k, o, p = self._payload_view(rec)
                karrs.append(k)
                oarrs.append(o)
                parrs.append(p)
                segs.append(
                    {"thread": t, "token": rec["token"], "slot": rec["slot"],
                     "off": off, "n": rec["n"]}
                )
                off += rec["n"]
                self._ring_spans.pop(t, None)  # span consumed at dispatch
            fill = pad - off
            if fill:
                karrs.append(jnp.zeros((fill,), jnp.int32))
                oarrs.append(jnp.full((fill,), OP_NONE, jnp.int32))
                parrs.append(jnp.zeros((fill,), jnp.float32))
            dev_keys.append(jnp.concatenate(karrs))
            dev_ops.append(jnp.concatenate(oarrs))
            dev_params.append(jnp.concatenate(parrs))
            host_keys = (
                np.concatenate([rec["keys"] for _, rec in g])
                if g else np.zeros((0,), np.int64)
            )
            host_ops = (
                np.concatenate([rec["ops"] for _, rec in g])
                if g else np.zeros((0,), np.int32)
            )
            batches.append(
                {"threads": segs, "shard": self.route_host(host_keys),
                 "ops": host_ops}
            )

        # stage 1: dispatch the chained device combine (async under jit)
        (
            self.groups, self.meta, resp, out_kinds,
            states, epochs_before, epochs, phases_cum, ops_cum,
        ) = hetero_multi_step(
            self.groups,
            jnp.asarray(self.table),
            jnp.stack(dev_keys),
            jnp.stack(dev_ops),
            jnp.stack(dev_params),
            self.meta,
            kinds=tuple(self.kinds),
            lanes=self.lanes,
            backend=self.backend,
            unroll=self.depth,
        )
        self._inflight.append({
            "batches": batches, "resp": resp, "kinds": out_kinds,
            "states": states, "epochs_before": epochs_before,
            "epochs": epochs, "phases_cum": phases_cum, "ops_cum": ops_cum,
            "repoch": self.r_epoch,
        })
        self.last_dispatch = [
            tuple((seg["thread"], seg["token"]) for seg in info["threads"])
            for info in batches
            if info["threads"]
        ]
        if self.obs.enabled:
            self.obs.event(
                EV_DISPATCH,
                batches=[
                    [[seg["thread"], seg["token"]] for seg in info["threads"]]
                    for info in batches
                ],
                inflight=len(self._inflight),
            )
            self.obs.metrics.gauge("inflight_chains", len(self._inflight))
        # stage 2: retire the oldest chains, in commit order, while the
        # device combines — keep at most depth-1 chains in flight
        while len(self._inflight) > self.depth - 1:
            self._retire(self._inflight.popleft())
        if self.obs.enabled:
            self.obs.observe_fabric(self)
        return [seg["thread"] for info in batches for seg in info["threads"]]

    def _retire(self, fl: Dict[str, Any]) -> List[int]:
        """Persist + commit one dispatched chain, batch by batch: persist
        batch b's touched shards into their inactive slots, write batch b's
        responses into the combined announcements, ONE pfence, then the
        per-shard two-increment epoch commits — identical durable schedule
        (and pwb/pfence counts) to that many serial phases."""
        resp = np.asarray(fl["resp"])
        kinds = np.asarray(fl["kinds"])
        epochs = np.asarray(fl["epochs"])  # [B, S]
        phases_cum = np.asarray(fl["phases_cum"])
        ops_cum = np.asarray(fl["ops_cum"])
        prev_epochs = np.asarray(fl["epochs_before"])
        # one device->host fetch per stacked leaf (not per shard slice)
        states_np = {
            k: jax.tree_util.tree_map(np.asarray, st)
            for k, st in fl["states"].items()
        }

        def batch_shard_state(b, s):
            k, r = self.kinds[s], self._row(s)
            return jax.tree_util.tree_map(lambda leaf: leaf[b, r], states_np[k])
        retired = []
        for b, info in enumerate(fl["batches"]):
            e_b = epochs[b]
            touched = [int(s) for s in np.nonzero(e_b != prev_epochs)[0]]
            if not info["threads"] and not touched:
                continue  # chain-padding pass-through: no durable work
            shard = info["shard"]
            ops_host = info["ops"]
            kinds_row = kinds[b][: len(ops_host)]
            # per-side lanes: plan each touched split shard's commit (which
            # lane(s) advance, or a handoff) from the batch's op mix + the
            # post-phase counters, BEFORE any durable write of this phase
            plans: Dict[int, Tuple[str, List[int]]] = {}
            for s in touched:
                if self._is_split(s):
                    plans[s] = self._plan_lane_commit(
                        s, ops_host, kinds_row, shard, batch_shard_state(b, s)
                    )
            files: List[str] = []
            for s in touched:
                if s in plans:
                    files += self._persist_split_shard(
                        s, plans[s][0], plans[s][1],
                        state=batch_shard_state(b, s),
                        counters=(phases_cum[b][s], ops_cum[b][s]),
                    )
                else:
                    files += self._persist_shard(
                        s,
                        int(e_b[s]),
                        state=batch_shard_state(b, s),
                        counters=(phases_cum[b][s], ops_cum[b][s]),
                    )
            fallback = e_b[shard]  # per-op commit target (its shard)
            if self.split_lanes:
                targets, op_lanes = self._lane_targets_per_op(
                    ops_host, shard, plans, fallback
                )
            else:
                targets = [int(e) for e in fallback]
                op_lanes = None
            for seg in info["threads"]:
                sl = slice(seg["off"], seg["off"] + seg["n"])
                ann = self._read_ann(seg["thread"], seg["slot"])
                ann["val"] = {
                    "resp": [float(v) for v in resp[b][sl]],
                    "kinds": [int(k) for k in kinds[b][sl]],
                    "shards": [int(s) for s in shard[sl]],
                    "targets": list(targets[sl]),
                    "repoch": fl["repoch"],
                }
                if op_lanes is not None:
                    ann["val"]["lanes"] = list(op_lanes[sl])
                rel = self._ann_path(seg["thread"], seg["slot"])
                self.fs.write(rel, json.dumps(ann).encode(), tag="resp")
                files.append(rel)
                retired.append(seg["thread"])
            self.fs.fsync(files, tag="phase")  # ONE pfence for slots + responses
            self._promote_elision()
            for s in touched:  # per-shard two-increment epoch commit
                if s in plans:
                    self._commit_lane_epochs(s, plans[s][0], plans[s][1])
                    continue
                e = int(e_b[s])
                self.fs.write(self._epoch_path(s), str(e - 1).encode(), tag="epoch")
                self.fs.fsync([self._epoch_path(s)], tag="epoch")
                self.fs.write(self._epoch_path(s), str(e).encode(), tag="epoch")
                self.obs.event(EV_EPOCH, shard=s, epoch=e)
            if self.obs.enabled:
                self.obs.event(
                    EV_RETIRE,
                    batch=b,
                    threads=[
                        [seg["thread"], seg["token"]] for seg in info["threads"]
                    ],
                    touched=touched,
                    files=len(files),
                )
            prev_epochs = e_b
        return retired

    def flush(self) -> List[int]:
        """Retire every in-flight chain, oldest first (pipelined mode):
        persist their shard states and responses and commit their epochs, in
        commit order.  Returns the thread ids whose announcements became
        durable."""
        retired: List[int] = []
        while self._inflight:
            retired += self._retire(self._inflight.popleft())
        return retired

    def _drain(self) -> None:
        """Combine every ready announcement AND retire the pipeline — the
        quiescent point resharding transactions start from."""
        self.combine_phase()
        self.flush()

    # ------------------------------------------------------ fused phase loop
    def phase_loop(
        self,
        schedule: Sequence[Tuple[int, int, Any, Any, Any]],
        *,
        unroll: Optional[int] = None,
    ) -> List[Dict[str, Any]]:
        """Fuse K combining phases into ONE device dispatch, then drain the
        per-phase persist intents host-side — subsuming ``combine_phase`` +
        ``_retire`` for a whole schedule of batches.

        ``schedule`` is K per-phase entries ``(thread, token, keys, ops,
        params)``: each entry is one thread's announced batch, combined as
        its OWN phase (phase order = schedule order; per-thread tokens must
        be monotone across the schedule, the ``announce`` contract).  The
        device side routes, combines, and accumulates every phase's
        epoch/persist intents in device arrays (``hetero_phase_loop_step``:
        one ``lax.scan`` over the phases per kind group, group buffers
        donated off-CPU so stacked shard state never
        leaves the device between phases), with the whole schedule staged
        through the announcement ring in one scatter when it fits.  The host
        then drains the intent log in strict serial order — for each phase:
        the batch's durable announce (3 pwb + 2 pfence, the exact
        ``announce`` write sequence), the touched shards' slot persists, the
        response record write, ONE pfence, the per-shard two-increment epoch
        commits — so oldest-first commit order and the serial path's
        pwb/pfence counts are preserved EXACTLY (``bench_phase_loop.py``
        asserts both, the way ``bench_multithread.py`` asserts for depth).

        A crash anywhere in the drain leaves the durable log shaped exactly
        like a serial run that crashed at the same persistence op — up to
        K phases of device-combined intents simply vanish with the volatile
        state — so ``recover`` / ``replay_pending`` roll the log forward to
        the last committed epoch with per-thread detectability verdicts
        intact, and phases whose announce never reached the log are the
        driver's to re-drive (same contract as the pipelined sweeps).

        Because a thread's double-buffered records retain only its last two
        batches, responses for the whole schedule are RETURNED (one record
        per phase, in phase order: ``{"thread", "token", "resp", "kinds",
        "shards", "targets", "repoch"}``); ``read_responses`` still serves
        each thread's final two tokens afterwards.
        """
        assert self.fs is not None, "phase_loop needs a SimFS"
        self._drain()  # quiescent start: no ready announcements, no chains
        if not schedule:
            return []

        k_phases = len(schedule)
        batches = []
        for thread, token, keys, ops, params in schedule:
            batches.append((
                int(thread), int(token),
                np.asarray(keys, np.int64),
                np.asarray(ops, np.int32),
                np.asarray(params, np.float32),
            ))
        maxlen = max(b[3].shape[0] for b in batches)
        pad = max(8, 1 << max(0, (maxlen - 1)).bit_length())
        keys_h = np.zeros((k_phases, pad), np.int64)
        ops_h = np.full((k_phases, pad), OP_NONE, np.int32)
        params_h = np.zeros((k_phases, pad), np.float32)
        for j, (_, _, keys, ops, params) in enumerate(batches):
            n = ops.shape[0]
            keys_h[j, :n] = keys
            ops_h[j, :n] = ops
            params_h[j, :n] = params

        # stage the whole schedule through the announcement ring (one device
        # scatter + one phase-axis gather) when it fits; host upload if not
        dev = None
        if self.ring is not None and k_phases * pad:
            slots = int(self.ring.keys.shape[0])
            oldest = min(
                (s0 for s0, _ in self._ring_spans.values()),
                default=self._ring_tail,
            )
            if ring_has_room(slots, self._ring_tail, oldest, k_phases * pad):
                self.ring = ring_announce_phases(
                    self.ring,
                    jnp.asarray(keys_h.astype(np.int32)),
                    jnp.asarray(ops_h),
                    jnp.asarray(params_h),
                )
                start = self._ring_tail
                self._ring_tail += k_phases * pad
                dev = ring_drain_phases(self.ring, start, k_phases, pad)
        if dev is None:
            dev = (
                jnp.asarray(keys_h.astype(np.int32)),
                jnp.asarray(ops_h),
                jnp.asarray(params_h),
            )

        # ONE fused dispatch for the whole schedule
        (
            self.groups, self.meta, resp, out_kinds,
            states, epochs_before, intents,
        ) = hetero_phase_loop_step(
            self.groups,
            jnp.asarray(self.table),
            dev[0], dev[1], dev[2],
            self.meta,
            kinds=tuple(self.kinds),
            lanes=self.lanes,
            backend=self.backend,
            unroll=self.depth if unroll is None else int(unroll),
        )
        self.last_dispatch = [((t, tok),) for t, tok, *_ in batches]
        if self.obs.enabled:
            self.obs.event(
                EV_DISPATCH,
                fused=True,
                k_phases=k_phases,
                pad=pad,
                batches=[[t, tok] for t, tok, *_ in batches],
            )

        # fetch the intent log: one device->host transfer per stacked leaf
        resp_np = np.asarray(resp)
        kinds_np = np.asarray(out_kinds)
        epochs = np.asarray(intents.epoch)  # [K, S]
        phases_cum = np.asarray(intents.phases_cum)
        ops_cum = np.asarray(intents.ops_cum)
        prev_epochs = np.asarray(epochs_before)
        states_np = {
            k: jax.tree_util.tree_map(np.asarray, st)
            for k, st in states.items()
        }

        def phase_shard_state(j, s):
            k, r = self.kinds[s], self._row(s)
            return jax.tree_util.tree_map(
                lambda leaf: leaf[j, r], states_np[k]
            )

        # host intent drain: replay the exact serial durable schedule,
        # phase by phase, behind the device
        out_records: List[Dict[str, Any]] = []
        for j, (thread, token, keys, ops, params) in enumerate(batches):
            n = ops.shape[0]
            slot, ann = self._announce_durable(thread, token, keys, ops, params)
            self._slot_tokens[(thread, slot)] = token
            self._live[thread] = {
                "token": token, "slot": slot, "n": n,
                "keys": keys, "ops": ops, "params": params,
                "ring_start": None,
            }
            e_j = epochs[j]
            touched = [int(s) for s in np.nonzero(e_j != prev_epochs)[0]]
            shard = self.route_host(keys)
            kinds_row = kinds_np[j][:n]
            plans: Dict[int, Tuple[str, List[int]]] = {}
            for s in touched:
                if self._is_split(s):
                    plans[s] = self._plan_lane_commit(
                        s, ops, kinds_row, shard, phase_shard_state(j, s)
                    )
            files: List[str] = []
            for s in touched:
                if s in plans:
                    files += self._persist_split_shard(
                        s, plans[s][0], plans[s][1],
                        state=phase_shard_state(j, s),
                        counters=(phases_cum[j][s], ops_cum[j][s]),
                    )
                else:
                    files += self._persist_shard(
                        s,
                        int(e_j[s]),
                        state=phase_shard_state(j, s),
                        counters=(phases_cum[j][s], ops_cum[j][s]),
                    )
            fallback = e_j[shard]
            if self.split_lanes:
                targets, op_lanes = self._lane_targets_per_op(
                    ops, shard, plans, fallback
                )
            else:
                targets = [int(e) for e in fallback]
                op_lanes = None
            ann["val"] = {
                "resp": [float(v) for v in resp_np[j][:n]],
                "kinds": [int(k) for k in kinds_row],
                "shards": [int(s) for s in shard],
                "targets": list(targets),
                "repoch": self.r_epoch,
            }
            if op_lanes is not None:
                ann["val"]["lanes"] = list(op_lanes)
            rel = self._ann_path(thread, slot)
            self.fs.write(rel, json.dumps(ann).encode(), tag="resp")
            files.append(rel)
            self.fs.fsync(files, tag="phase")  # ONE pfence for slots + responses
            self._promote_elision()
            for s in touched:  # per-shard two-increment epoch commit
                if s in plans:
                    self._commit_lane_epochs(s, plans[s][0], plans[s][1])
                    continue
                e = int(e_j[s])
                self.fs.write(self._epoch_path(s), str(e - 1).encode(), tag="epoch")
                self.fs.fsync([self._epoch_path(s)], tag="epoch")
                self.fs.write(self._epoch_path(s), str(e).encode(), tag="epoch")
                self.obs.event(EV_EPOCH, shard=s, epoch=e)
            if self.obs.enabled:
                self.obs.event(
                    EV_DRAIN,
                    phase=j,
                    thread=thread,
                    token=token,
                    touched=touched,
                    files=len(files),
                )
            prev_epochs = e_j
            out_records.append(dict(ann["val"], thread=thread, token=token))
        if self.obs.enabled:
            self.obs.observe_fabric(self)
        return out_records

    def read_responses(
        self, thread: int, token: Optional[int] = None,
        lane: Optional[int] = None,
    ) -> Optional[Dict[str, Any]]:
        """A thread's combined announcement, or None while still pending.

        Returns ``{"token", "resp", "kinds", "shards", "targets", ...}`` —
        the durable response record written when the phase that combined
        this thread's announcement was retired.  With ``token``, searches
        BOTH announcement slots for that batch — in pipelined mode a
        thread's previous batch retires while its newest is still in flight,
        so the response being read usually lives in the older slot.

        With ``lane`` (split-lane fabrics), the returned record is filtered
        to the ops that rode that announcement lane (``LANE_HEAD`` /
        ``LANE_TAIL``; ops on unsplit shards are ``LANE_NONE``).  The filter
        applies AFTER the slot search and AFTER staleness detection: with
        per-side combiners a thread's retained slots can hold one head-side
        and one tail-side batch with interleaved tokens, and the monotone
        staleness rule below must still judge ``token`` against the NEWEST
        retained token across BOTH lanes — a lane-local view would mistake
        an overwritten token of the other lane for "pending" and spin
        forever (the PR-6 gap-token regression, per-side edition).

        Raises :class:`StaleTokenError` when ``token`` predates both
        retained slots (its record was overwritten by two later
        announcements); returns ``None`` only while the batch is genuinely
        pending (announced and not yet retired, or not yet announced).
        """

        def _lane_view(val: Dict[str, Any], tok: int):
            out = dict(val, token=tok)
            if lane is None:
                return out
            lanes = val.get("lanes")
            if lanes is None:
                lanes = [LANE_NONE] * len(val.get("kinds", []))
            idx = [i for i, ln in enumerate(lanes) if ln == lane]
            for key in ("resp", "kinds", "shards", "targets", "lanes"):
                if key in out and isinstance(out[key], list):
                    out[key] = [out[key][i] for i in idx]
            return out

        v = self._read_valid(thread)
        if token is None:
            ann = self._read_ann(thread, v & 1)
            if ann.get("val") is BOT:
                return None
            return _lane_view(ann["val"], ann["token"])
        held = []
        for slot in (v & 1, 1 - (v & 1)):
            ann = self._read_ann(thread, slot)
            t = ann.get("token", -1)
            if t == token:
                if ann.get("val") is BOT:
                    return None  # announced, not yet combined/retired
                return _lane_view(ann["val"], ann["token"])
            if t >= 0:
                held.append(t)
        # Staleness: per-thread tokens are MONOTONE, so a requested token
        # below the NEWEST retained one provably predates the retained
        # slot(s) — either it was announced and its record has been
        # overwritten, or it was skipped and can never be announced now.
        # (Comparing against min(held) missed the gap case — a token between
        # the two retained ones, or below the only retained one while the
        # other slot is still unannounced — and silently returned None,
        # indistinguishable from "pending", so pollers spun forever.)
        if held and token < max(held):
            raise StaleTokenError(
                f"thread {thread} token {token} predates retained "
                f"announcement slot(s) (tokens held: {sorted(held)}); its "
                "response record was overwritten or never announced — read "
                "responses before announcing two successor batches"
            )
        return None

    # ----------------------------------------------------------- resharding
    def _snapshot_donor(self, s: int, op: str) -> None:
        """Detectable typed snapshot of the donor shard, via the checkpoint
        manager's ``combine_structure`` (same SimFS: fault-injection sweeps
        tick through the snapshot's pwb/pfence ops too)."""
        self._reshard_seq += 1
        mgr = DFCCheckpointManager(self.fs, 1, prefix="reshard/ckpt")
        e = mgr._read_epoch()
        if e % 2 == 1:  # a crash mid-snapshot commit left the log's epoch
            mgr._write_epoch(e + 1, sync=True)  # odd: finish the increment
        mgr.announce(0, {"step": self._reshard_seq})
        mgr.combine_structure(
            self._shard_state(s),
            extra_meta={"donor": int(s), "op": op, "repoch": self.r_epoch},
        )

    def _commit_routing(
        self,
        intent: Dict[str, Any],
        new_table: np.ndarray,
        new_kinds: List[str],
        shard_files: List[str],
    ) -> None:
        """Steps 3-5 of the reshard transaction: intent, routing slot (+ any
        pre-written shard slots), ONE pfence, then the rEpoch two-increment
        commit — the transaction's commit point."""
        target = self.r_epoch + 2
        self.fs.write(self._INTENT_PATH, json.dumps(intent).encode(), tag="routing")
        self.fs.fsync([self._INTENT_PATH], tag="routing")
        slot = self._routing_slot(self.r_epoch, nxt=True)
        self.fs.write(
            slot,
            json.dumps(self._routing_record(target, new_table, new_kinds)).encode(),
            tag="routing",
        )
        self.fs.fsync(shard_files + [slot], tag="routing")
        self.fs.write(self._REPOCH_PATH, str(target - 1).encode(), tag="routing")
        self.fs.fsync([self._REPOCH_PATH], tag="routing")
        self.fs.write(self._REPOCH_PATH, str(target).encode(), tag="routing")
        if self.obs.enabled:
            self.obs.event(
                EV_RESHARD,
                op=intent.get("op"),
                target_repoch=target,
                n_shards=len(new_kinds),
            )

    def split_shard(self, donor: int) -> int:
        """Split a hot shard: move half of the donor's buckets to a NEW empty
        shard of the same kind.  Crash-consistent (commit point = rEpoch);
        the donor's contents stay put — only future routing changes — so
        there is nothing to roll forward on the shard side.  Returns the new
        shard id.
        """
        buckets = [b for b in range(self.n_buckets) if self.table[b] == donor]
        if len(buckets) < 2:
            raise ValueError(
                f"shard {donor} holds {len(buckets)} bucket(s); construct the "
                "fabric with n_buckets > n_shards to make shards splittable"
            )
        kind = self.kinds[donor]
        new_id = self.n_shards
        new_table = self.table.copy()
        new_table[buckets[1::2]] = new_id
        new_kinds = self.kinds + [kind]

        if self.fs is not None:
            self._drain()  # drain ready announcements AND the pipeline
            self._snapshot_donor(donor, "split")
            intent = {
                "op": "split",
                "donor": int(donor),
                "new_shard": new_id,
                "kind": kind,
                "pre_repoch": self.r_epoch,
                "target_repoch": self.r_epoch + 2,
                "target_epochs": {},  # split moves no shard state
            }
            # the new shard needs no durable state: no cEpoch file means
            # epoch 0, no slot means a fresh empty init on recovery
            self._commit_routing(intent, new_table, new_kinds, [])
            self.fs.delete(self._INTENT_PATH)

        # in-memory install
        fresh = STRUCTS[kind].init(self.capacity)
        self.groups[kind] = jax.tree_util.tree_map(
            lambda leaf, f: jnp.concatenate([leaf, f[None]]), self.groups[kind], fresh
        )
        self.kinds = new_kinds
        self.n_shards += 1
        self.table = new_table
        self.r_epoch += 2
        new_row = _init_meta([kind])  # single source of truth for columns
        self.meta = {
            key: jnp.concatenate(
                [col, new_row.get(key, jnp.zeros((1,), col.dtype))]
            )
            for key, col in self.meta.items()
        }
        return new_id

    def merge_shards(self, src: int, dst: int) -> None:
        """Merge a cold shard into another of the SAME kind: ``dst`` absorbs
        ``src``'s committed contents (appended after ``dst``'s own — enqueued
        at the tail / pushed on top / pushed right), ``src`` empties and its
        buckets re-route to ``dst``.  ``src``'s shard id stays allocated but
        unrouted, so recorded detectability verdicts never dangle.

        Crash-consistent: both post-merge states are pwb'd into their
        inactive slots and pfenced BEFORE the rEpoch commit; recovery rolls
        their cEpochs forward when the rEpoch committed and the per-shard GC
        reclaims the orphaned slots when it did not.
        """
        if src == dst:
            raise ValueError("cannot merge a shard into itself")
        if self.kinds[src] != self.kinds[dst]:
            raise ValueError(
                f"kind mismatch: shard {src} is {self.kinds[src]!r}, "
                f"shard {dst} is {self.kinds[dst]!r}"
            )
        kind = self.kinds[src]
        if self.fs is not None:
            self._drain()  # drain ready announcements AND the pipeline
        merged = self.shard_contents(dst) + self.shard_contents(src)
        if len(merged) + self.lanes > self.capacity:
            raise ValueError(
                f"merged contents ({len(merged)}) + lanes ({self.lanes}) "
                f"exceed capacity {self.capacity}"
            )
        epochs = self.shard_epochs()
        t_src, t_dst = int(epochs[src]) + 2, int(epochs[dst]) + 2
        src_new = state_from_contents(kind, [], self.capacity, t_src)
        dst_new = state_from_contents(kind, merged, self.capacity, t_dst)
        new_table = self.table.copy()
        new_table[new_table == src] = dst

        if self.fs is not None:
            self._snapshot_donor(src, "merge")
            # split shards reshard handoff-style: BOTH lanes advance, the
            # intent records the lane pair, and recovery rolls the composite
            # epoch forward componentwise
            split = self._is_split(src)
            if split:
                lane_targets = {
                    sid: [e + 2 for e in self._lane_epoch_pair(sid)]
                    for sid in (src, dst)
                }
                intent_targets = {
                    str(sid): list(lane_targets[sid]) for sid in (src, dst)
                }
            else:
                intent_targets = {str(src): t_src, str(dst): t_dst}
            intent = {
                "op": "merge",
                "src": int(src),
                "dst": int(dst),
                "kind": kind,
                "pre_repoch": self.r_epoch,
                "target_repoch": self.r_epoch + 2,
                "target_epochs": intent_targets,
            }
            if split:
                files = self._persist_split_shard(
                    src, "handoff", lane_targets[src], state=src_new,
                    counters=None,
                )
                files += self._persist_split_shard(
                    dst, "handoff", lane_targets[dst], state=dst_new,
                    counters=None,
                )
            else:
                files = self._persist_shard(src, t_src, state=src_new)
                files += self._persist_shard(dst, t_dst, state=dst_new)
            self._commit_routing(intent, new_table, self.kinds, files)
            self._promote_elision()
            if split:
                for sid in (src, dst):
                    self._commit_lane_epochs(sid, "handoff", lane_targets[sid])
            else:
                for sid, tgt in ((src, t_src), (dst, t_dst)):
                    self.fs.write(self._epoch_path(sid), str(tgt - 1).encode(), tag="epoch")
                    self.fs.fsync([self._epoch_path(sid)], tag="epoch")
                    self.fs.write(self._epoch_path(sid), str(tgt).encode(), tag="epoch")
                    self.obs.event(EV_EPOCH, shard=sid, epoch=tgt)
            self.fs.delete(self._INTENT_PATH)

        self._set_shard_state(src, src_new)
        self._set_shard_state(dst, dst_new)
        self.table = new_table
        self.r_epoch += 2

    # -------------------------------------------------------------- recover
    @classmethod
    def recover(
        cls,
        fs: SimFS,
        *,
        kind: Union[str, Sequence[str]] = "queue",
        n_shards: int = 1,
        capacity: int,
        lanes: int,
        backend: str = "jnp",
        n_threads: int = 1,
        n_buckets: Optional[int] = None,
        table=None,
        pipeline: bool = False,
        depth: Optional[int] = None,
        chain: int = 1,
        ring_slots: int = 2048,
        split_lanes: bool = False,
        obs=None,
    ) -> Tuple["ShardedDFCRuntime", Dict[int, Dict[str, Any]]]:
        """Recover the fabric + per-thread/per-op detectability report.

        Topology first: the durable routing record (if any) overrides the
        caller's ``kind`` / ``n_shards`` / ``table`` bootstrap arguments, so
        a fabric that resharded before the crash comes back with its
        post-reshard shape (pass the construction-time ``table`` when
        recovering a custom-routed fabric that never resharded — the first
        reshard is what makes the topology durable).
        An interrupted reshard is resolved by its intent record: rolled
        FORWARD when the routing epoch committed (finish the touched shards'
        cEpoch bumps — their slot data was pfenced before the commit point),
        rolled BACK otherwise (old routing; the per-shard GC reclaims the
        orphaned slot writes).

        Then per shard: round an odd durable epoch up to even (finish the
        interrupted second increment, paper lines 28-30), garbage-collect the
        inactive slot (§4), and reload the active slot (or a fresh init when
        the shard never committed).  Per announced op: applied iff its
        shard's committed epoch reached the target recorded with the
        response; everything else is reported not-applied and is safe to
        re-announce (see ``replay_pending``).

        Overlap-aware (pipelined path): a thread's OLDER announcement slot
        may hold an in-flight predecessor — batch k, combined by the
        pipeline but never retired (no durable responses) or retired but not
        committed — while its newest slot holds batch k+1.  Recovery
        resolves it: when the predecessor never fully committed, the report
        carries its verdicts under ``report[t]["prev"]`` and
        ``replay_pending`` re-announces it BEFORE the newest batch, keeping
        per-thread op order.  A fully committed predecessor is ordinary
        history (its durable responses are readable via
        ``read_responses(t, token=...)``) and is not reported.
        """
        # Attach the observer FIRST so recovery's own repair writes join the
        # durable timeline the pre-crash incarnation left behind (the
        # recorder continues the sidecar's sequence numbering).
        obs = obs if obs is not None else NULL_OBS
        if obs.enabled:
            fs.obs = obs
            obs.event(EV_RECOVER, stage="begin")

        # --- routing epoch: round odd up (finish the second increment)
        raw = fs.read(cls._REPOCH_PATH)
        repoch = int(raw.decode()) if raw else 0
        if repoch % 2 == 1:
            repoch += 1
            fs.write(cls._REPOCH_PATH, str(repoch).encode(), tag="recovery")
            fs.fsync([cls._REPOCH_PATH], tag="recovery")

        # --- adopt the committed routing record, if any
        kinds = [kind] * n_shards if isinstance(kind, str) else list(kind)
        active_slot = f"routing/slot{(repoch // 2) % 2}.json"
        rec_raw = fs.read(active_slot)
        if rec_raw:
            rec = json.loads(rec_raw.decode())
            kinds = list(rec["kinds"])
            n_shards = int(rec["n_shards"])
            n_buckets = int(rec["n_buckets"])
            capacity = int(rec.get("capacity", capacity))
            lanes = int(rec.get("lanes", lanes))
            split_lanes = bool(rec.get("split_lanes", split_lanes))
            table = np.asarray(rec["table"], np.int32)

        # --- resolve an interrupted reshard via its intent record
        intent_raw = fs.read(cls._INTENT_PATH)
        if intent_raw:
            intent = json.loads(intent_raw.decode())
            if intent["target_repoch"] <= repoch:
                # committed: roll the touched shards' cEpochs forward (their
                # slot data was pfenced before the rEpoch commit).  Split
                # shards record a ``[eH, eT]`` lane pair; roll each
                # component forward and keep the pair in one atomic file.
                for sid_str, tgt in intent.get("target_epochs", {}).items():
                    p = f"shard_{int(sid_str)}/cEpoch"
                    raw_e = fs.read(p)
                    if isinstance(tgt, list):
                        txt = raw_e.decode() if raw_e else ""
                        cur = (
                            json.loads(txt)
                            if txt.lstrip().startswith("[")
                            else [0, int(txt)] if txt else [0, 0]
                        )
                        new = [max(int(cur[i]), int(tgt[i])) for i in (0, 1)]
                        if new != [int(cur[0]), int(cur[1])]:
                            fs.write(p, json.dumps(new).encode(), tag="recovery")
                            fs.fsync([p], tag="recovery")
                        continue
                    cur = int(raw_e.decode()) if raw_e else 0
                    if cur < int(tgt):
                        fs.write(p, str(int(tgt)).encode(), tag="recovery")
                        fs.fsync([p], tag="recovery")
            else:
                # aborted: routing and shard epochs are still pre-reshard;
                # drop the half-written inactive routing slot
                fs.delete(f"routing/slot{(repoch // 2 + 1) % 2}.json")
            fs.delete(cls._INTENT_PATH)

        rt = cls(
            kinds, n_shards, capacity, lanes,
            backend=backend, fs=fs, n_threads=n_threads,
            n_buckets=n_buckets, table=table,
            pipeline=pipeline, depth=depth, chain=chain, ring_slots=ring_slots,
            split_lanes=split_lanes, obs=obs,
        )
        rt.r_epoch = repoch

        shard_states = []
        phases = np.zeros((n_shards,), np.int32)
        ops_combined = np.zeros((n_shards,), np.int32)
        committed_epochs = np.zeros((n_shards,), np.int64)
        committed_lane_epochs: Dict[int, List[int]] = {}
        for s in range(n_shards):
            fresh = STRUCTS[kinds[s]].init(capacity)
            if rt._is_split(s):
                # --- split shard: round each lane's odd epoch component up
                # (the composite pair file keeps a handoff's two components
                # atomic — a crash can never land between them), reload the
                # two ACTIVE lane records, and reassemble one state
                pair = rt._read_lane_epochs(s)
                if any(e % 2 == 1 for e in pair):
                    pair = [e + (e % 2) for e in pair]
                    fs.write(
                        rt._epoch_path(s), json.dumps(pair).encode(),
                        tag="recovery",
                    )
                    fs.fsync([rt._epoch_path(s)], tag="recovery")
                committed_lane_epochs[s] = list(pair)
                rt.lane_epochs[s] = list(pair)
                committed_epochs[s] = pair[0] + pair[1]
                recs: List[Optional[Dict[str, Any]]] = [None, None]
                live = set()
                for lane in (LANE_HEAD, LANE_TAIL):
                    adir = rt._lane_slot_dir(s, lane, pair[lane], nxt=False)
                    rrel = f"{adir}/rec.json"
                    raw_rec = fs.read_durable(rrel)
                    if raw_rec:
                        recs[lane] = json.loads(raw_rec.decode())
                        live.add(rrel)
                        if _LANE_WRITES_VALUES[kinds[s]][lane]:
                            live.add(f"{adir}/values.npy")
                f_ends = np.asarray(fresh.ends)[0]
                h = int(recs[LANE_HEAD]["ctr"]) if recs[LANE_HEAD] else int(f_ends[0])
                t = int(recs[LANE_TAIL]["ctr"]) if recs[LANE_TAIL] else int(f_ends[1])
                # values: the lane whose record carries the larger ``phases``
                # commit-sequence number holds the chronologically last
                # committed copy (each values-owning lane re-validates its
                # slot's values at every commit, elided when identical)
                values = np.asarray(fresh.values)
                best = (-1, None)
                for lane in (LANE_HEAD, LANE_TAIL):
                    r = recs[lane]
                    if r is None or not _LANE_WRITES_VALUES[kinds[s]][lane]:
                        continue
                    if int(r.get("phases", 0)) > best[0]:
                        adir = rt._lane_slot_dir(s, lane, pair[lane], nxt=False)
                        best = (int(r.get("phases", 0)), f"{adir}/values.npy")
                if best[1] is not None:
                    raw_v = fs.read_durable(best[1])
                    if raw_v:
                        values = np.load(io.BytesIO(raw_v))
                shard_states.append(
                    fresh.__class__(
                        values=jnp.asarray(values),
                        ends=jnp.asarray([[h, t], [h, t]], jnp.int32),
                        epoch=jnp.asarray(pair[0] + pair[1], jnp.int32),
                    )
                )
                phases[s] = max(
                    int(r.get("phases", 0)) for r in recs if r is not None
                ) if any(r is not None for r in recs) else 0
                ops_combined[s] = max(
                    int(r.get("ops_combined", 0)) for r in recs if r is not None
                ) if any(r is not None for r in recs) else 0
                # GC: drop partial lane-slot writes of the interrupted phase
                for lane in (LANE_HEAD, LANE_TAIL):
                    for p in (0, 1):
                        d = f"shard_{s}/lane{_LANE_TAGS[lane]}{p}"
                        for rel in list(fs.listdir(d)):
                            if rel not in live:
                                fs.delete(rel)
                continue
            epoch = rt._read_shard_epoch(s)
            if epoch % 2 == 1:  # crashed between the two increments
                epoch += 1
                fs.write(rt._epoch_path(s), str(epoch).encode(), tag="recovery")
                fs.fsync([rt._epoch_path(s)], tag="recovery")
            committed_epochs[s] = epoch
            active = rt._slot_dir(s, epoch, nxt=False)
            inactive = rt._slot_dir(s, epoch, nxt=True)
            meta_raw = fs.read_durable(f"{active}/meta.json")
            live = {f"{active}/meta.json"}
            if meta_raw:
                meta = json.loads(meta_raw.decode())
                live |= {f"{active}/{e['file']}" for e in meta["leaves"]}
                leaves = [
                    np.load(io.BytesIO(fs.read_durable(f"{active}/{e['file']}")))
                    for e in meta["leaves"]
                ]
                treedef = jax.tree_util.tree_structure(fresh)
                shard_states.append(
                    jax.tree_util.tree_unflatten(
                        treedef, [jnp.asarray(leaf) for leaf in leaves]
                    )
                )
                phases[s] = meta.get("phases", 0)
                ops_combined[s] = meta.get("ops_combined", 0)
            else:
                shard_states.append(fresh)
            # GC: drop partial writes of the interrupted phase
            for rel in list(fs.listdir(active)) + list(fs.listdir(inactive)):
                if rel not in live:
                    fs.delete(rel)

        rt.groups = {
            k: stack_shards([shard_states[s] for s in ids])
            for k, ids in _group_ids(tuple(kinds)).items()
        }
        rt.meta = {
            "phases": jnp.asarray(phases),
            "ops_combined": jnp.asarray(ops_combined),
            "kind": jnp.asarray([KIND_CODES[k] for k in kinds], jnp.int32),
        }

        def _slot_verdicts(ann) -> Tuple[List[OpVerdict], bool]:
            """Per-op verdicts of one announcement record + whether the
            record's phase fully committed (every target epoch reached).
            Split-lane ops carry their LANE's target: committed iff that
            lane's composite-epoch component reached it — the other lane's
            progress neither commits nor rolls back this op."""
            verdicts: List[OpVerdict] = []
            val = ann.get("val")
            n_ops = len(ann.get("ops", []))
            if val is BOT:
                return [OpVerdict(applied=False) for _ in range(n_ops)], False
            op_lanes = val.get("lanes")
            fully = True
            for i in range(n_ops):
                s = val["shards"][i]
                k = val["kinds"][i]
                ln = op_lanes[i] if op_lanes is not None else LANE_NONE
                if ln != LANE_NONE and s in committed_lane_epochs:
                    committed = committed_lane_epochs[s][ln] >= val["targets"][i]
                else:
                    committed = committed_epochs[s] >= val["targets"][i]
                fully = fully and bool(committed)
                applied = bool(committed) and k != R_OVERFLOW and k != R_NONE
                verdicts.append(
                    OpVerdict(
                        applied=applied,
                        kind=k if committed else None,
                        resp=val["resp"][i] if committed else None,
                        shard=s,
                    )
                )
            return verdicts, fully

        report: Dict[int, Dict[str, Any]] = {}
        for t in range(n_threads):
            v = rt._read_valid(t)
            lsb = v & 1
            if (v >> 1) & 1 == 0:  # re-publish a half-written valid selector
                fs.write(rt._valid_path(t), str(2 | lsb).encode(), tag="recovery")
            ann = rt._read_ann(t, lsb)
            if ann.get("token", -1) < 0:
                report[t] = {"token": None, "ops": [], "prev": None}
                continue
            verdicts, _ = _slot_verdicts(ann)
            # overlap-aware: the OLDER slot may hold an in-flight PREDECESSOR
            # (combined by the pipeline, never retired or never committed).
            # Only a SMALLER token qualifies (per-thread tokens are monotone):
            # a larger one is an unpublished successor whose announce crashed
            # before the valid flip — never announced, the thread re-runs it.
            prev = None
            pann = rt._read_ann(t, 1 - lsb)
            ptok = pann.get("token", -1)
            if 0 <= ptok < ann["token"] and pann.get("ops"):
                pverdicts, pfully = _slot_verdicts(pann)
                if not pfully:
                    prev = {"token": ptok, "ops": pverdicts}
            report[t] = {"token": ann["token"], "ops": verdicts, "prev": prev}
            if ann.get("val") is BOT:
                # still pending: re-stage it (ring re-filled from the durable
                # mirror) so a post-recovery combine_phase can run unchanged
                rt._register_live(
                    t, lsb, ann["token"], ann["keys"], ann["ops"], ann["params"]
                )
        if obs.enabled:
            # Extend the pre-crash durable trace prefix with the recovery
            # timeline: one verdict event per announced thread, then flush
            # the sidecar explicitly (a sanctioned host-side flush point —
            # recovery has no pfence of its own to ride here).
            for t, rep in report.items():
                if rep["token"] is None:
                    continue
                obs.event(
                    EV_VERDICT,
                    thread=t,
                    token=rep["token"],
                    applied=[bool(v.applied) for v in rep["ops"]],
                    prev_token=(rep["prev"] or {}).get("token"),
                    prev_applied=[
                        bool(v.applied) for v in (rep["prev"] or {}).get("ops", [])
                    ],
                )
            obs.event(
                EV_RECOVER,
                stage="end",
                repoch=repoch,
                epochs=[int(e) for e in committed_epochs],
                threads=sum(1 for r in report.values() if r["token"] is not None),
            )
            obs.flush()
        return rt, report

    def replay_pending(self, report: Dict[int, Dict[str, Any]]) -> List[int]:
        """Re-announce exactly the not-applied ops of every thread (read back
        from the durable announcement records) and run one combining phase —
        the exactly-once resume step after a crash mid-phase or mid-reshard.
        Returns the thread ids that were replayed.

        Ops whose phase committed with an ``R_NONE`` response are NOT
        replayed: they completed as no-ops (an op code the target structure
        does not interpret, legal in mixed fabrics) and would no-op again on
        every replay forever.  Uncommitted ops (``kind is None``) and
        ``R_OVERFLOW`` rejections are replayed.

        Overlap-aware: when recovery reported an in-flight PREDECESSOR batch
        (``report[t]["prev"]``, pipelined path), its not-applied ops are
        replayed in a round of their own BEFORE the newest announcements, so
        per-thread op order survives the crash."""

        def _redo(ann, verdicts):
            if not ann.get("ops"):
                return None
            idx = [
                i for i, v in enumerate(verdicts)
                if not v.applied and v.kind != R_NONE
            ]
            if not idx:
                return None
            return (
                [ann["keys"][i] for i in idx],
                [ann["ops"][i] for i in idx],
                [ann["params"][i] for i in idx],
            )

        # snapshot both slots' durable records BEFORE any re-announcement
        # flips the valid selectors
        prev_round: List[Tuple[int, int, Tuple]] = []
        newest_round: List[Tuple[int, int, Dict[str, Any], List[OpVerdict]]] = []
        for t in sorted(report):
            r = report[t]
            lsb = self._read_valid(t) & 1
            prev = r.get("prev")
            if prev is not None:
                pann = self._read_ann(t, 1 - lsb)
                if pann.get("token", -1) == prev["token"]:
                    redo = _redo(pann, prev["ops"])
                    if redo is not None:
                        prev_round.append((t, prev["token"], redo))
            if r["token"] is None:
                continue
            ann = self._read_ann(t, lsb)
            if _redo(ann, r["ops"]) is not None:
                newest_round.append((t, r["token"], ann, r["ops"]))

        replayed = set()
        # round 1: in-flight predecessors, so per-thread op order survives
        for t, token, (keys, ops, params) in prev_round:
            self.announce(t, keys, ops, params, token=token)
            replayed.add(t)
        if prev_round:
            self._drain()

        # round 2: newest announcements.  A still-PENDING one (val BOT at
        # recovery) may have been swept up by round 1's combining phase —
        # the combiner takes every ready announcement — in which case it is
        # now applied and committed, and only its R_OVERFLOW rejections
        # (which never touch state) still need a replay.
        for t, token, ann, verdicts in newest_round:
            pre_combined = any(v.shard is not None for v in verdicts)
            if not pre_combined:
                val = self.read_responses(t, token=token)
                if val is not None:
                    idx = [
                        i for i, k in enumerate(val["kinds"]) if k == R_OVERFLOW
                    ]
                    if not idx:
                        continue
                    self.announce(
                        t,
                        [ann["keys"][i] for i in idx],
                        [ann["ops"][i] for i in idx],
                        [ann["params"][i] for i in idx],
                        token=token,
                    )
                    replayed.add(t)
                    continue
            keys, ops, params = _redo(ann, verdicts)
            self.announce(t, keys, ops, params, token=token)
            replayed.add(t)
        if replayed:
            self._drain()
        return sorted(replayed)

    # -------------------------------------------------------------- helpers
    def shard_contents(self, s: int) -> List[float]:
        """Committed contents of shard ``s`` (bottom-to-top / left-to-right)."""
        one = self._shard_state(s)
        if self.kinds[s] == "stack":
            top = int(one.active_size())
            return [float(v) for v in np.asarray(one.values[:top])]
        if self.kinds[s] == "map":
            occ = np.asarray(one.occupied)
            mk = np.asarray(one.keys)
            mv = np.asarray(one.values)
            return [
                (int(mk[i]), float(mv[i]))
                for i in range(occ.shape[0])
                if occ[i]
            ]
        values = np.asarray(one.values)  # one fetch, not one per element
        lo, hi = (int(x) for x in np.asarray(one.active_ends()))
        return [float(v) for v in values[np.arange(lo, hi) % values.shape[0]]]

    def shard_sizes(self) -> np.ndarray:
        """Committed sizes of every shard (for hot/cold reshard policies) —
        read from the active root counters, without materializing contents."""
        out = np.zeros((self.n_shards,), np.int64)
        for k, ids in _group_ids(tuple(self.kinds)).items():
            st = self.groups[k]
            rows = np.arange(len(ids))
            active = (np.asarray(st.epoch) // 2) % 2
            if k == "stack":
                sizes = np.asarray(st.size)[rows, active]
            elif k == "map":
                sizes = np.asarray(st.count)[rows, active]
            else:
                ends = np.asarray(st.ends)[rows, active]  # [Sg, 2]
                sizes = ends[:, 1] - ends[:, 0]
            out[np.asarray(ids)] = sizes
        return out
