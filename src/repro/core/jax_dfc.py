"""TPU-native DFC: the paper's combiners as data-parallel JAX ops.

All three of the paper's structures — LIFO stack, FIFO queue, double-ended
queue — are expressed as array-backed states with double-buffered root
pointers and a one-pass vectorized ``combine``:

  * stack: ``values[capacity]`` + two alternating ``size`` pointers,
  * queue: a ring ``values[capacity]`` + double-buffered ``(head, tail)``
    absolute counters (``ends[2, 2]``); slot = counter % capacity,
  * deque: the same ring with double-buffered ``(left, right)`` counters —
    the window [left, right) grows left on pushL and right on pushR.

A combine phase only writes ring slots *outside* the committed window and
publishes by writing the inactive counter pair with an epoch bump of +2
(contract: capacity >= committed size + lanes), so a crash mid-combine
leaves the committed state intact — exactly the paper's alternating-root
crash-consistency argument.

The paper's combiner walks an announcement array sequentially, eliminating
push/pop pairs and applying the surplus to a linked-list structure.  Here the
same *semantic combining* is done in one vectorized pass over the
announcement lanes:

  * rank-matching elimination — the k-th announced push pairs with the k-th
    announced pop (all batch ops are concurrent, so any pairing linearizes);
    computed with prefix sums over the lane masks,
  * the stack is an array `values[capacity]` with **two alternating size
    pointers** `size[2]` — exactly the paper's two `top`s: both sizes share
    the storage prefix, a combine phase only writes *above* the committed
    prefix (surplus pushes) and publishes by flipping the active size with an
    epoch bump of +2.  A crash mid-combine leaves the active prefix intact.
  * all permutations (rank-compaction, pair-value routing) are expressed as
    one-hot matmuls so the hot path maps onto the MXU (see
    `repro/kernels/dfc_reduce` for the Pallas kernel of this function).

Linearization order of a combined batch (the canonical witness used by the
tests): eliminated pairs first (push_k, pop_k adjacent, k ascending), then
surplus pushes in rank order, then surplus pops in rank order.

The host-side persistence protocol (pwb/pfence analogue: device→host fetch +
fsync; two-increment epoch commit) lives in `repro.checkpoint`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

# op codes (stack/queue: enq==push, deq==pop)
OP_NONE = 0
OP_PUSH = 1
OP_POP = 2
OP_ENQ = OP_PUSH
OP_DEQ = OP_POP
# deque op codes
OP_PUSHL = 1
OP_POPL = 2
OP_PUSHR = 3
OP_POPR = 4
# serving-tier aliases: priority admission runs a request shard as a deque —
# a normal arrival joins the BACK of the line (pushR), admission drains the
# FRONT (popL), and a high-priority arrival jumps the line (pushL).  Note
# OP_POP_FRONT == OP_DEQ == 2, so one admission op code serves both queue
# and deque request shards.
OP_PUSH_BACK = OP_PUSHR
OP_PUSH_FRONT = OP_PUSHL
OP_POP_FRONT = OP_POPL
# response kinds
R_NONE = 0
R_ACK = 1
R_VALUE = 2
R_EMPTY = 3
# keyed-map op codes (interpreted by map shards; see MapState below)
OP_MAP_INSERT = 1
OP_MAP_LOOKUP = 2
OP_MAP_DELETE = 3
OP_MAP_CAS = 4
# map response kinds: code 4 is reserved for the runtime-level R_OVERFLOW
# (repro.runtime.dfc_shard), so the map's rejections start at 5 — both are
# DEFINITIVE verdicts (the op completed without touching state), unlike
# R_OVERFLOW which marks an op that never reached its shard.
R_FULL = 5  # insert into a full bucket: clean rejection, no write
R_CAS_FAIL = 6  # CAS found the key but the expected value did not match
# OP_MAP_CAS packs (expected, new) into ONE f32 param as
# ``expected * CAS_DOM + new``, both operands in [0, CAS_DOM).  The maximum
# packed value CAS_DOM**2 - 1 == 2**24 - 1 is exactly the top of f32's
# contiguous-integer range, so the packing is lossless end to end (including
# the JSON durable mirror, which cannot carry NaN-boxed payloads).
CAS_DOM = 4096
# slots per hash bucket of a map shard (the fixed probe window)
MAP_BUCKET_SLOTS = 8


def pack_cas(expected: int, new: int) -> float:
    """Pack a CAS ``(expected, new)`` pair into one f32-exact op param.

    Owns the CAS packing domain: both operands must sit in ``[0, CAS_DOM)``
    or the packed value would alias a DIFFERENT (expected, new) pair — the
    combine unpacks with floor-divide, so an out-of-range operand wraps
    silently into the other field.  Callers that widen their own value
    encodings (e.g. the serving tier's session states) route through here
    so the domain check cannot be forgotten.
    """
    expected, new = int(expected), int(new)
    if not 0 <= expected < CAS_DOM:
        raise ValueError(f"CAS expected value {expected} outside [0, {CAS_DOM})")
    if not 0 <= new < CAS_DOM:
        raise ValueError(f"CAS new value {new} outside [0, {CAS_DOM})")
    packed = expected * CAS_DOM + new
    # CAS_DOM**2 - 1 == 2**24 - 1: the top of f32's contiguous-integer range
    assert packed < CAS_DOM * CAS_DOM and float(np.float32(packed)) == packed
    return float(packed)


def unpack_cas(packed) -> Tuple[int, int]:
    """Invert :func:`pack_cas` -> ``(expected, new)``."""
    p = int(packed)
    if not 0 <= p < CAS_DOM * CAS_DOM:
        raise ValueError(f"packed CAS param {p} outside [0, {CAS_DOM ** 2})")
    return p // CAS_DOM, p % CAS_DOM

# announcement lanes (per-side combiners, ISSUE 8): every op code of a
# two-sided structure belongs to exactly one combining lane — the HEAD lane
# (the consuming side: queue dequeues, deque left-side ops) or the TAIL lane
# (the producing side: queue enqueues, deque right-side ops).  Single-sided
# structures (the stack) have one combiner and no lane split.  LANE_NONE
# marks op codes with no lane (OP_NONE, or any op on a single-lane kind).
LANE_NONE = -1
LANE_HEAD = 0
LANE_TAIL = 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StackState:
    """Array-backed DFC stack with double-buffered top (paper Fig 1)."""

    values: jax.Array  # f32[capacity]
    size: jax.Array  # i32[2] — two alternating stack sizes
    epoch: jax.Array  # i32[]  — cEpoch (always even between phases)

    @property
    def active_idx(self) -> jax.Array:
        return (self.epoch // 2) % 2

    def active_size(self) -> jax.Array:
        return self.size[self.active_idx]


def init_stack(capacity: int, dtype=jnp.float32) -> StackState:
    return StackState(
        values=jnp.zeros((capacity,), dtype=dtype),
        size=jnp.zeros((2,), dtype=jnp.int32),
        epoch=jnp.zeros((), dtype=jnp.int32),
    )


def _onehot_route(src_idx: jax.Array, vals: jax.Array, n_out: int) -> jax.Array:
    """out[src_idx[j]] += vals[j] — as a one-hot matmul (MXU-friendly).

    src_idx entries outside [0, n_out) are dropped.  HIGHEST precision keeps
    f32 payloads exact on the TPU, whose default f32 matmul rounds inputs
    to bf16.
    """
    onehot = (src_idx[None, :] == jnp.arange(n_out)[:, None]).astype(vals.dtype)
    return jnp.dot(onehot, vals, precision=jax.lax.Precision.HIGHEST)


def combine(
    state: StackState, ops: jax.Array, params: jax.Array
) -> Tuple[StackState, jax.Array, jax.Array]:
    """One DFC combining phase over N announcement lanes.

    Returns (new_state, responses f32[N], kinds i32[N]).
    """
    n = ops.shape[0]
    cap = state.values.shape[0]
    idx = jnp.arange(n)

    is_push = ops == OP_PUSH
    is_pop = ops == OP_POP
    push_rank = jnp.where(is_push, jnp.cumsum(is_push) - 1, -1)
    pop_rank = jnp.where(is_pop, jnp.cumsum(is_pop) - 1, -1)
    p_total = jnp.sum(is_push)
    q_total = jnp.sum(is_pop)
    n_elim = jnp.minimum(p_total, q_total)

    old_size = state.active_size()

    # --- elimination: pop_k gets push_k's param (REDUCE lines 102-110) ------
    push_by_rank = _onehot_route(push_rank, params.astype(jnp.float32), n)
    elim_pop_val = push_by_rank[jnp.clip(pop_rank, 0, n - 1)]

    # --- surplus pushes: compact above the committed prefix -----------------
    surplus_push = is_push & (push_rank >= n_elim)
    seg_idx = jnp.where(surplus_push, push_rank - n_elim, n)  # n => dropped
    segment = _onehot_route(seg_idx, params.astype(state.values.dtype), n)
    n_push_surplus = jnp.maximum(p_total - n_elim, 0)
    new_values = jax.lax.dynamic_update_slice(
        state.values,
        segment,
        (jnp.clip(old_size, 0, cap - n),),
    )
    # only the [old_size, old_size + n_push_surplus) part of the segment is
    # real; restore the tail beyond it.  Contract: capacity >= size + N.
    keep_mask = (jnp.arange(cap) >= old_size) & (
        jnp.arange(cap) < old_size + n_push_surplus
    )
    new_values = jnp.where(keep_mask, new_values, state.values)

    # --- surplus pops: read below the committed prefix ----------------------
    surplus_pop = is_pop & (pop_rank >= n_elim)
    depth = pop_rank - n_elim  # 0 == top of committed stack
    pop_src = old_size - 1 - depth
    pop_ok = surplus_pop & (pop_src >= 0)
    stack_val = state.values[jnp.clip(pop_src, 0, cap - 1)].astype(jnp.float32)

    # --- responses -----------------------------------------------------------
    kinds = jnp.full((n,), R_NONE, dtype=jnp.int32)
    kinds = jnp.where(is_push, R_ACK, kinds)
    kinds = jnp.where(is_pop & (pop_rank < n_elim), R_VALUE, kinds)
    kinds = jnp.where(pop_ok, R_VALUE, kinds)
    kinds = jnp.where(surplus_pop & ~pop_ok, R_EMPTY, kinds)
    responses = jnp.zeros((n,), dtype=jnp.float32)
    responses = jnp.where(is_pop & (pop_rank < n_elim), elim_pop_val, responses)
    responses = jnp.where(pop_ok, stack_val, responses)

    # --- publish: write the inactive size, bump epoch by 2 -------------------
    n_popped = jnp.minimum(jnp.maximum(q_total - n_elim, 0), old_size)
    new_size_val = old_size + n_push_surplus - n_popped
    inactive = (state.epoch // 2 + 1) % 2
    new_size = state.size.at[inactive].set(new_size_val)
    new_state = StackState(
        values=new_values, size=new_size, epoch=state.epoch + 2
    )
    return new_state, responses, kinds


combine_jit = jax.jit(combine)


# ------------------------------------------------------------------ reference
def sequential_reference(stack_list, ops, params):
    """Canonical linearization witness in pure Python (test oracle).

    Applies: eliminated pairs, then surplus pushes (rank order), then surplus
    pops (rank order) to a Python list; returns (new_list, responses, kinds).
    """
    n = len(ops)
    pushes = [i for i in range(n) if ops[i] == OP_PUSH]
    pops = [i for i in range(n) if ops[i] == OP_POP]
    e = min(len(pushes), len(pops))
    responses = [0.0] * n
    kinds = [R_NONE] * n
    stack = list(stack_list)
    for k in range(e):  # eliminated pairs
        kinds[pushes[k]] = R_ACK
        kinds[pops[k]] = R_VALUE
        responses[pops[k]] = float(params[pushes[k]])
    for i in pushes[e:]:  # surplus pushes
        stack.append(float(params[i]))
        kinds[i] = R_ACK
    for i in pops[e:]:  # surplus pops
        if stack:
            responses[i] = stack.pop()
            kinds[i] = R_VALUE
        else:
            kinds[i] = R_EMPTY
    return stack, responses, kinds


# ======================================================================= queue
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QueueState:
    """Ring-backed DFC queue with double-buffered (head, tail) counters.

    ``ends[b] = (head, tail)`` are absolute (monotone) counters; the occupied
    window is [head, tail), slot index = counter % capacity.
    """

    values: jax.Array  # f32[capacity] ring
    ends: jax.Array  # i32[2, 2] — two alternating (head, tail) pairs
    epoch: jax.Array  # i32[]  — cEpoch (always even between phases)

    @property
    def active_idx(self) -> jax.Array:
        return (self.epoch // 2) % 2

    def active_ends(self) -> jax.Array:
        return self.ends[self.active_idx]

    def active_size(self) -> jax.Array:
        e = self.active_ends()
        return e[1] - e[0]


def init_queue(capacity: int, dtype=jnp.float32) -> QueueState:
    return QueueState(
        values=jnp.zeros((capacity,), dtype=dtype),
        ends=jnp.zeros((2, 2), dtype=jnp.int32),
        epoch=jnp.zeros((), dtype=jnp.int32),
    )


def combine_queue(
    state: QueueState, ops: jax.Array, params: jax.Array
) -> Tuple[QueueState, jax.Array, jax.Array]:
    """One DFC queue combining phase over N announcement lanes.

    Linearization witness (shared with ``sequential_reference_queue`` and the
    Pallas kernel): dequeues drain the committed window FIFO; once drained,
    deq rank size+k pairs with enq rank k (two-sided elimination — the value
    flows announcement-to-announcement); surplus enqueues append in rank
    order; deqs beyond every enqueue return EMPTY.

    Returns (new_state, responses f32[N], kinds i32[N]).
    """
    n = ops.shape[0]
    cap = state.values.shape[0]
    ends = state.active_ends()
    head, tail = ends[0], ends[1]
    size = tail - head

    is_enq = ops == OP_ENQ
    is_deq = ops == OP_DEQ
    enq_rank = jnp.where(is_enq, jnp.cumsum(is_enq) - 1, -1)
    deq_rank = jnp.where(is_deq, jnp.cumsum(is_deq) - 1, -1)
    p_total = jnp.sum(is_enq)
    q_total = jnp.sum(is_deq)
    n_from_q = jnp.minimum(q_total, size)  # deqs served from the ring
    n_elim = jnp.minimum(jnp.maximum(q_total - size, 0), p_total)

    # --- deqs served FIFO from the committed window -------------------------
    served = is_deq & (deq_rank < size)
    ring_val = state.values[(head + jnp.clip(deq_rank, 0, None)) % cap].astype(
        jnp.float32
    )

    # --- drained: deq rank size+k pairs with enq rank k ---------------------
    enq_by_rank = _onehot_route(enq_rank, params.astype(jnp.float32), n)
    paired = is_deq & (deq_rank >= size) & (deq_rank - size < n_elim)
    pair_val = enq_by_rank[jnp.clip(deq_rank - size, 0, n - 1)]
    empty = is_deq & (deq_rank >= size + n_elim)

    # --- surplus enqs append at the tail ------------------------------------
    surplus_enq = is_enq & (enq_rank >= n_elim)
    n_enq_surplus = p_total - n_elim
    seg_idx = jnp.where(surplus_enq, enq_rank - n_elim, n)
    segment = _onehot_route(seg_idx, params.astype(state.values.dtype), n)
    pos = (tail + jnp.arange(n)) % cap
    write = jnp.arange(n) < n_enq_surplus
    new_values = state.values.at[jnp.where(write, pos, cap)].set(
        segment, mode="drop"
    )

    # --- responses -----------------------------------------------------------
    kinds = jnp.full((n,), R_NONE, dtype=jnp.int32)
    kinds = jnp.where(is_enq, R_ACK, kinds)
    kinds = jnp.where(served | paired, R_VALUE, kinds)
    kinds = jnp.where(empty, R_EMPTY, kinds)
    responses = jnp.zeros((n,), dtype=jnp.float32)
    responses = jnp.where(served, ring_val, responses)
    responses = jnp.where(paired, pair_val, responses)

    # --- publish: write the inactive (head, tail), bump epoch by 2 -----------
    new_ends = jnp.stack([head + n_from_q, tail + n_enq_surplus])
    inactive = (state.epoch // 2 + 1) % 2
    new_state = QueueState(
        values=new_values,
        ends=state.ends.at[inactive].set(new_ends),
        epoch=state.epoch + 2,
    )
    return new_state, responses, kinds


combine_queue_jit = jax.jit(combine_queue)


def sequential_reference_queue(queue_list, ops, params):
    """Canonical queue linearization witness in pure Python (test oracle)."""
    n = len(ops)
    enqs = [i for i in range(n) if ops[i] == OP_ENQ]
    deqs = [i for i in range(n) if ops[i] == OP_DEQ]
    responses = [0.0] * n
    kinds = [R_NONE] * n
    q = list(queue_list)
    for i in enqs:
        kinds[i] = R_ACK
    di = 0
    while di < len(deqs) and q:  # serve from the committed queue
        responses[deqs[di]] = q.pop(0)
        kinds[deqs[di]] = R_VALUE
        di += 1
    ei = 0
    while di < len(deqs) and ei < len(enqs):  # eliminated pairs
        responses[deqs[di]] = float(params[enqs[ei]])
        kinds[deqs[di]] = R_VALUE
        di += 1
        ei += 1
    while di < len(deqs):
        kinds[deqs[di]] = R_EMPTY
        di += 1
    for i in enqs[ei:]:  # surplus enqueues
        q.append(float(params[i]))
    return q, responses, kinds


# ======================================================================= deque
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DequeState:
    """Ring-backed DFC deque with double-buffered (left, right) counters.

    ``ends[b] = (left, right)``; the occupied window is [left, right), slot
    index = counter % capacity (counters may go negative — Python-style
    modulo keeps slots in range).
    """

    values: jax.Array  # f32[capacity] ring
    ends: jax.Array  # i32[2, 2] — two alternating (left, right) pairs
    epoch: jax.Array  # i32[]

    @property
    def active_idx(self) -> jax.Array:
        return (self.epoch // 2) % 2

    def active_ends(self) -> jax.Array:
        return self.ends[self.active_idx]

    def active_size(self) -> jax.Array:
        e = self.active_ends()
        return e[1] - e[0]


def init_deque(capacity: int, dtype=jnp.float32) -> DequeState:
    return DequeState(
        values=jnp.zeros((capacity,), dtype=dtype),
        ends=jnp.zeros((2, 2), dtype=jnp.int32),
        epoch=jnp.zeros((), dtype=jnp.int32),
    )


def combine_deque(
    state: DequeState, ops: jax.Array, params: jax.Array
) -> Tuple[DequeState, jax.Array, jax.Array]:
    """One DFC deque combining phase over N announcement lanes.

    Linearization witness (shared with ``sequential_reference_deque`` and the
    Pallas kernel): same-side eliminated pairs first (pushL_k;popL_k and
    pushR_k;popR_k adjacent — state untouched), then the LEFT surplus in rank
    order, then the RIGHT surplus in rank order.  Right surplus pops may
    therefore consume values pushed left in the same phase.

    Returns (new_state, responses f32[N], kinds i32[N]).
    """
    n = ops.shape[0]
    cap = state.values.shape[0]
    ends = state.active_ends()
    left, right = ends[0], ends[1]
    size = right - left

    is_pl = ops == OP_PUSHL
    is_ql = ops == OP_POPL
    is_pr = ops == OP_PUSHR
    is_qr = ops == OP_POPR
    pl_rank = jnp.where(is_pl, jnp.cumsum(is_pl) - 1, -1)
    ql_rank = jnp.where(is_ql, jnp.cumsum(is_ql) - 1, -1)
    pr_rank = jnp.where(is_pr, jnp.cumsum(is_pr) - 1, -1)
    qr_rank = jnp.where(is_qr, jnp.cumsum(is_qr) - 1, -1)
    npl, nql = jnp.sum(is_pl), jnp.sum(is_ql)
    npr, nqr = jnp.sum(is_pr), jnp.sum(is_qr)
    nl_elim = jnp.minimum(npl, nql)
    nr_elim = jnp.minimum(npr, nqr)

    # --- same-side elimination: pop_k gets push_k's param -------------------
    f32params = params.astype(jnp.float32)
    pl_by_rank = _onehot_route(pl_rank, f32params, n)
    pr_by_rank = _onehot_route(pr_rank, f32params, n)
    eliml = is_ql & (ql_rank < nl_elim)
    elimr = is_qr & (qr_rank < nr_elim)
    eliml_val = pl_by_rank[jnp.clip(ql_rank, 0, n - 1)]
    elimr_val = pr_by_rank[jnp.clip(qr_rank, 0, n - 1)]

    # --- left surplus (pushes XOR pops) -------------------------------------
    sl = jnp.maximum(npl - nl_elim, 0)  # surplus pushes left
    tl = jnp.maximum(nql - nl_elim, 0)  # surplus pops left
    surplus_pl = is_pl & (pl_rank >= nl_elim)
    seg_l = _onehot_route(
        jnp.where(surplus_pl, pl_rank - nl_elim, n), params.astype(state.values.dtype), n
    )
    # push j lands at slot left-1-j (later pushes further left)
    posl = (left - 1 - jnp.arange(n)) % cap
    vals1 = state.values.at[jnp.where(jnp.arange(n) < sl, posl, cap)].set(
        seg_l, mode="drop"
    )
    dl = jnp.minimum(tl, size)  # left pops consume the committed front
    surplus_ql = is_ql & (ql_rank >= nl_elim)
    kl = ql_rank - nl_elim
    lpop_ok = surplus_ql & (kl < size)
    lpop_val = state.values[(left + jnp.clip(kl, 0, None)) % cap].astype(jnp.float32)
    size_after = size + sl - dl  # window after the left surplus

    # --- right surplus (pushes XOR pops), applied after the left ------------
    sr = jnp.maximum(npr - nr_elim, 0)
    tr = jnp.maximum(nqr - nr_elim, 0)
    surplus_pr = is_pr & (pr_rank >= nr_elim)
    seg_r = _onehot_route(
        jnp.where(surplus_pr, pr_rank - nr_elim, n), params.astype(state.values.dtype), n
    )
    posr = (right + jnp.arange(n)) % cap
    new_values = vals1.at[jnp.where(jnp.arange(n) < sr, posr, cap)].set(
        seg_r, mode="drop"
    )
    dr = jnp.minimum(tr, size_after)
    surplus_qr = is_qr & (qr_rank >= nr_elim)
    kr = qr_rank - nr_elim
    rpop_ok = surplus_qr & (kr < size_after)
    # right pop k reads slot right-1-k: committed when k < size, otherwise a
    # value pushed left in this phase (vals1 holds both)
    rpop_val = vals1[(right - 1 - jnp.clip(kr, 0, None)) % cap].astype(jnp.float32)

    # --- responses -----------------------------------------------------------
    kinds = jnp.full((n,), R_NONE, dtype=jnp.int32)
    kinds = jnp.where(is_pl | is_pr, R_ACK, kinds)
    kinds = jnp.where(eliml | elimr | lpop_ok | rpop_ok, R_VALUE, kinds)
    kinds = jnp.where(surplus_ql & ~lpop_ok, R_EMPTY, kinds)
    kinds = jnp.where(surplus_qr & ~rpop_ok, R_EMPTY, kinds)
    responses = jnp.zeros((n,), dtype=jnp.float32)
    responses = jnp.where(eliml, eliml_val, responses)
    responses = jnp.where(elimr, elimr_val, responses)
    responses = jnp.where(lpop_ok, lpop_val, responses)
    responses = jnp.where(rpop_ok, rpop_val, responses)

    # --- publish: write the inactive (left, right), bump epoch by 2 ----------
    new_ends = jnp.stack([left - sl + dl, right + sr - dr])
    inactive = (state.epoch // 2 + 1) % 2
    new_state = DequeState(
        values=new_values,
        ends=state.ends.at[inactive].set(new_ends),
        epoch=state.epoch + 2,
    )
    return new_state, responses, kinds


combine_deque_jit = jax.jit(combine_deque)


def sequential_reference_deque(deque_list, ops, params):
    """Canonical deque linearization witness in pure Python (test oracle)."""
    n = len(ops)
    pl = [i for i in range(n) if ops[i] == OP_PUSHL]
    ql = [i for i in range(n) if ops[i] == OP_POPL]
    pr = [i for i in range(n) if ops[i] == OP_PUSHR]
    qr = [i for i in range(n) if ops[i] == OP_POPR]
    nl = min(len(pl), len(ql))
    nr = min(len(pr), len(qr))
    responses = [0.0] * n
    kinds = [R_NONE] * n
    d = list(deque_list)
    for k in range(nl):  # same-side eliminated pairs
        kinds[pl[k]] = R_ACK
        kinds[ql[k]] = R_VALUE
        responses[ql[k]] = float(params[pl[k]])
    for k in range(nr):
        kinds[pr[k]] = R_ACK
        kinds[qr[k]] = R_VALUE
        responses[qr[k]] = float(params[pr[k]])
    for i in pl[nl:]:  # left surplus first…
        d.insert(0, float(params[i]))
        kinds[i] = R_ACK
    for i in ql[nl:]:
        if d:
            responses[i] = d.pop(0)
            kinds[i] = R_VALUE
        else:
            kinds[i] = R_EMPTY
    for i in pr[nr:]:  # …then right surplus
        d.append(float(params[i]))
        kinds[i] = R_ACK
    for i in qr[nr:]:
        if d:
            responses[i] = d.pop()
            kinds[i] = R_VALUE
        else:
            kinds[i] = R_EMPTY
    return d, responses, kinds


# ========================================================================= map
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MapState:
    """Bucketed-hash DFC map with a double-buffered entry count.

    Fixed capacity, open addressing confined to one bucket: slot ``i``
    belongs to bucket ``i // bslots`` where ``bslots = min(capacity,
    MAP_BUCKET_SLOTS)``, and a key only ever lives in its hash bucket's
    ``bslots`` slots — an insert into a bucket with no free slot is a CLEAN
    rejection (``R_FULL``; state untouched).  Unlike the ring structures
    there is no committed/inactive split of the table itself: a combining
    phase mutates ``keys/values/occupied`` in place and durability comes
    from the runtime's slot-alternating full-state snapshots (the same
    generic ``_persist_shard`` path every kind rides).  Only ``count`` is
    double-buffered by epoch parity so committed sizes are readable without
    trusting an in-flight phase.
    """

    keys: jax.Array  # i32[capacity]
    values: jax.Array  # f32[capacity]
    occupied: jax.Array  # i32[capacity] — 0/1 per slot
    count: jax.Array  # i32[2] — two alternating live-entry counts
    epoch: jax.Array  # i32[]  — cEpoch (always even between phases)

    @property
    def active_idx(self) -> jax.Array:
        return (self.epoch // 2) % 2

    def active_count(self) -> jax.Array:
        return self.count[self.active_idx]


def map_geometry(capacity: int) -> Tuple[int, int]:
    """(slots per bucket, bucket count) of a map shard of ``capacity``.

    Capacity must be a multiple of the bucket width so every slot belongs
    to exactly one bucket.
    """
    bslots = min(capacity, MAP_BUCKET_SLOTS)
    if capacity % bslots != 0:
        raise ValueError(
            f"map capacity {capacity} not a multiple of bucket width {bslots}"
        )
    return bslots, capacity // bslots


def init_map(capacity: int, dtype=jnp.float32) -> MapState:
    map_geometry(capacity)  # validate up front
    return MapState(
        keys=jnp.zeros((capacity,), jnp.int32),
        values=jnp.zeros((capacity,), dtype=dtype),
        occupied=jnp.zeros((capacity,), jnp.int32),
        count=jnp.zeros((2,), jnp.int32),
        epoch=jnp.zeros((), jnp.int32),
    )


def map_bucket(keys, n_buckets: int) -> jax.Array:
    """Bucket of each key inside ONE map shard (device path).

    A second multiplicative mix, decorrelated from the router's shard hash
    (which stops after the first xor-shift): keys that collide into one
    shard still spread across its buckets.
    """
    h = jnp.asarray(keys).astype(jnp.uint32) * jnp.uint32(2654435761)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    return (h % jnp.uint32(n_buckets)).astype(jnp.int32)


def map_bucket_host(keys, n_buckets: int) -> np.ndarray:
    """NumPy twin of :func:`map_bucket` for host-side oracles and rebuilds."""
    h = (np.asarray(keys, np.uint64) * 2654435761) & 0xFFFFFFFF
    h = h ^ (h >> 16)
    h = (h * 2246822519) & 0xFFFFFFFF
    h = h ^ (h >> 13)
    return (h % n_buckets).astype(np.int32)


def combine_map(
    state: MapState, keys: jax.Array, ops: jax.Array, params: jax.Array
) -> Tuple[MapState, jax.Array, jax.Array]:
    """One DFC map combining phase over N keyed announcement lanes.

    Map ops do not commute (insert/delete/CAS on one key), so there is no
    elimination pass: the lanes are applied in announcement order by a
    ``lax.scan`` — the linearization IS lane order, shared with
    ``sequential_reference_map`` and the Pallas twin.  Per lane:

      op              hit                      miss
      --------------  -----------------------  -------------------------
      OP_MAP_INSERT   overwrite, R_ACK         free slot: write, R_ACK;
                                               bucket full: R_FULL
      OP_MAP_LOOKUP   R_VALUE (resp=value)     R_EMPTY
      OP_MAP_DELETE   clear slot, R_VALUE      R_EMPTY
      OP_MAP_CAS      match: write new,        R_EMPTY
                      R_VALUE (resp=old);
                      mismatch: R_CAS_FAIL
                      (resp=current)

    Returns (new_state, responses f32[N], kinds i32[N]).
    """
    cap = state.keys.shape[0]
    bslots, n_buckets = map_geometry(cap)
    slot_bucket = jnp.arange(cap, dtype=jnp.int32) // bslots
    slot_idx = jnp.arange(cap, dtype=jnp.int32)

    def lane(carry, xs):
        mk, mv, mo, cnt = carry
        key, op, par = xs
        in_b = slot_bucket == map_bucket(key, n_buckets)
        occ = mo != 0
        # key 0 is legal, so a hit needs the occupied flag, not just key match
        hit = in_b & occ & (mk == key)
        has_hit = jnp.any(hit)
        hit_idx = jnp.argmax(hit).astype(jnp.int32)
        free = in_b & ~occ
        has_free = jnp.any(free)
        free_idx = jnp.argmax(free).astype(jnp.int32)
        cur = mv[jnp.where(has_hit, hit_idx, 0)].astype(jnp.float32)

        is_ins = op == OP_MAP_INSERT
        is_lku = op == OP_MAP_LOOKUP
        is_del = op == OP_MAP_DELETE
        is_cas = op == OP_MAP_CAS
        expected = jnp.floor(par / CAS_DOM)
        cas_new = par - expected * CAS_DOM
        cas_hit = is_cas & has_hit
        cas_ok = cas_hit & (cur == expected)

        do_ins = is_ins & (has_hit | has_free)
        do_del = is_del & has_hit
        do_write = do_ins | cas_ok
        wslot = jnp.where(cas_ok | has_hit, hit_idx, free_idx)
        wval = jnp.where(is_cas, cas_new, par).astype(mv.dtype)
        wmask = do_write & (slot_idx == wslot)
        dmask = do_del & (slot_idx == hit_idx)
        mk = jnp.where(wmask, key, jnp.where(dmask, 0, mk))
        mv = jnp.where(wmask, wval, jnp.where(dmask, 0, mv))
        mo = jnp.where(wmask, 1, jnp.where(dmask, 0, mo))
        cnt = (
            cnt
            + (is_ins & ~has_hit & has_free).astype(jnp.int32)
            - do_del.astype(jnp.int32)
        )

        kind = jnp.full((), R_NONE, jnp.int32)
        kind = jnp.where(do_ins, R_ACK, kind)
        kind = jnp.where(is_ins & ~has_hit & ~has_free, R_FULL, kind)
        kind = jnp.where((is_lku | is_del | is_cas) & ~has_hit, R_EMPTY, kind)
        kind = jnp.where((is_lku | do_del | cas_ok) & has_hit, R_VALUE, kind)
        kind = jnp.where(cas_hit & ~cas_ok, R_CAS_FAIL, kind)
        resp = jnp.where((is_lku | is_del | is_cas) & has_hit, cur, 0.0)
        return (mk, mv, mo, cnt), (resp, kind.astype(jnp.int32))

    (mk, mv, mo, cnt), (responses, kinds) = jax.lax.scan(
        lane,
        (state.keys, state.values, state.occupied, state.active_count()),
        (
            jnp.asarray(keys).astype(jnp.int32),
            jnp.asarray(ops).astype(jnp.int32),
            jnp.asarray(params).astype(jnp.float32),
        ),
    )

    # --- publish: write the inactive count, bump epoch by 2 ------------------
    inactive = (state.epoch // 2 + 1) % 2
    new_state = MapState(
        keys=mk,
        values=mv,
        occupied=mo,
        count=state.count.at[inactive].set(cnt),
        epoch=state.epoch + 2,
    )
    return new_state, responses, kinds


combine_map_jit = jax.jit(combine_map)


def sequential_reference_map(entries, keys, ops, params, capacity=None):
    """Canonical map linearization witness in pure Python (test oracle).

    ``entries`` is a ``{int key: float value}`` dict; lanes apply in
    announcement order.  With ``capacity``, an insert of an ABSENT key is
    rejected ``R_FULL`` iff its hash bucket already holds ``bslots`` live
    keys — bucket occupancy depends only on the live-key set (deletes fully
    clear their slot), so the dict oracle models the fixed table exactly.
    CAS decode runs in float32 so the oracle's arithmetic is bit-identical
    to the device's.  Returns (new_entries, responses, kinds).
    """
    n = len(ops)
    responses = [0.0] * n
    kinds = [R_NONE] * n
    m = dict(entries)
    if capacity is not None:
        bslots, n_buckets = map_geometry(int(capacity))
        bucket_of = {
            k: int(map_bucket_host([k], n_buckets)[0]) for k in m
        }
    for i in range(n):
        op = int(ops[i])
        key = int(keys[i])
        par = float(np.float32(params[i]))
        if op == OP_MAP_INSERT:
            if key not in m and capacity is not None:
                b = int(map_bucket_host([key], n_buckets)[0])
                if sum(1 for v in bucket_of.values() if v == b) >= bslots:
                    kinds[i] = R_FULL
                    continue
                bucket_of[key] = b
            m[key] = par
            kinds[i] = R_ACK
        elif op == OP_MAP_LOOKUP:
            if key in m:
                responses[i] = m[key]
                kinds[i] = R_VALUE
            else:
                kinds[i] = R_EMPTY
        elif op == OP_MAP_DELETE:
            if key in m:
                responses[i] = m.pop(key)
                kinds[i] = R_VALUE
                if capacity is not None:
                    bucket_of.pop(key, None)
            else:
                kinds[i] = R_EMPTY
        elif op == OP_MAP_CAS:
            expected = float(np.floor(np.float32(par) / np.float32(CAS_DOM)))
            new = float(np.float32(par) - np.float32(expected) * np.float32(CAS_DOM))
            if key not in m:
                kinds[i] = R_EMPTY
            elif m[key] == expected:
                responses[i] = m[key]
                m[key] = new
                kinds[i] = R_VALUE
            else:
                responses[i] = m[key]
                kinds[i] = R_CAS_FAIL
    return m, responses, kinds


# ================================================================== registry
@dataclasses.dataclass(frozen=True)
class StructSpec:
    """One of the paper's structures, as seen by multi-object runtimes.

    ``init``/``combine``/``reference`` are the single-object entry points
    above; ``n_opcodes`` bounds the valid op-code range [0, n_opcodes) so a
    router can generate well-formed random workloads per structure.

    ``op_lanes`` maps each op code to its announcement lane (per-side
    combiners, ISSUE 8): ``LANE_HEAD`` for the consuming side (dequeue /
    left-side deque ops), ``LANE_TAIL`` for the producing side (enqueue /
    right-side deque ops), ``LANE_NONE`` for OP_NONE or any op on a
    single-lane kind.  A kind is lane-splittable iff some op code maps to
    each of the two lanes.
    """

    kind: str
    state_cls: type
    init: Callable[..., Any]
    combine: Callable[..., Any]
    reference: Callable[..., Any]
    n_opcodes: int
    op_lanes: Tuple[int, ...] = ()
    # keyed kinds interpret the announced KEY as part of the op (the map's
    # hash key), so their combine/reference take an extra keys argument:
    # ``combine(state, keys, ops, params)`` and
    # ``reference(contents, keys, ops, params, capacity=None)``.
    keyed: bool = False

    @property
    def lane_splittable(self) -> bool:
        return LANE_HEAD in self.op_lanes and LANE_TAIL in self.op_lanes


STRUCTS: Dict[str, StructSpec] = {
    "stack": StructSpec(
        "stack", StackState, init_stack, combine, sequential_reference, 3,
        op_lanes=(LANE_NONE, LANE_NONE, LANE_NONE),  # one combiner, no split
    ),
    "queue": StructSpec(
        "queue",
        QueueState,
        init_queue,
        combine_queue,
        sequential_reference_queue,
        3,
        # OP_ENQ produces at the tail, OP_DEQ consumes at the head
        op_lanes=(LANE_NONE, LANE_TAIL, LANE_HEAD),
    ),
    "deque": StructSpec(
        "deque",
        DequeState,
        init_deque,
        combine_deque,
        sequential_reference_deque,
        5,
        # left-side ops (pushL/popL) ride the head lane, right-side ops
        # (pushR/popR) the tail lane — the serving tier's arrivals
        # (push_back) and admission pops (pop_front) land on opposite lanes
        op_lanes=(LANE_NONE, LANE_HEAD, LANE_HEAD, LANE_TAIL, LANE_TAIL),
    ),
    "map": StructSpec(
        "map",
        MapState,
        init_map,
        combine_map,
        sequential_reference_map,
        5,
        # map ops do not commute, so there is no per-side split: every op
        # rides the single combiner lane
        op_lanes=(LANE_NONE,) * 5,
        keyed=True,
    ),
}


def lane_of_ops(kind: str, ops) -> jax.Array:
    """Per-op announcement lane of a batch targeting ``kind`` shards
    (device path): LANE_HEAD / LANE_TAIL / LANE_NONE, via the kind's
    ``op_lanes`` table."""
    table = jnp.asarray(STRUCTS[kind].op_lanes, jnp.int32)
    o = jnp.asarray(ops, jnp.int32)
    return table[jnp.clip(o, 0, table.shape[0] - 1)]


def lane_of_ops_host(kind: str, ops) -> np.ndarray:
    """NumPy twin of :func:`lane_of_ops` for the runtime's host-side lane
    routing and oracles."""
    table = np.asarray(STRUCTS[kind].op_lanes, np.int32)
    o = np.asarray(ops, np.int32)
    return table[np.clip(o, 0, table.shape[0] - 1)]


def struct_kind(state) -> str:
    """Structure kind of a (possibly shard-stacked) state pytree."""
    for kind, spec in STRUCTS.items():
        if isinstance(state, spec.state_cls):
            return kind
    raise TypeError(f"not a DFC structure state: {type(state)!r}")


# Stable integer codes for structure kinds, used wherever a kind has to live
# in an array (the sharded runtime's per-shard ``kind`` metadata column) or
# in compact durable records.  Codes are assigned in sorted-kind order so they
# cannot drift as STRUCTS grows.
KIND_CODES: Dict[str, int] = {kind: i for i, kind in enumerate(sorted(STRUCTS))}
CODE_KINDS: Dict[int, str] = {i: kind for kind, i in KIND_CODES.items()}


def state_from_contents(kind: str, contents, capacity: int, epoch: int):
    """Build a committed single-object state holding exactly ``contents``.

    Used by shard merges: the absorbing shard's post-merge state is rebuilt
    from its merged value list (bottom-to-top for the stack, left-to-right
    for the ring structures) at the given (even) epoch — the active buffer
    selected by ``epoch`` holds the window [0, len(contents)).
    """
    spec = STRUCTS[kind]
    n = len(contents)
    if n > capacity:
        raise ValueError(f"{n} values exceed capacity {capacity}")
    state = spec.init(capacity)
    active = (epoch // 2) % 2
    if kind == "map":
        # contents is a list of (key, value) pairs; rebuild by host-side
        # bucket probe.  Merged shards hold disjoint key sets (routing is
        # injective per key), but the union can still overflow one bucket —
        # surface that as the same ValueError a too-long ring would raise.
        bslots, n_buckets = map_geometry(capacity)
        mk = np.zeros((capacity,), np.int32)
        mv = np.zeros((capacity,), np.asarray(state.values).dtype)
        mo = np.zeros((capacity,), np.int32)
        for key, val in contents:
            base = int(map_bucket_host([int(key)], n_buckets)[0]) * bslots
            for j in range(bslots):
                if not mo[base + j]:
                    mk[base + j] = int(key)
                    mv[base + j] = val
                    mo[base + j] = 1
                    break
            else:
                raise ValueError(
                    f"map bucket {base // bslots} overflows rebuilding "
                    f"{n} entries at capacity {capacity}"
                )
        return MapState(
            keys=jnp.asarray(mk),
            values=jnp.asarray(mv),
            occupied=jnp.asarray(mo),
            count=state.count.at[active].set(n),
            epoch=jnp.asarray(epoch, jnp.int32),
        )
    values = state.values.at[: max(n, 0)].set(
        jnp.asarray(contents, state.values.dtype)
    ) if n else state.values
    if kind == "stack":
        return StackState(
            values=values,
            size=state.size.at[active].set(n),
            epoch=jnp.asarray(epoch, jnp.int32),
        )
    ends = state.ends.at[active].set(jnp.asarray([0, n], jnp.int32))
    cls = spec.state_cls
    return cls(values=values, ends=ends, epoch=jnp.asarray(epoch, jnp.int32))


# ============================================================ announce ring
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AnnounceRing:
    """Device-side announcement queue: a preallocated ring of (key, op,
    param) lanes that announced batches land in, so combining phases consume
    device arrays directly instead of reconstructing them from per-thread
    durable records each phase (the durable mirror — SimFS — keeps only the
    compact JSON needed for recovery and replay).

    ``tail`` is an absolute (monotone) producer counter; slot index =
    counter % slots.  Consumption bookkeeping (which spans are still live) is
    host-side: the ring itself is volatile staging, rebuilt from the durable
    announcement mirror on recovery.

    ``lanes`` (ISSUE 8) is the per-slot announcement lane of the staged op —
    LANE_HEAD / LANE_TAIL for ops targeting a lane-split shard, LANE_NONE
    otherwise — so a per-side combine dispatch can drain one lane's ops
    straight off the device ring (``ring_drain(..., lane=...)`` masks the
    other lane's slots to OP_NONE without a host round-trip).
    """

    keys: jax.Array  # i32[slots]
    ops: jax.Array  # i32[slots]
    params: jax.Array  # f32[slots]
    lanes: jax.Array  # i32[slots] — LANE_HEAD/LANE_TAIL/LANE_NONE per slot
    tail: jax.Array  # i32[] — absolute producer counter


def init_announce_ring(slots: int) -> AnnounceRing:
    """STRUCTS-style init: an empty device ring of ``slots`` lanes.

    ``slots`` must be a power of two: the device-side ``tail`` is an int32
    that overflows (wraps mod 2^32) after ~2^31 announced lanes, while the
    host mirror (``ShardedDFCRuntime._ring_tail``) is an unbounded Python
    int.  With a power-of-two slot count, ``tail % slots`` is congruent
    under the int32 wraparound (2^32 is a multiple of ``slots``), so the
    two counters keep agreeing on slot indices forever; with any other slot
    count they silently diverge after the overflow.
    """
    if slots <= 0 or (slots & (slots - 1)) != 0:
        raise ValueError(f"ring slots must be a power of two, got {slots}")
    return AnnounceRing(
        keys=jnp.zeros((slots,), jnp.int32),
        ops=jnp.full((slots,), OP_NONE, jnp.int32),
        params=jnp.zeros((slots,), jnp.float32),
        lanes=jnp.full((slots,), LANE_NONE, jnp.int32),
        tail=jnp.zeros((), jnp.int32),
    )


@jax.jit
def ring_announce(
    ring: AnnounceRing,
    keys: jax.Array,
    ops: jax.Array,
    params: jax.Array,
    lanes: jax.Array = None,
) -> AnnounceRing:
    """Land one announced batch at the ring tail (device-side scatter).

    The caller guarantees the span [tail, tail+n) does not overlap a span
    that is still awaiting its combining phase (host-side bookkeeping in the
    runtime); the write itself is one masked scatter per field.  ``lanes``
    (optional) stages each op's announcement lane alongside it — the
    lane-split runtime computes it once at announce time (op code x target
    shard kind) so per-side drains never recompute routing.
    """
    n = ops.shape[0]
    slots = ring.keys.shape[0]
    pos = (ring.tail + jnp.arange(n)) % slots
    lane_col = (
        jnp.full((n,), LANE_NONE, jnp.int32)
        if lanes is None
        else jnp.asarray(lanes).astype(jnp.int32)
    )
    return AnnounceRing(
        keys=ring.keys.at[pos].set(jnp.asarray(keys).astype(jnp.int32)),
        ops=ring.ops.at[pos].set(jnp.asarray(ops).astype(jnp.int32)),
        params=ring.params.at[pos].set(jnp.asarray(params).astype(jnp.float32)),
        lanes=ring.lanes.at[pos].set(lane_col),
        tail=ring.tail + n,
    )


def ring_has_room(slots: int, tail: int, oldest_live: int, n: int) -> bool:
    """Host-side admission check for a span of ``n`` lanes landing at absolute
    position ``tail``: the write must not wrap onto the OLDEST span still
    awaiting its combining phase (``oldest_live`` is that span's absolute
    start; pass ``tail`` itself when no span is live).  The sharded
    runtime's ``_register_live`` is the canonical caller — an announcement
    that fails this check falls back to the host-upload path."""
    return n <= slots and (tail + n) - oldest_live <= slots


@jax.jit
def _ring_gather(ring: AnnounceRing, idx: jax.Array):
    return ring.keys[idx], ring.ops[idx], ring.params[idx]


@functools.partial(jax.jit, static_argnames=("lane",))
def _ring_gather_lane(ring: AnnounceRing, idx: jax.Array, lane: int):
    keys, ops, params = _ring_gather(ring, idx)
    keep = ring.lanes[idx] == lane
    return keys, jnp.where(keep, ops, OP_NONE), params


def ring_drain(ring: AnnounceRing, start: int, n: int, lane: int = None):
    """Read span [start, start+n) of the ring as device arrays (the combine
    path's view; no host round-trip).  ``start`` is the absolute counter the
    span was announced at.  With ``lane``, ops staged on the OTHER lane are
    masked to OP_NONE (lane positions are preserved, so per-op bookkeeping
    still lines up with the unfiltered span) — the per-side combine
    dispatch's view of a mixed span."""
    slots = int(ring.keys.shape[0])
    idx = (start + np.arange(n, dtype=np.int64)) % slots
    if lane is None:
        return _ring_gather(ring, jnp.asarray(idx, jnp.int32))
    return _ring_gather_lane(ring, jnp.asarray(idx, jnp.int32), int(lane))


def ring_announce_phases(
    ring: AnnounceRing,
    keys: jax.Array,
    ops: jax.Array,
    params: jax.Array,
    lanes: jax.Array = None,
) -> AnnounceRing:
    """Land a whole PHASE SCHEDULE — ``[K, pad]`` per-phase batches, padded
    with ``OP_NONE`` lanes — at the ring tail in ONE device scatter.  The
    K phases occupy the contiguous span ``[tail, tail + K*pad)``; the fused
    phase loop reads them back with :func:`ring_drain_phases`."""
    return ring_announce(
        ring,
        keys.reshape(-1),
        ops.reshape(-1),
        params.reshape(-1),
        None if lanes is None else lanes.reshape(-1),
    )


def ring_drain_phases(
    ring: AnnounceRing, start: int, k: int, pad: int, lane: int = None
):
    """Consume the announcement ring ACROSS A PHASE AXIS: read the span of
    ``k`` phases of ``pad`` lanes each announced at absolute position
    ``start`` back as ``[K, pad]`` device arrays — the fused K-phase
    dispatch's input view, one gather for the whole schedule instead of one
    per phase.  ``lane`` filters to one announcement lane, as in
    :func:`ring_drain`."""
    keys, ops, params = ring_drain(ring, start, k * pad, lane=lane)
    return (
        keys.reshape(k, pad), ops.reshape(k, pad), params.reshape(k, pad)
    )


# ------------------------------------------------------ phase-intent records
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PhaseIntents:
    """Device-side persist-intent log of a fused K-phase combine.

    A fused dispatch (``dfc_multi_phase_step`` / the runtime's
    ``phase_loop``) commits NOTHING durably by itself: it accumulates, per
    phase, everything the host needs to later issue that phase's pwb/pfence
    batch — which shards the phase touched, the epoch each touched shard
    must commit to, and the cumulative combiner counters its slot metadata
    must record.  The host drains this log phase-by-phase behind the device,
    replaying the exact serial persistence schedule.

    All leaves carry a leading ``K`` (phase) axis over ``S`` shards:

      * ``epoch``      — ``i32[K, S]``: per-shard epoch AFTER phase k (the
        two-increment commit target of every op phase k routed to shard s),
      * ``touched``    — ``bool[K, S]``: shard s received ops in phase k
        (untouched shards keep state AND epoch: no phantom phases),
      * ``phases_cum`` — ``i32[K, S]``: combining phases absorbed by shard s
        up to and including phase k, counted from this dispatch's start,
      * ``ops_cum``    — ``i32[K, S]``: ops combined into shard s likewise.

    The cumulative counters start at zero: the runtime adds its durable
    ``meta`` baseline when it turns an intent into a slot persist.
    """

    epoch: jax.Array  # i32[K, S]
    touched: jax.Array  # bool[K, S]
    phases_cum: jax.Array  # i32[K, S]
    ops_cum: jax.Array  # i32[K, S]


# ============================================================ shard stacking
def replicate_state(state, n_shards: int):
    """Stack ``n_shards`` copies of a freshly-initialized state into one
    pytree with a leading shard axis on every leaf (``vmap``-ready)."""
    return jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(leaf, (n_shards,) + leaf.shape), state
    )


def init_sharded(kind: str, n_shards: int, capacity: int, dtype=jnp.float32):
    """``n_shards`` homogeneous DFC objects as one stacked pytree.

    Leaf shapes: stack ``values[S, cap] / size[S, 2] / epoch[S]``; queue and
    deque ``values[S, cap] / ends[S, 2, 2] / epoch[S]``.  Each shard keeps its
    own epoch, so shards commit (and recover) independently.
    """
    return replicate_state(STRUCTS[kind].init(capacity, dtype), n_shards)


def shard_slice(state, s: int):
    """Extract shard ``s`` of a stacked state as a single-object state."""
    return jax.tree_util.tree_map(lambda leaf: leaf[s], state)


def stack_shards(shard_states):
    """Inverse of ``shard_slice`` over all shards: list of single-object
    states -> one stacked state."""
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *shard_states)
