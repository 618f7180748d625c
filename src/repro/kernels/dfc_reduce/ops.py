"""Jitted public wrappers: full DFC combine steps using the Pallas kernels.

Splice the kernel outputs (responses / surplus segments / counts) into the
array-backed double-buffered structure states (stack, queue, deque, map).
``backend`` is one of :data:`BACKENDS`: ``jnp`` vmaps the vectorized
combine of ``repro.core.jax_dfc``, ``ref`` vmaps the pure-jnp twin of the
kernels (a test oracle), and ``pallas`` runs the Pallas kernels — compiled
for the chip, or in the Pallas interpreter when the platform is the CPU
(``kernel.default_interpret``).

Each ring structure factors into a window builder (read the committed
end(s) of the array into the kernel's lane-sized window) and a splice
(apply the kernel's surplus segments/counts back to the double-buffered
state with an epoch bump of +2).  The sharded steps
(``dfc_sharded_*_combine_step``) vmap the builder and the splice over a
leading shard axis and run ALL shards' combining phases in one Pallas grid
dispatch (grid=(S,), one program instance per shard) — the multi-object
amortization the sharded runtime (`repro.runtime.dfc_shard`) is built on.
The single-object steps are the sharded steps over a one-shard stack.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.jax_dfc import (
    OP_NONE,
    DequeState,
    MapState,
    PhaseIntents,
    QueueState,
    StackState,
)
from repro.kernels.dfc_reduce.kernel import (
    dfc_deque_reduce_grid_call,
    dfc_map_reduce_grid_call,
    dfc_queue_reduce_grid_call,
    dfc_reduce_grid_call,
)
from repro.kernels.dfc_reduce.ref import (
    dfc_deque_reduce_ref,
    dfc_map_reduce_ref,
    dfc_queue_reduce_ref,
    dfc_reduce_ref,
)

BACKENDS = ("jnp", "ref", "pallas")


def _kernel_or_ref(backend: str, kernel, ref, *args):
    """Run the Pallas grid ``kernel`` or the vmapped pure-jnp ``ref`` twin."""
    if backend == "pallas":
        return kernel(*args)
    if backend == "ref":
        return jax.vmap(ref)(*args)
    raise ValueError(f"backend {backend!r} is not one of {BACKENDS}")


# ------------------------------------------------------------------- stack
def _stack_window(state: StackState, n: int):
    """window = stack[top-n : top], zero-padded below the bottom."""
    cap = state.values.shape[0]
    old_size = state.active_size()
    start = jnp.clip(old_size - n, 0, cap - n)
    raw = jax.lax.dynamic_slice(state.values, (start,), (n,))
    # when old_size < n the slice starts at 0 and the top is at old_size-1;
    # shift so the committed top sits at window[n-1]
    shift = jnp.where(old_size >= n, 0, n - old_size)
    window = jnp.roll(raw, shift)
    window = jnp.where(jnp.arange(n) >= shift, window, 0.0)
    return window, old_size


def _stack_splice(state: StackState, segment, counts) -> StackState:
    n = segment.shape[0]
    cap = state.values.shape[0]
    old_size = state.active_size()
    n_push_surplus, n_popped = counts[0], counts[1]
    new_values = jax.lax.dynamic_update_slice(
        state.values, segment.astype(state.values.dtype), (jnp.clip(old_size, 0, cap - n),)
    )
    keep = (jnp.arange(cap) >= old_size) & (jnp.arange(cap) < old_size + n_push_surplus)
    new_values = jnp.where(keep, new_values, state.values)

    new_size_val = old_size + n_push_surplus - n_popped
    inactive = (state.epoch // 2 + 1) % 2
    return StackState(
        values=new_values,
        size=state.size.at[inactive].set(new_size_val),
        epoch=state.epoch + 2,
    )


# ------------------------------------------------------------------- queue
def _queue_window(state: QueueState, n: int):
    """Front window: queue[head : head+n], zero-padded past the tail."""
    cap = state.values.shape[0]
    ends = state.active_ends()
    head, size = ends[0], ends[1] - ends[0]
    lanes = jnp.arange(n)
    window = jnp.where(lanes < size, state.values[(head + lanes) % cap], 0.0)
    return window.astype(jnp.float32), size


def _queue_splice(state: QueueState, segment, counts) -> QueueState:
    n = segment.shape[0]
    cap = state.values.shape[0]
    ends = state.active_ends()
    head, tail = ends[0], ends[1]
    n_enq_surplus, n_from_q = counts[0], counts[1]
    lanes = jnp.arange(n)
    pos = (tail + lanes) % cap
    new_values = state.values.at[
        jnp.where(lanes < n_enq_surplus, pos, cap)
    ].set(segment.astype(state.values.dtype), mode="drop")

    inactive = (state.epoch // 2 + 1) % 2
    new_ends = jnp.stack([head + n_from_q, tail + n_enq_surplus])
    return QueueState(
        values=new_values,
        ends=state.ends.at[inactive].set(new_ends),
        epoch=state.epoch + 2,
    )


# ------------------------------------------------------------------- deque
def _deque_windows(state: DequeState, n: int):
    """End windows seen from the left and from the right."""
    cap = state.values.shape[0]
    ends = state.active_ends()
    left, right = ends[0], ends[1]
    size = right - left
    lanes = jnp.arange(n)
    window_l = jnp.where(lanes < size, state.values[(left + lanes) % cap], 0.0)
    window_r = jnp.where(lanes < size, state.values[(right - 1 - lanes) % cap], 0.0)
    return window_l.astype(jnp.float32), window_r.astype(jnp.float32), size


def _deque_splice(state: DequeState, seg_l, seg_r, counts) -> DequeState:
    n = seg_l.shape[0]
    cap = state.values.shape[0]
    ends = state.active_ends()
    left, right = ends[0], ends[1]
    sl, dl, sr, dr = counts[0], counts[1], counts[2], counts[3]
    lanes = jnp.arange(n)
    posl = (left - 1 - lanes) % cap
    new_values = state.values.at[jnp.where(lanes < sl, posl, cap)].set(
        seg_l.astype(state.values.dtype), mode="drop"
    )
    posr = (right + lanes) % cap
    new_values = new_values.at[jnp.where(lanes < sr, posr, cap)].set(
        seg_r.astype(state.values.dtype), mode="drop"
    )

    inactive = (state.epoch // 2 + 1) % 2
    new_ends = jnp.stack([left - sl + dl, right + sr - dr])
    return DequeState(
        values=new_values,
        ends=state.ends.at[inactive].set(new_ends),
        epoch=state.epoch + 2,
    )


# ----------------------------------------------------------------- sharded
# All shards' combining phases in one dispatch.  States are shard-stacked
# pytrees (leading S axis on every leaf, see ``repro.core.jax_dfc``); ops and
# params are [S, N] per-shard announcement matrices.
@functools.partial(jax.jit, static_argnames=("backend",))
def dfc_sharded_combine_step(state: StackState, ops, params, *, backend: str = "ref"):
    """Sharded stack combine: one grid dispatch, program instance = shard."""
    windows, sizes = jax.vmap(_stack_window, in_axes=(0, None))(state, ops.shape[1])
    resp, kinds, segments, counts = _kernel_or_ref(
        backend, dfc_reduce_grid_call, dfc_reduce_ref, ops, params, windows, sizes
    )
    return jax.vmap(_stack_splice)(state, segments, counts), resp, kinds


@functools.partial(jax.jit, static_argnames=("backend",))
def dfc_sharded_queue_combine_step(
    state: QueueState, ops, params, *, backend: str = "ref"
):
    """Sharded queue combine: one grid dispatch, program instance = shard."""
    windows, sizes = jax.vmap(_queue_window, in_axes=(0, None))(state, ops.shape[1])
    resp, kinds, segments, counts = _kernel_or_ref(
        backend, dfc_queue_reduce_grid_call, dfc_queue_reduce_ref,
        ops, params, windows, sizes,
    )
    return jax.vmap(_queue_splice)(state, segments, counts), resp, kinds


@functools.partial(jax.jit, static_argnames=("backend",))
def dfc_sharded_deque_combine_step(
    state: DequeState, ops, params, *, backend: str = "ref"
):
    """Sharded deque combine: one grid dispatch, program instance = shard."""
    windows_l, windows_r, sizes = jax.vmap(_deque_windows, in_axes=(0, None))(
        state, ops.shape[1]
    )
    resp, kinds, segs_l, segs_r, counts = _kernel_or_ref(
        backend, dfc_deque_reduce_grid_call, dfc_deque_reduce_ref,
        ops, params, windows_l, windows_r, sizes,
    )
    return jax.vmap(_deque_splice)(state, segs_l, segs_r, counts), resp, kinds


def _one_shard(sharded_step, doc):
    """Single-object twin of a sharded step: the same step over a one-shard
    stack, so both entry points share one kernel path."""

    @functools.partial(jax.jit, static_argnames=("backend",))
    def step(state, ops, params, *, backend: str = "ref"):
        one = jax.tree_util.tree_map(lambda leaf: leaf[None], state)
        new, resp, kinds = sharded_step(one, ops[None], params[None], backend=backend)
        return jax.tree_util.tree_map(lambda leaf: leaf[0], new), resp[0], kinds[0]

    step.__doc__ = doc
    return step


dfc_combine_step = _one_shard(
    dfc_sharded_combine_step,
    "Stack combine phase: top window -> kernel -> splice above the top.",
)
dfc_queue_combine_step = _one_shard(
    dfc_sharded_queue_combine_step,
    "Queue combine phase: front window -> kernel -> masked ring splice.",
)
dfc_deque_combine_step = _one_shard(
    dfc_sharded_deque_combine_step,
    "Deque combine phase: end windows -> two-sided kernel -> ring splices.",
)


# --------------------------------------------------------------------- map
@functools.partial(jax.jit, static_argnames=("backend",))
def dfc_sharded_map_combine_step(state: MapState, keys, ops, params, *, backend: str = "ref"):
    """Sharded map combine: one grid dispatch, program instance = shard.

    Unlike the ring kinds there is no window/splice factoring — each shard's
    bucketed table rides through the kernel (map writes scatter by bucket,
    not contiguously at an end), and only the double-buffered ``count`` is
    published on the inactive slot here.
    """
    s = ops.shape[0]
    rows = jnp.arange(s)
    active_counts = state.count[rows, (state.epoch // 2) % 2]
    mk, mv, mo, cnt, resp, kinds = _kernel_or_ref(
        backend, dfc_map_reduce_grid_call, dfc_map_reduce_ref,
        state.keys, state.values, state.occupied, active_counts,
        keys, ops, params,
    )
    inactive = (state.epoch // 2 + 1) % 2
    new_state = MapState(
        keys=mk,
        values=mv.astype(state.values.dtype),
        occupied=mo,
        count=state.count.at[rows, inactive].set(cnt),
        epoch=state.epoch + 2,
    )
    return new_state, resp, kinds


SHARDED_COMBINE_STEPS = {
    "stack": dfc_sharded_combine_step,
    "queue": dfc_sharded_queue_combine_step,
    "deque": dfc_sharded_deque_combine_step,
}


# -------------------------------------------------------------- multi-batch
def _one_sharded_combine(kind: str, backend: str, state, ops, params, keys=None):
    """One sharded combining phase of ``kind`` — the shared dispatch used by
    both the single-batch and the chained entry points: a ``vmap`` of the
    single-object combine for the jnp backend, one Pallas grid otherwise.

    Keyed kinds (the map) additionally consume the announced KEYS: callers
    that routed a batch thread them through; ``None`` falls back to all-zero
    keys (only valid for batches with no keyed ops).
    """
    from repro.core.jax_dfc import STRUCTS

    spec = STRUCTS[kind]
    if spec.keyed:
        k = jnp.zeros_like(ops) if keys is None else keys
        if backend == "jnp":
            return jax.vmap(spec.combine)(state, k, ops, params)
        return dfc_sharded_map_combine_step(state, k, ops, params, backend=backend)
    if backend == "jnp":
        return jax.vmap(spec.combine)(state, ops, params)
    return SHARDED_COMBINE_STEPS[kind](state, ops, params, backend=backend)


# ---------------------------------------------------- per-side lanes (ISSUE 8)
def _lane_mask_ops(kind: str, ops, lane: int):
    """Mask a per-shard announcement matrix down to ONE announcement lane:
    ops whose side is not ``lane`` become OP_NONE (positions preserved, so
    per-op bookkeeping lines up with the unmasked batch)."""
    from repro.core.jax_dfc import lane_of_ops

    return jnp.where(lane_of_ops(kind, ops) == lane, ops, OP_NONE)


@functools.partial(jax.jit, static_argnames=("kind", "lane", "backend"))
def dfc_lane_combine_step(state, ops, params, *, kind, lane, backend="jnp"):
    """One PER-SIDE combining phase: combine only the ``lane``-side ops
    (LANE_HEAD = consuming side, LANE_TAIL = producing side) of each shard's
    announcement matrix, leaving the opposite side's ops untouched
    (their response lanes come back R_NONE).

    This is the device half of a split (two-lane) shard's ordinary phase:
    head-lane traffic moves only the head/left counter, tail-lane traffic
    only the values region and the tail/right counter, so the durable
    commit behind each dispatch persists just its own side.  Works for the
    vmap (``jnp``) and kernel-shaped (``ref`` / ``pallas``)
    paths via the shared ``_one_sharded_combine`` dispatch.
    """
    masked = _lane_mask_ops(kind, ops, lane)
    return _one_sharded_combine(kind, backend, state, masked, params)


@functools.partial(jax.jit, static_argnames=("kind", "backend"))
def dfc_handoff_combine_step(state, ops, params, *, kind, backend="jnp"):
    """The DRAINED-QUEUE HANDOFF step: both lanes' ops of a split shard in
    ONE combining phase, reusing the existing elimination math unchanged —
    when the head lane's pops outrun the tail lane's committed pushes, the
    two sides synchronize here (queue: drained two-sided elimination pairs
    deq rank size+k with enq rank k; deque: same-side elimination), and the
    runtime commits BOTH lane epochs atomically behind this dispatch.

    Semantically identical to the one-lane combine of the same batch (that
    is the point: a handoff phase must linearize exactly like the unsplit
    fabric would), for both the vmap and Pallas-grid paths.
    """
    return _one_sharded_combine(kind, backend, state, ops, params)


@functools.partial(jax.jit, static_argnames=("kind", "backend", "unroll"))
def dfc_sharded_multi_combine_step(
    state, ops, params, *, kind, backend="ref", unroll=1, keys=None
):
    """Chain B sharded combining phases through ONE dispatch.

    ``ops`` / ``params`` are ``[B, S, N]`` per-batch announcement matrices;
    the B batches are applied sequentially (``lax.scan`` over the leading
    batch axis) to the shard-stacked ``state``, exactly as B separate
    ``SHARDED_COMBINE_STEPS[kind]`` calls would — but the whole chain costs
    one dispatch (one scanned vmap for the jnp backend, one scanned Pallas
    grid for the kernel backends), which is what lets a pipelined durable
    path amortize dispatch overhead across batches.

    Per batch, shards that received no ops keep their state AND epoch (no
    phantom phases), so the per-shard epoch after batch b is exactly what b
    separate phases would have produced — the two-increment durable commit
    per batch is unchanged.  An all-``OP_NONE`` batch is therefore a pure
    pass-through (state, epochs, and counters untouched, ``R_NONE``
    responses): a depth-D pipeline exploits this by PADDING every chain to a
    fixed batch count, so all of a fabric's dispatches — however many
    announcers happened to be ready — share one compiled program per lane
    width instead of one per ready-set size.

    ``unroll`` (static) unrolls the scan body that many batches per step —
    the depth-aware dispatch knob: a depth-D pipeline passes D so XLA can
    fuse the window of batches it keeps in flight into straight-line code.

    Returns ``(states, resp, kinds)`` where ``states`` is the shard-stacked
    state AFTER each batch (every leaf gains a leading B axis; ``states[-1]``
    is the final state) and ``resp`` / ``kinds`` are ``[B, S, N]``.
    """

    all_keys = jnp.zeros_like(ops) if keys is None else keys

    def body(carry, xs):
        b_keys, b_ops, b_params = xs
        combined, s_resp, s_kinds = _one_sharded_combine(
            kind, backend, carry, b_ops, b_params, keys=b_keys
        )
        touched = jnp.any(b_ops != OP_NONE, axis=1)  # bool[S]

        def _select(new_leaf, old_leaf):
            t = touched.reshape(touched.shape + (1,) * (new_leaf.ndim - 1))
            return jnp.where(t, new_leaf, old_leaf)

        new_state = jax.tree_util.tree_map(_select, combined, carry)
        return new_state, (new_state, s_resp, s_kinds)

    _, (states, resp, kinds) = jax.lax.scan(
        body,
        state,
        (all_keys, ops, params),
        unroll=max(1, min(int(unroll), ops.shape[0])),
    )
    return states, resp, kinds


def dfc_hetero_multi_combine_step(
    groups, group_ops, group_params, *, backend="ref", unroll=1,
    group_keys=None,
):
    """Chained heterogeneous combine: ``dfc_sharded_multi_combine_step`` per
    kind group present.  ``group_ops[kind]`` is ``[B, S_kind, N]``; every kind
    chains its B batches in one dispatch, unrolled ``unroll`` batches per
    scan step (the pipeline passes its depth).  ``group_keys`` carries the
    routed announcement keys for keyed kinds (the map).  Returns ``{kind:
    (states, resp, kinds)}`` with the per-batch leading axis (see the
    homogeneous twin).  Meant to be called inside an enclosing jit (not
    jitted itself)."""
    out = {}
    for kind in sorted(groups):
        out[kind] = dfc_sharded_multi_combine_step(
            groups[kind], group_ops[kind], group_params[kind],
            kind=kind, backend=backend, unroll=unroll,
            keys=None if group_keys is None else group_keys.get(kind),
        )
    return out


@functools.partial(jax.jit, static_argnames=("kind", "backend", "unroll"))
def dfc_multi_phase_step(
    state, ops, params, *, kind, backend="ref", unroll=1, keys=None,
):
    """Fuse K combining PHASES of one kind group into a single dispatch and
    accumulate each phase's persist INTENTS device-side.

    ``ops`` / ``params`` are ``[K, S, N]`` per-phase announcement matrices.
    The K phases chain exactly like K separate sharded combine calls — a
    ``lax.scan`` over the phase axis, ``unroll`` phases per step, whose body
    is the same ``_one_sharded_combine`` dispatch (so the Pallas backend
    runs one shard-grid kernel per phase inside the fused program) and
    honors the pass-through-batch contract (an all-``OP_NONE`` phase is a
    pure no-op: state, epochs, counters untouched) — but nothing leaves the
    device between phases, and nothing durable happens here at all.  Instead
    the per-phase epoch/persist intents come back as one
    :class:`~repro.core.jax_dfc.PhaseIntents` log that the host drains
    asynchronously behind the device, issuing each phase's pwb/pfence batch
    in serial commit order (see ``ShardedDFCRuntime.phase_loop``).

    Returns ``(states, resp, kinds, intents)``: ``states`` with a leading K
    axis (``states[-1]`` is the final state), ``resp`` / ``kinds``
    ``[K, S, N]``, and ``intents`` the ``PhaseIntents`` record (cumulative
    counters start at zero — the caller adds its durable baseline).
    """
    states, resp, kinds = dfc_sharded_multi_combine_step(
        state, ops, params, kind=kind, backend=backend, unroll=unroll,
        keys=keys,
    )
    touched = jnp.any(ops != OP_NONE, axis=2)  # bool[K, S]
    per_phase_ops = jnp.sum((ops != OP_NONE).astype(jnp.int32), axis=2)
    intents = PhaseIntents(
        epoch=states.epoch.astype(jnp.int32),
        touched=touched,
        phases_cum=jnp.cumsum(touched.astype(jnp.int32), axis=0),
        ops_cum=jnp.cumsum(per_phase_ops, axis=0),
    )
    return states, resp, kinds, intents


def dfc_hetero_multi_phase_step(
    groups, group_ops, group_params, *, backend="ref", unroll=1,
    group_keys=None,
):
    """Heterogeneous K-phase fusion: ``dfc_multi_phase_step`` per kind group
    present (``group_ops[kind]`` is ``[K, S_kind, N]``).  ``group_keys``
    carries the routed announcement keys for keyed kinds (the map).  Returns
    ``{kind: (states, resp, kinds, intents)}`` — every kind fuses its whole
    phase chain in one dispatch.  Meant to be called inside an enclosing jit
    (not jitted itself)."""
    out = {}
    for kind in sorted(groups):
        out[kind] = dfc_multi_phase_step(
            groups[kind], group_ops[kind], group_params[kind],
            kind=kind, backend=backend, unroll=unroll,
            keys=None if group_keys is None else group_keys.get(kind),
        )
    return out


# ------------------------------------------------------------- heterogeneous
def dfc_hetero_combine_step(
    groups, group_ops, group_params, *, backend="ref", group_keys=None
):
    """STRUCTS-dispatched combine over a heterogeneous shard fabric.

    ``groups`` maps a structure kind to that kind's shard-stacked state;
    ``group_ops`` / ``group_params`` hold the matching ``[S_kind, N]``
    announcement matrices.  Program instances are grouped BY KIND: each kind
    present gets exactly one dispatch — a ``vmap`` of its combine for the
    ``jnp`` backend, or one Pallas grid call (``grid=(S_kind,)``, program
    instance = shard) for the kernel backends — so a mixed stack/queue/deque
    fabric costs one dispatch per kind, not per shard.

    Returns ``{kind: (new_state, responses[S_kind, N], kinds[S_kind, N])}``.
    Meant to be called inside an enclosing jit (it is not jitted itself).
    """
    out = {}
    for kind in sorted(groups):
        out[kind] = _one_sharded_combine(
            kind, backend, groups[kind], group_ops[kind], group_params[kind],
            keys=None if group_keys is None else group_keys.get(kind),
        )
    return out
