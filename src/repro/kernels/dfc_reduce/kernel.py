"""Pallas TPU kernels for the DFC combining phase (paper Algorithm 2, REDUCE).

Every kernel is a sharded grid: ``grid=(S,)``, and program instance ``s``
runs shard ``s``'s combining phase over its announcement lanes.  One
instance holds one shard's whole batch of N lanes plus a window of the
structure's active end(s):

  * rank prefix sums over the op lane masks, as a matmul with a
    lower-triangular one-hot matrix (Mosaic has no ``cumsum`` lowering),
  * all value routing (elimination pairing, surplus compaction) as one-hot
    f32 matmuls at HIGHEST precision, so f32 payloads route bit-exactly on
    the MXU — the TPU-native replacement for the paper's pointer-walking
    sequential combiner,
  * end windows are read for surplus removals and new segments are produced
    for surplus insertions; the caller splices them into the full array
    (stack: dynamic_update_slice above the committed top; queue/deque:
    masked ring scatter outside the committed window).

Layout rules the TPU compiler enforces: the last two dims of every block are
(8, 128)-divisible or equal the array's.  Lane rows therefore travel as
``[S, 1, N]`` arrays in ``(1, 1, N)`` blocks, the counts as ``[S, 1, 8]``,
and the committed per-shard sizes sit whole in SMEM, read at
``program_id``.  The wrappers below take and return the natural ``[S, N]``
/ ``[S]`` shapes.

Ring kernels (stack, queue, deque), per shard:
  ops      i32[N]   op codes (0 none, 1 push/enq/pushL, 2 pop/deq/popL,
                    3 pushR, 4 popR)
  params   f32[N]   insert arguments
  window   f32[N]   the committed end as the caller's window builder reads it
  size     i32      committed size (EMPTY detection)
  -> resp f32[N], kind i32[N], segment(s) f32[N], counts i32[4 or 8]:
     stack  (n_push_surplus, n_popped, n_elim, q_total)
     queue  (n_enq_surplus, n_from_q, n_elim, q_total)
     deque  (sl, dl, sr, dr, nl_elim, nr_elim, size_after, 0)

Map kernel: lanes apply in announcement order (map ops do not commute), one
``fori_loop`` step per lane.  Each step reads the lane's key, op and param
as SMEM scalars and probes only the key's bucket as one dynamic row of the
table viewed as ``[cap / 128, 128]`` (a bucket of 8 slots is 8 consecutive
lanes of one row).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

OP_PUSH = 1
OP_POP = 2
OP_ENQ = OP_PUSH
OP_DEQ = OP_POP
OP_PUSHL = 1
OP_POPL = 2
OP_PUSHR = 3
OP_POPR = 4
R_NONE = 0
R_ACK = 1
R_VALUE = 2
R_EMPTY = 3
# map op codes / response kinds (local copies; see core/jax_dfc.py — code 4
# is the runtime's R_OVERFLOW, so map rejections start at 5)
OP_MAP_INSERT = 1
OP_MAP_LOOKUP = 2
OP_MAP_DELETE = 3
OP_MAP_CAS = 4
R_FULL = 5
R_CAS_FAIL = 6
CAS_DOM = 4096
MAP_BUCKET_SLOTS = 8
N_COUNTS = 8  # width of every kernel's counts row
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def default_interpret() -> bool:
    """Interpret mode follows the platform: the Pallas interpreter on the
    CPU (tests), the compiled Mosaic kernel everywhere else."""
    return jax.default_backend() == "cpu"


# ------------------------------------------------------------ lane-row math
# Every helper works on (1, N) lane rows: the kernels' native layout.
def _iota(n, dim):
    return jax.lax.broadcasted_iota(jnp.int32, (n, n), dim)


def _onehot(idx):
    """``E[a, b] = [idx[b] == a]`` for a lane row ``idx``."""
    n = idx.shape[-1]
    return (jnp.broadcast_to(idx, (n, n)) == _iota(n, 0)).astype(_F32)


def _route(src_idx, vals):
    """``out[i] = sum_j [src_idx[j] == i] * vals[j]``; indices outside
    [0, n) are dropped."""
    return jax.lax.dot_general(
        vals.astype(_F32), _onehot(src_idx), (((1,), (1,)), ((), ())),
        precision=_HI, preferred_element_type=_F32,
    )


def _gather(vals, idx):
    """``out[i] = vals[clip(idx[i], 0, n - 1)]``."""
    n = vals.shape[-1]
    return jnp.dot(
        vals.astype(_F32), _onehot(jnp.clip(idx, 0, n - 1)),
        precision=_HI, preferred_element_type=_F32,
    )


def _rank(mask):
    """Rank of each set lane among the set lanes, -1 elsewhere."""
    n = mask.shape[-1]
    tri = (_iota(n, 0) <= _iota(n, 1)).astype(_F32)
    incl = jnp.dot(
        mask.astype(_F32), tri, precision=_HI, preferred_element_type=_F32
    )
    return jnp.where(mask, incl.astype(jnp.int32) - 1, -1)


def _count(mask):
    return jnp.sum(mask.astype(jnp.int32), axis=-1, keepdims=True)


def _pack_counts(*vals):
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, N_COUNTS), 1)
    out = jnp.zeros((1, N_COUNTS), jnp.int32)
    for k, v in enumerate(vals):
        out = jnp.where(slot == k, v, out)
    return out


def _stack_reduce_rows(ops, params, window, size):
    n = ops.shape[-1]
    is_push = ops == OP_PUSH
    is_pop = ops == OP_POP
    push_rank = _rank(is_push)
    pop_rank = _rank(is_pop)
    p_total = _count(is_push)
    q_total = _count(is_pop)
    n_elim = jnp.minimum(p_total, q_total)

    # elimination pairing: pop_k <- push_k.param (one-hot route + gather)
    elim_pop_val = _gather(_route(push_rank, params), pop_rank)

    # surplus push compaction into the segment
    surplus_push = is_push & (push_rank >= n_elim)
    segment = _route(jnp.where(surplus_push, push_rank - n_elim, n), params)

    # surplus pops read the window: window[N-1] is the committed top
    surplus_pop = is_pop & (pop_rank >= n_elim)
    depth = pop_rank - n_elim
    win_src = n - 1 - depth
    pop_ok = surplus_pop & (win_src >= 0) & (depth < size)
    stack_val = _gather(window, win_src)

    elim = is_pop & (pop_rank < n_elim)
    kinds = jnp.where(is_push, R_ACK, R_NONE)
    kinds = jnp.where(elim | pop_ok, R_VALUE, kinds)
    kinds = jnp.where(surplus_pop & ~pop_ok, R_EMPTY, kinds)
    resp = jnp.where(elim, elim_pop_val, 0.0)
    resp = jnp.where(pop_ok, stack_val, resp)

    counts = _pack_counts(
        jnp.maximum(p_total - n_elim, 0),
        jnp.minimum(jnp.maximum(q_total - n_elim, 0), size),
        n_elim,
        q_total,
    )
    return resp, kinds, segment, counts


def _queue_reduce_rows(ops, params, window, size):
    n = ops.shape[-1]
    is_enq = ops == OP_ENQ
    is_deq = ops == OP_DEQ
    enq_rank = _rank(is_enq)
    deq_rank = _rank(is_deq)
    p_total = _count(is_enq)
    q_total = _count(is_deq)
    n_from_q = jnp.minimum(q_total, size)
    n_elim = jnp.minimum(jnp.maximum(q_total - size, 0), p_total)

    # deqs served FIFO from the front window (window[j] = j-th from head)
    served = is_deq & (deq_rank < size)
    ring_val = _gather(window, deq_rank)

    # drained: deq rank size+k pairs with enq rank k (two-sided elimination)
    paired = is_deq & (deq_rank >= size) & (deq_rank - size < n_elim)
    pair_val = _gather(_route(enq_rank, params), deq_rank - size)
    empty = is_deq & (deq_rank >= size + n_elim)

    # surplus enqs, rank-compacted into the tail-append segment
    surplus_enq = is_enq & (enq_rank >= n_elim)
    segment = _route(jnp.where(surplus_enq, enq_rank - n_elim, n), params)

    kinds = jnp.where(is_enq, R_ACK, R_NONE)
    kinds = jnp.where(served | paired, R_VALUE, kinds)
    kinds = jnp.where(empty, R_EMPTY, kinds)
    resp = jnp.where(served, ring_val, 0.0)
    resp = jnp.where(paired, pair_val, resp)

    counts = _pack_counts(
        jnp.maximum(p_total - n_elim, 0), n_from_q, n_elim, q_total
    )
    return resp, kinds, segment, counts


def _deque_reduce_rows(ops, params, window_l, window_r, size):
    n = ops.shape[-1]
    is_pl = ops == OP_PUSHL
    is_ql = ops == OP_POPL
    is_pr = ops == OP_PUSHR
    is_qr = ops == OP_POPR
    pl_rank = _rank(is_pl)
    ql_rank = _rank(is_ql)
    pr_rank = _rank(is_pr)
    qr_rank = _rank(is_qr)
    npl, nql = _count(is_pl), _count(is_ql)
    npr, nqr = _count(is_pr), _count(is_qr)
    nl_elim = jnp.minimum(npl, nql)
    nr_elim = jnp.minimum(npr, nqr)

    # same-side elimination: pop_k gets push_k's param
    eliml = is_ql & (ql_rank < nl_elim)
    elimr = is_qr & (qr_rank < nr_elim)
    eliml_val = _gather(_route(pl_rank, params), ql_rank)
    elimr_val = _gather(_route(pr_rank, params), qr_rank)

    # left surplus (pushes XOR pops), applied first
    sl = jnp.maximum(npl - nl_elim, 0)
    tl = jnp.maximum(nql - nl_elim, 0)
    surplus_pl = is_pl & (pl_rank >= nl_elim)
    seg_l = _route(jnp.where(surplus_pl, pl_rank - nl_elim, n), params)
    dl = jnp.minimum(tl, size)
    surplus_ql = is_ql & (ql_rank >= nl_elim)
    kl = ql_rank - nl_elim
    lpop_ok = surplus_ql & (kl < size)
    lpop_val = _gather(window_l, kl)
    size_after = size + sl - dl

    # right surplus, applied after the left; right pop k reads the committed
    # window when k < size, else a value pushed left in this phase
    sr = jnp.maximum(npr - nr_elim, 0)
    tr = jnp.maximum(nqr - nr_elim, 0)
    surplus_pr = is_pr & (pr_rank >= nr_elim)
    seg_r = _route(jnp.where(surplus_pr, pr_rank - nr_elim, n), params)
    dr = jnp.minimum(tr, size_after)
    surplus_qr = is_qr & (qr_rank >= nr_elim)
    kr = qr_rank - nr_elim
    rpop_ok = surplus_qr & (kr < size_after)
    rpop_val = jnp.where(
        kr < size, _gather(window_r, kr), _gather(seg_l, kr - size)
    )

    kinds = jnp.where(is_pl | is_pr, R_ACK, R_NONE)
    kinds = jnp.where(eliml | elimr | lpop_ok | rpop_ok, R_VALUE, kinds)
    kinds = jnp.where(surplus_ql & ~lpop_ok, R_EMPTY, kinds)
    kinds = jnp.where(surplus_qr & ~rpop_ok, R_EMPTY, kinds)
    resp = jnp.where(eliml, eliml_val, 0.0)
    resp = jnp.where(elimr, elimr_val, resp)
    resp = jnp.where(lpop_ok, lpop_val, resp)
    resp = jnp.where(rpop_ok, rpop_val, resp)

    counts = _pack_counts(sl, dl, sr, dr, nl_elim, nr_elim, size_after)
    return resp, kinds, seg_l, seg_r, counts


# ------------------------------------------------------------ ring kernels
def _ring_kernel(math, n_windows):
    """Kernel body for one ring kind: refs are (sizes in SMEM, ops, params,
    windows..., resp, kind, segments..., counts), lane rows in (1, 1, N)
    blocks."""

    def kernel(sizes_ref, ops_ref, params_ref, *refs):
        windows = [r[0] for r in refs[:n_windows]]
        outs = refs[n_windows:]
        size = sizes_ref[pl.program_id(0)]
        results = math(ops_ref[0], params_ref[0], *windows, size)
        for out_ref, val in zip(outs, results):
            out_ref[0] = val

    return kernel


def _ring_grid_call(math, n_windows, n_segments, ops, params, windows, sizes,
                    interpret):
    s, n = ops.shape
    row = pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0))
    rows = lambda x: x.reshape(s, 1, n)  # noqa: E731
    f32_row = jax.ShapeDtypeStruct((s, 1, n), _F32)
    outs = pl.pallas_call(
        _ring_kernel(math, n_windows),
        grid=(s,),
        out_shape=(f32_row, jax.ShapeDtypeStruct((s, 1, n), jnp.int32))
        + (f32_row,) * n_segments
        + (jax.ShapeDtypeStruct((s, 1, N_COUNTS), jnp.int32),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [row] * (2 + n_windows),
        out_specs=(row,) * (2 + n_segments)
        + (pl.BlockSpec((1, 1, N_COUNTS), lambda i: (i, 0, 0)),),
        interpret=default_interpret() if interpret is None else interpret,
    )(
        sizes.astype(jnp.int32),
        rows(ops.astype(jnp.int32)),
        rows(params.astype(_F32)),
        *[rows(w.astype(_F32)) for w in windows],
    )
    return tuple(o[:, 0] for o in outs)


def dfc_reduce_grid_call(ops, params, windows, sizes, *,
                         interpret: Optional[bool] = None):
    """All shards' stack combines in ONE pallas dispatch: ``[S, N]`` lane
    matrices and windows, ``[S]`` committed sizes -> ``(resp, kinds,
    segments [S, N], counts [S, 8])``.  ``interpret`` defaults to the
    platform (see :func:`default_interpret`)."""
    return _ring_grid_call(
        _stack_reduce_rows, 1, 1, ops, params, [windows], sizes, interpret
    )


def dfc_queue_reduce_grid_call(ops, params, windows, sizes, *,
                               interpret: Optional[bool] = None):
    """All shards' queue combines in one dispatch (see
    :func:`dfc_reduce_grid_call`); ``windows`` are the front windows."""
    return _ring_grid_call(
        _queue_reduce_rows, 1, 1, ops, params, [windows], sizes, interpret
    )


def dfc_deque_reduce_grid_call(ops, params, windows_l, windows_r, sizes, *,
                               interpret: Optional[bool] = None):
    """All shards' deque combines in one dispatch -> ``(resp, kinds,
    segs_l, segs_r, counts)`` (see :func:`dfc_reduce_grid_call`)."""
    return _ring_grid_call(
        _deque_reduce_rows, 2, 2, ops, params, [windows_l, windows_r], sizes,
        interpret,
    )


# --------------------------------------------------------------- map kernel
def _map_bucket(keys, n_buckets):
    """In-shard bucket hash (local twin of core's ``map_bucket``)."""
    h = jnp.asarray(keys).astype(jnp.uint32) * jnp.uint32(2654435761)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    return (h % jnp.uint32(n_buckets)).astype(jnp.int32)


def map_table_geometry(cap: int):
    """``(bucket slots, table rows, row width)``: the ``[rows, width]`` view
    the map kernel probes — 128-lane rows when ``cap`` allows, one row of
    ``cap`` slots otherwise.  A bucket never straddles two rows."""
    bslots = min(cap, MAP_BUCKET_SLOTS)
    width = 128 if cap % 128 == 0 else cap
    return bslots, cap // width, width


def _map_kernel(bslots, n_lanes):
    def kernel(rows_ref, offs_ref, lkeys_ref, ops_ref, params_ref, count_ref,
               mk_ref, mv_ref, mo_ref,
               mk_out, mv_out, mo_out, count_out, resp_ref, kind_ref):
        mk_out[...] = mk_ref[...]
        mv_out[...] = mv_ref[...]
        mo_out[...] = mo_ref[...]
        width = mk_out.shape[-1]
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        lane_id = jax.lax.broadcasted_iota(jnp.int32, (1, n_lanes), 1)

        def lane(i, carry):
            cnt, resp_row, kind_row = carry
            r = rows_ref[0, 0, i]
            off = offs_ref[0, 0, i]
            key = lkeys_ref[0, 0, i]
            op = jnp.full((1, 1), ops_ref[0, 0, i], jnp.int32)
            par = jnp.full((1, 1), params_ref[0, 0, i], _F32)
            wk = mk_out[0, pl.ds(r, 1), :]
            wv = mv_out[0, pl.ds(r, 1), :]
            wo = mo_out[0, pl.ds(r, 1), :]
            in_b = (slot >= off) & (slot < off + bslots)
            occ = wo != 0
            hit = in_b & occ & (wk == key)  # key 0 is legal: needs occupied
            hit_off = jnp.min(jnp.where(hit, slot, width), axis=1, keepdims=True)
            free_off = jnp.min(
                jnp.where(in_b & ~occ, slot, width), axis=1, keepdims=True
            )
            has_hit = hit_off < width
            has_free = free_off < width
            # table keys are unique, so the masked sum IS the hit slot's value
            cur = jnp.sum(jnp.where(hit, wv, 0.0), axis=1, keepdims=True)

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
            wmask = (do_ins | cas_ok) & (
                slot == jnp.where(has_hit, hit_off, free_off)
            )
            dmask = do_del & (slot == hit_off)
            mk_out[0, pl.ds(r, 1), :] = jnp.where(
                wmask, key, jnp.where(dmask, 0, wk)
            )
            mv_out[0, pl.ds(r, 1), :] = jnp.where(
                wmask, jnp.where(is_cas, cas_new, par), jnp.where(dmask, 0.0, wv)
            )
            mo_out[0, pl.ds(r, 1), :] = jnp.where(
                wmask, 1, jnp.where(dmask, 0, wo)
            )
            cnt = (
                cnt
                + (is_ins & ~has_hit & has_free).astype(jnp.int32)
                - do_del.astype(jnp.int32)
            )

            kind = jnp.where(do_ins, R_ACK, R_NONE)
            kind = jnp.where(is_ins & ~has_hit & ~has_free, R_FULL, kind)
            kind = jnp.where((is_lku | is_del | is_cas) & ~has_hit, R_EMPTY, kind)
            kind = jnp.where((is_lku | do_del | cas_ok) & has_hit, R_VALUE, kind)
            kind = jnp.where(cas_hit & ~cas_ok, R_CAS_FAIL, kind)
            resp = jnp.where((is_lku | is_del | is_cas) & has_hit, cur, 0.0)
            at = lane_id == i
            return (
                cnt,
                jnp.where(at, resp, resp_row),
                jnp.where(at, kind, kind_row),
            )

        cnt, resp_row, kind_row = jax.lax.fori_loop(
            0, n_lanes, lane,
            (
                jnp.full((1, 1), count_ref[pl.program_id(0)], jnp.int32),
                jnp.zeros((1, n_lanes), _F32),
                jnp.zeros((1, n_lanes), jnp.int32),
            ),
        )
        count_out[0] = _pack_counts(cnt)
        resp_ref[0] = resp_row
        kind_ref[0] = kind_row

    return kernel


def dfc_map_reduce_grid_call(
    mkeys, mvals, mocc, counts, lkeys, ops, params, *,
    interpret: Optional[bool] = None,
):
    """All shards' map combines in one dispatch.  Unlike the ring kinds
    there is no caller-side splice: each shard's table comes back updated
    (map writes scatter by bucket, not contiguously).  Returns ``(keys',
    values', occupied' [S, cap], count' [S], resp, kinds [S, N])``.

    The lane's bucket (row and lane offset in the ``[rows, width]`` table
    view) is hashed here, vectorized over all lanes, so the kernel's
    sequential loop only does the probe."""
    s, cap = mkeys.shape
    n = ops.shape[1]
    bslots, n_rows, width = map_table_geometry(cap)
    base = _map_bucket(lkeys, cap // bslots) * bslots
    lane_rows = lambda x: x.reshape(s, 1, n)  # noqa: E731
    table = lambda x: x.reshape(s, n_rows, width)  # noqa: E731
    smem_row = pl.BlockSpec(
        (1, 1, n), lambda i: (i, 0, 0), memory_space=pltpu.SMEM
    )
    tab = pl.BlockSpec((1, n_rows, width), lambda i: (i, 0, 0))
    row = pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0))
    mk, mv, mo, cnt, resp, kinds = pl.pallas_call(
        _map_kernel(bslots, n),
        grid=(s,),
        out_shape=(
            jax.ShapeDtypeStruct((s, n_rows, width), jnp.int32),
            jax.ShapeDtypeStruct((s, n_rows, width), _F32),
            jax.ShapeDtypeStruct((s, n_rows, width), jnp.int32),
            jax.ShapeDtypeStruct((s, 1, N_COUNTS), jnp.int32),
            jax.ShapeDtypeStruct((s, 1, n), _F32),
            jax.ShapeDtypeStruct((s, 1, n), jnp.int32),
        ),
        in_specs=[smem_row] * 5
        + [pl.BlockSpec(memory_space=pltpu.SMEM), tab, tab, tab],
        out_specs=(
            tab, tab, tab,
            pl.BlockSpec((1, 1, N_COUNTS), lambda i: (i, 0, 0)),
            row, row,
        ),
        interpret=default_interpret() if interpret is None else interpret,
    )(
        lane_rows(base // width),
        lane_rows(base % width),
        lane_rows(lkeys.astype(jnp.int32)),
        lane_rows(ops.astype(jnp.int32)),
        lane_rows(params.astype(_F32)),
        counts.astype(jnp.int32),
        table(mkeys.astype(jnp.int32)),
        table(mvals.astype(_F32)),
        table(mocc.astype(jnp.int32)),
    )
    return (
        mk.reshape(s, cap), mv.reshape(s, cap), mo.reshape(s, cap),
        cnt[:, 0, 0], resp[:, 0], kinds[:, 0],
    )
