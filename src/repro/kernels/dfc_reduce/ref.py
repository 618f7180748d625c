"""Pure-jnp oracles for the dfc_reduce kernels (same signatures/outputs)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dfc_reduce.kernel import (
    CAS_DOM,
    MAP_BUCKET_SLOTS,
    OP_DEQ,
    OP_ENQ,
    OP_MAP_CAS,
    OP_MAP_DELETE,
    OP_MAP_INSERT,
    OP_MAP_LOOKUP,
    OP_POP,
    OP_POPL,
    OP_POPR,
    OP_PUSH,
    OP_PUSHL,
    OP_PUSHR,
    R_ACK,
    R_CAS_FAIL,
    R_EMPTY,
    R_FULL,
    R_NONE,
    R_VALUE,
    _map_bucket,
)


def dfc_reduce_ref(ops, params, window, size):
    n = ops.shape[0]
    params = params.astype(jnp.float32)
    window = window.astype(jnp.float32)
    size = jnp.asarray(size, jnp.int32).reshape(())

    is_push = ops == OP_PUSH
    is_pop = ops == OP_POP
    push_rank = jnp.where(is_push, jnp.cumsum(is_push) - 1, -1)
    pop_rank = jnp.where(is_pop, jnp.cumsum(is_pop) - 1, -1)
    p_total = jnp.sum(is_push)
    q_total = jnp.sum(is_pop)
    n_elim = jnp.minimum(p_total, q_total)

    push_by_rank = jnp.zeros((n,), jnp.float32).at[
        jnp.where(is_push, push_rank, n)
    ].add(params, mode="drop")
    elim_pop_val = push_by_rank[jnp.clip(pop_rank, 0, n - 1)]

    surplus_push = is_push & (push_rank >= n_elim)
    segment = jnp.zeros((n,), jnp.float32).at[
        jnp.where(surplus_push, push_rank - n_elim, n)
    ].add(params, mode="drop")

    surplus_pop = is_pop & (pop_rank >= n_elim)
    depth = pop_rank - n_elim
    win_src = n - 1 - depth
    pop_ok = surplus_pop & (win_src >= 0) & (depth < size)
    stack_val = window[jnp.clip(win_src, 0, n - 1)]

    kinds = jnp.full((n,), R_NONE, dtype=jnp.int32)
    kinds = jnp.where(is_push, R_ACK, kinds)
    kinds = jnp.where(is_pop & (pop_rank < n_elim), R_VALUE, kinds)
    kinds = jnp.where(pop_ok, R_VALUE, kinds)
    kinds = jnp.where(surplus_pop & ~pop_ok, R_EMPTY, kinds)
    resp = jnp.zeros((n,), jnp.float32)
    resp = jnp.where(is_pop & (pop_rank < n_elim), elim_pop_val, resp)
    resp = jnp.where(pop_ok, stack_val, resp)

    counts = jnp.stack(
        [
            jnp.maximum(p_total - n_elim, 0),
            jnp.minimum(jnp.maximum(q_total - n_elim, 0), size),
            n_elim,
            q_total,
        ]
    ).astype(jnp.int32)
    return resp, kinds, segment, counts


def dfc_queue_reduce_ref(ops, params, window, size):
    n = ops.shape[0]
    params = params.astype(jnp.float32)
    window = window.astype(jnp.float32)
    size = jnp.asarray(size, jnp.int32).reshape(())

    is_enq = ops == OP_ENQ
    is_deq = ops == OP_DEQ
    enq_rank = jnp.where(is_enq, jnp.cumsum(is_enq) - 1, -1)
    deq_rank = jnp.where(is_deq, jnp.cumsum(is_deq) - 1, -1)
    p_total = jnp.sum(is_enq)
    q_total = jnp.sum(is_deq)
    n_from_q = jnp.minimum(q_total, size)
    n_elim = jnp.minimum(jnp.maximum(q_total - size, 0), p_total)

    served = is_deq & (deq_rank < size)
    ring_val = window[jnp.clip(deq_rank, 0, n - 1)]

    enq_by_rank = jnp.zeros((n,), jnp.float32).at[
        jnp.where(is_enq, enq_rank, n)
    ].add(params, mode="drop")
    paired = is_deq & (deq_rank >= size) & (deq_rank - size < n_elim)
    pair_val = enq_by_rank[jnp.clip(deq_rank - size, 0, n - 1)]
    empty = is_deq & (deq_rank >= size + n_elim)

    surplus_enq = is_enq & (enq_rank >= n_elim)
    segment = jnp.zeros((n,), jnp.float32).at[
        jnp.where(surplus_enq, enq_rank - n_elim, n)
    ].add(params, mode="drop")

    kinds = jnp.full((n,), R_NONE, dtype=jnp.int32)
    kinds = jnp.where(is_enq, R_ACK, kinds)
    kinds = jnp.where(served | paired, R_VALUE, kinds)
    kinds = jnp.where(empty, R_EMPTY, kinds)
    resp = jnp.zeros((n,), jnp.float32)
    resp = jnp.where(served, ring_val, resp)
    resp = jnp.where(paired, pair_val, resp)

    counts = jnp.stack(
        [jnp.maximum(p_total - n_elim, 0), n_from_q, n_elim, q_total]
    ).astype(jnp.int32)
    return resp, kinds, segment, counts


def dfc_deque_reduce_ref(ops, params, window_l, window_r, size):
    n = ops.shape[0]
    params = params.astype(jnp.float32)
    window_l = window_l.astype(jnp.float32)
    window_r = window_r.astype(jnp.float32)
    size = jnp.asarray(size, jnp.int32).reshape(())

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

    pl_by_rank = jnp.zeros((n,), jnp.float32).at[
        jnp.where(is_pl, pl_rank, n)
    ].add(params, mode="drop")
    pr_by_rank = jnp.zeros((n,), jnp.float32).at[
        jnp.where(is_pr, pr_rank, n)
    ].add(params, mode="drop")
    eliml = is_ql & (ql_rank < nl_elim)
    elimr = is_qr & (qr_rank < nr_elim)
    eliml_val = pl_by_rank[jnp.clip(ql_rank, 0, n - 1)]
    elimr_val = pr_by_rank[jnp.clip(qr_rank, 0, n - 1)]

    sl = jnp.maximum(npl - nl_elim, 0)
    tl = jnp.maximum(nql - nl_elim, 0)
    surplus_pl = is_pl & (pl_rank >= nl_elim)
    seg_l = jnp.zeros((n,), jnp.float32).at[
        jnp.where(surplus_pl, pl_rank - nl_elim, n)
    ].add(params, mode="drop")
    dl = jnp.minimum(tl, size)
    surplus_ql = is_ql & (ql_rank >= nl_elim)
    kl = ql_rank - nl_elim
    lpop_ok = surplus_ql & (kl < size)
    lpop_val = window_l[jnp.clip(kl, 0, n - 1)]
    size_after = size + sl - dl

    sr = jnp.maximum(npr - nr_elim, 0)
    tr = jnp.maximum(nqr - nr_elim, 0)
    surplus_pr = is_pr & (pr_rank >= nr_elim)
    seg_r = jnp.zeros((n,), jnp.float32).at[
        jnp.where(surplus_pr, pr_rank - nr_elim, n)
    ].add(params, mode="drop")
    dr = jnp.minimum(tr, size_after)
    surplus_qr = is_qr & (qr_rank >= nr_elim)
    kr = qr_rank - nr_elim
    rpop_ok = surplus_qr & (kr < size_after)
    rpop_val = jnp.where(
        kr < size,
        window_r[jnp.clip(kr, 0, n - 1)],
        seg_l[jnp.clip(kr - size, 0, n - 1)],
    )

    kinds = jnp.full((n,), R_NONE, dtype=jnp.int32)
    kinds = jnp.where(is_pl | is_pr, R_ACK, kinds)
    kinds = jnp.where(eliml | elimr | lpop_ok | rpop_ok, R_VALUE, kinds)
    kinds = jnp.where(surplus_ql & ~lpop_ok, R_EMPTY, kinds)
    kinds = jnp.where(surplus_qr & ~rpop_ok, R_EMPTY, kinds)
    resp = jnp.zeros((n,), jnp.float32)
    resp = jnp.where(eliml, eliml_val, resp)
    resp = jnp.where(elimr, elimr_val, resp)
    resp = jnp.where(lpop_ok, lpop_val, resp)
    resp = jnp.where(rpop_ok, rpop_val, resp)

    counts = jnp.stack(
        [sl, dl, sr, dr, nl_elim, nr_elim, size_after, jnp.zeros((), jnp.int32)]
    ).astype(jnp.int32)
    return resp, kinds, seg_l, seg_r, counts


def dfc_map_reduce_ref(mkeys, mvals, mocc, count, lkeys, ops, params):
    """Oracle for the map kernel: same lane-order walk, but probing via
    full-table masks instead of the kernel's one-row bucket windows."""
    cap = mkeys.shape[0]
    bslots = min(cap, MAP_BUCKET_SLOTS)
    n_buckets = cap // bslots
    slot_bucket = jnp.arange(cap, dtype=jnp.int32) // bslots
    slot_idx = jnp.arange(cap, dtype=jnp.int32)

    def lane(carry, xs):
        mk, mv, mo, cnt = carry
        key, op, par = xs
        in_b = slot_bucket == _map_bucket(key, n_buckets)
        occ = mo != 0
        hit = in_b & occ & (mk == key)
        has_hit = jnp.any(hit)
        hit_idx = jnp.argmax(hit).astype(jnp.int32)
        free = in_b & ~occ
        has_free = jnp.any(free)
        free_idx = jnp.argmax(free).astype(jnp.int32)
        cur = jnp.sum(jnp.where(hit, mv, 0.0))

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
        wslot = jnp.where(has_hit, hit_idx, free_idx)
        wval = jnp.where(is_cas, cas_new, par)
        wmask = do_write & (slot_idx == wslot)
        dmask = do_del & (slot_idx == hit_idx)
        mk = jnp.where(wmask, key, jnp.where(dmask, 0, mk))
        mv = jnp.where(wmask, wval, jnp.where(dmask, 0.0, mv))
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
        return (mk, mv, mo, cnt), (resp, kind)

    (mk, mv, mo, cnt), (resp, kinds) = jax.lax.scan(
        lane,
        (
            jnp.asarray(mkeys, jnp.int32),
            jnp.asarray(mvals, jnp.float32),
            jnp.asarray(mocc, jnp.int32),
            jnp.asarray(count, jnp.int32).reshape(()),
        ),
        (
            jnp.asarray(lkeys, jnp.int32),
            jnp.asarray(ops, jnp.int32),
            jnp.asarray(params, jnp.float32),
        ),
    )
    return mk, mv, mo, cnt, resp, kinds
