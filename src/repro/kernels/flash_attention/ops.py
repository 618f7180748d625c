"""Public wrapper: backend-selected attention (pallas kernel / jnp oracle).

Also provides ``chunked_attention`` — an XLA-native online-softmax attention
(scan over key blocks) used by the dry-run path where TPU Pallas cannot
lower.  Identical math to the kernel; O(S·blk) live memory instead of O(S²).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def attention(q, k, v, *, causal=True, backend: str = "ref", **kw):
    if backend == "pallas":  # interpreted on the CPU only
        return flash_attention(
            q, k, v, causal=causal,
            interpret=jax.default_backend() == "cpu", **kw,
        )
    if backend == "chunked":
        return chunked_attention(q, k, v, causal=causal, **kw)
    return attention_ref(q, k, v, causal=causal)


@functools.partial(
    jax.jit, static_argnames=("causal", "blk_k", "unroll", "q_offset_static")
)
def chunked_attention(
    q, k, v, *, causal=True, blk_k: int = 512, q_offset=0, unroll: bool = True,
    q_offset_static=True,
):
    """Online-softmax attention over key chunks (flash-in-XLA).

    q: (B, S, Hq, hd); k/v: (B, T, Hkv, hd).  Never materializes (S, T).
    ``unroll=True`` uses a Python loop (static chunk count) — required for
    honest cost_analysis accounting (a lax.scan body would be counted once);
    it also lets XLA skip fully-masked chunks at compile time."""
    b, s, hq, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    blk_k = min(blk_k, t)
    n_k = t // blk_k
    scale = 1.0 / np.sqrt(hd)

    qf = q.reshape(b, s, hkv, g, hd).astype(jnp.float32)
    kc = k.reshape(b, n_k, blk_k, hkv, hd)
    vc = v.reshape(b, n_k, blk_k, hkv, hd)
    qpos = jnp.arange(s) + q_offset

    def step(carry, k_blk, v_blk, ki):
        m, l, acc = carry
        sres = jnp.einsum("bskgd,btkd->bkgst", qf, k_blk.astype(jnp.float32)) * scale
        if causal:
            kpos = ki * blk_k + jnp.arange(blk_k)
            mask = kpos[None, :] <= qpos[:, None]
            sres = jnp.where(mask[None, None, None], sres, -1e30)
        m_new = jnp.maximum(m, jnp.max(sres, axis=-1))
        p = jnp.exp(sres - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p, v_blk.astype(jnp.float32)
        )
        return (m_new, l, acc)

    carry = (
        jnp.full((b, hkv, g, s), -1e30, jnp.float32),
        jnp.zeros((b, hkv, g, s), jnp.float32),
        jnp.zeros((b, hkv, g, s, hd), jnp.float32),
    )
    if unroll:
        for ki in range(n_k):
            if causal and q_offset_static and ki * blk_k > s - 1:
                break  # fully-masked chunks contribute nothing (q_offset=0)
            carry = step(carry, kc[:, ki], vc[:, ki], ki)
    else:
        def scan_step(c, inp):
            kb, vb, ki = inp
            return step(c, kb, vb, ki), None

        carry, _ = jax.lax.scan(
            scan_step,
            carry,
            (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(n_k)),
        )
    m, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, -2, 1).reshape(b, s, hq, hd)
    return out.astype(q.dtype)
