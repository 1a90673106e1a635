"""Pure-jnp oracle for the fused paged-attention decode kernel.

Deliberately the *materializing* formulation the kernel replaces: scatter the
new K/V into the row's current pool block, gather the whole block table into
a dense ``[B, Hkv, L*bs, Dh]`` window, run masked dense softmax attention
(positions ``<= idx``).  Matches nn/attention.py's gather fallback
semantics; tests sweep shapes and assert the kernel agrees.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def paged_attention_decode_ref(q: jax.Array, k_new: jax.Array,
                               v_new: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, block_tables: jax.Array,
                               idx: jax.Array, scale: float,
                               softcap: float = 0.0):
    """Same contract as kernel.paged_attention_decode_kernel, minus the
    new K/V's unit axis: q [B, Hkv, g, Dh]; k_new/v_new [B, Hkv, Dh];
    pools [N, Hkv, bs, Dh];
    block_tables [B, L]; idx [B] -> (out [B, Hkv, g, Dh], k_pool', v_pool')."""
    b, hkv, g, dh = q.shape
    bs = k_pool.shape[2]
    nlog = block_tables.shape[1]
    blk = jnp.minimum(idx // bs, nlog - 1)
    bid = jnp.take_along_axis(block_tables, blk[:, None], 1)[:, 0]
    off = idx % bs
    k_pool = k_pool.at[bid, :, off].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[bid, :, off].set(v_new.astype(v_pool.dtype))
    k = k_pool[block_tables]                  # [B, L, Hkv, bs, Dh]
    v = v_pool[block_tables]
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nlog * bs, dh)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, hkv, nlog * bs, dh)
    s = jnp.einsum("bkgd,bktd->bkgt", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    t = nlog * bs
    mask = (jnp.arange(t)[None] <= idx[:, None])[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", w, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(k_pool.dtype), k_pool, v_pool
