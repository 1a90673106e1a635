"""Fused paged-attention decode kernel (serving hot path).

One decode step of paged attention without the dense block-table gather:
instead of materializing ``pool[block_tables]`` as a ``[B, L, Hkv, bs, Dh]``
buffer (worst-case bandwidth, exactly what the paged layout was meant to
kill), each (row, kv-head) grid cell streams the row's KV blocks straight out
of the shared pools and folds them into a flash-style online-softmax carry.

Grid / blocking scheme
----------------------
Grid ``(B, Hkv, L)`` with the logical-block dimension innermost; TPU grids
iterate in order, so the fp32 (m, z, acc) carry lives in VMEM scratch that
persists across a row's blocks (same trick as ssd_scan's recurrent state).
``block_tables`` and the per-row write positions ``idx`` ride in as
scalar-prefetch operands: the K/V pool BlockSpec index maps read
``bt[b, min(i, idx[b] // bs)]`` to pick which physical pool block the
pipeline fetches next.  Because consecutive grid steps that map to the same
block skip the re-fetch, rows shallower than the table width cost no extra
HBM traffic past their last resident block — KV bytes read per step are
``O(tokens resident)``, not ``O(B * L * bs)``.

In-kernel semantics (mirrors nn/attention.py's gather fallback):

  * stored positions ``p < idx[b]`` attend; garbage beyond the row's write
    position — trash-block contents, stale partial-last-block slots — is
    masked by zeroing its softmax weight (mask multiplies the exp term, so a
    fully-masked block contributes exactly nothing to the carry);
  * the step's new K/V (position ``idx[b]``) never round-trips through HBM:
    its score folds into the carry at the row's last block, and the
    scatter-write into the row's current pool block is fused — the kernel
    rewrites that one block with the new row spliced in, via pool outputs
    aliased onto the pool inputs (every other block is untouched);
  * idle rows (block table all trash, parked write position) stream the
    trash block and produce finite garbage the caller discards — no
    occupancy branch, same contract as the gather path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _kernel(idx_ref, bt_ref, q_ref, kn_ref, vn_ref, kp_ref, vp_ref,
            o_ref, ko_ref, vo_ref, m_ref, z_ref, acc_ref,
            *, bs: int, n_log: int, scale: float, softcap: float):
    b, i = pl.program_id(0), pl.program_id(2)
    idx = idx_ref[b]
    lim = jnp.minimum(idx // bs, n_log - 1)    # row's last resident block

    @pl.when(i <= lim)
    def _process():
        @pl.when(i == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            z_ref[...] = jnp.zeros_like(z_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[0, 0].astype(jnp.float32)            # [g, Dh]
        kb = kp_ref[0, 0].astype(jnp.float32)          # [bs, Dh]
        vb = vp_ref[0, 0].astype(jnp.float32)
        g = q.shape[0]

        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)
        valid = pos < idx                              # stored tokens only
        # mask by zeroing the exp term (not by NEG_INF scores): a block with
        # no stored tokens must contribute exactly nothing to the carry even
        # while m is still at its NEG_INF init (exp(NEG-NEG)=1 would leak)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=-1, keepdims=True))
        c = jnp.exp(m_ref[...] - m_new)
        p = jnp.exp(s - m_new) * valid
        m_ref[...] = m_new
        z_ref[...] = z_ref[...] * c + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * c + jax.lax.dot(
            p, vb, preferred_element_type=jnp.float32)

        @pl.when(i == lim)
        def _finish():
            # fused scatter: splice the new K/V row into the current block
            # and write that one block back (pool outputs alias the inputs)
            kn = kn_ref[0, 0, 0]                       # [Dh], model dtype
            vn = vn_ref[0, 0, 0]
            off = idx % bs
            row = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0) == off
            ko_ref[0, 0] = jnp.where(row, kn[None].astype(ko_ref.dtype),
                                     kp_ref[0, 0])
            vo_ref[0, 0] = jnp.where(row, vn[None].astype(vo_ref.dtype),
                                     vp_ref[0, 0])
            # fold the new token (position idx, always attended) into the
            # carry without an HBM round-trip, then normalize
            sn = jnp.sum(q * kn.astype(jnp.float32)[None], axis=-1,
                         keepdims=True) * scale        # [g, 1]
            if softcap > 0.0:
                sn = softcap * jnp.tanh(sn / softcap)
            m2 = jnp.maximum(m_ref[...], sn)
            c2 = jnp.exp(m_ref[...] - m2)
            pn = jnp.exp(sn - m2)
            z2 = z_ref[...] * c2 + pn
            acc2 = acc_ref[...] * c2 + pn * vn.astype(jnp.float32)[None]
            o_ref[0, 0] = (acc2 / jnp.maximum(z2, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
def paged_attention_decode_kernel(
        q: jax.Array, k_new: jax.Array, v_new: jax.Array,
        k_pool: jax.Array, v_pool: jax.Array,
        block_tables: jax.Array, idx: jax.Array,
        scale: float, softcap: float = 0.0, interpret: bool = False):
    """q [B, Hkv, g, Dh]; k_new/v_new [B, Hkv, 1, Dh]; pools
    [N, Hkv, bs, Dh]; block_tables int32 [B, L]; idx int32 [B] (per-row
    write position).  The new K/V carry a unit axis so their block's last
    two dims ``(1, Dh)`` equal the array's: Mosaic refuses a block whose
    second-to-last dim is 1 out of a larger array dim (here Hkv).

    Returns (out [B, Hkv, g, Dh] in pool dtype, k_pool', v_pool') with the
    new K/V scattered into each row's current block in place."""
    bq, hkv, g, dh = q.shape
    n, _, bs, _ = k_pool.shape
    n_log = block_tables.shape[1]

    def kv_map(b, h, i, idx_ref, bt_ref):
        j = jnp.minimum(i, jnp.minimum(idx_ref[b] // bs, n_log - 1))
        return (bt_ref[b, j], h, 0, 0)

    def kv_out_map(b, h, i, idx_ref, bt_ref):
        cur = jnp.minimum(idx_ref[b] // bs, n_log - 1)
        return (bt_ref[b, cur], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bq, hkv, n_log),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, dh), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, dh), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh), kv_map),
            pl.BlockSpec((1, 1, bs, dh), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh), kv_out_map),
            pl.BlockSpec((1, 1, bs, dh), kv_out_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),           # m
            pltpu.VMEM((g, 1), jnp.float32),           # z
            pltpu.VMEM((g, dh), jnp.float32),          # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, n_log=n_log, scale=scale,
                          softcap=softcap),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bq, hkv, g, dh), k_pool.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # pool operands (positions 5/6 incl. the two scalar-prefetch args)
        # alias the pool outputs: the scatter is in place, untouched blocks
        # keep their contents
        input_output_aliases={5: 1, 6: 2},
        interpret=interpret,
    )(idx, block_tables, q, k_new, v_new, k_pool, v_pool)
