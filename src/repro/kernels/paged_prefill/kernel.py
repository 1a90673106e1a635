"""Fused paged-prefill chunk kernel (serving admission hot path).

One chunked-prefill step of paged attention: each row advances a chunk of up
to T prompt tokens at once (positions ``start[b] .. start[b] + lens[b] - 1``)
against the shared block pools, instead of a token-at-a-time ``lax.scan`` of
decode steps on a private contiguous cache.  Decode rows are the ``lens == 1``
special case (the chunk is the row's last sampled token), so one grid scheme
serves Sarathi-style piggybacked steps that mix prefilling and decoding rows.

Grid / blocking scheme
----------------------
Grid ``(B, Hkv, L)`` with the logical-block dimension innermost, reusing
paged_attention's template: the fp32 (m, z, acc) carry for all ``T * g``
query rows persists in VMEM scratch across a row's blocks.  ``start``,
``lens``, and ``block_tables`` ride in as scalar-prefetch operands; the K/V
pool BlockSpec index map reads ``bt[b, min(i, c1)]`` (``c1`` = the last block
the row's chunk touches) so the pipeline streams each resident block exactly
once and rows shallower than the table width cost nothing past their last
block — KV bytes read per chunk step are ``O(tokens resident)``, not
``O(B * L * bs)``.

In-kernel semantics (mirrors nn/attention.py's chunk-gather fallback):

  * resident positions ``p < start[b]`` are attended by every chunk token;
    garbage beyond them (stale partial-block slots, trash-block contents for
    parked idle rows) is masked by zeroing its softmax weight.  Trie-shared
    prefix blocks are read in place — the prefix-cache seeding gather of the
    retired batch-of-one prefill path does not exist here;
  * the chunk attends itself causally (token ``j`` sees tokens ``<= j``)
    straight from the VMEM chunk operands at the row's last touched block —
    the chunk's K/V is folded into the carry without an HBM round-trip;
  * the chunk's K/V is scatter-written into the row's pool blocks covering
    ``[start, start + lens)`` via pool outputs aliased onto the pool inputs:
    each touched block is rewritten with the chunk rows spliced in (a one-hot
    ``[bs, T]`` matmul — no dynamic gather), every other block is untouched,
    and pad rows ``j >= lens[b]`` are never written;
  * idle rows (table all trash, parked start) stream the trash block and
    produce finite garbage the caller discards — no occupancy branch, the
    same contract as the decode kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.serving.tracing import PREFILL_KERNEL

NEG_INF = -2.0e38


def _kernel(start_ref, lens_ref, bt_ref, q_ref, kc_ref, vc_ref, kp_ref, vp_ref,
            o_ref, ko_ref, vo_ref, m_ref, z_ref, acc_ref,
            *, bs: int, n_log: int, t: int, g: int, scale: float,
            softcap: float):
    b, i = pl.program_id(0), pl.program_id(2)
    start = start_ref[b]
    ln = lens_ref[b]
    lr = (start - 1) // bs                     # last resident block (-1: none)
    c0 = jnp.minimum(start // bs, n_log - 1)   # first block the chunk writes
    c1 = jnp.minimum((start + ln - 1) // bs, n_log - 1)   # last block touched

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        z_ref[...] = jnp.zeros_like(z_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)        # [t*g, Dh], row r = j*g + gi

    @pl.when(i <= lr)
    def _resident():
        kb = kp_ref[0, 0].astype(jnp.float32)  # [bs, Dh]
        vb = vp_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (t * g, bs), 1)
        valid = pos < start                    # resident prefix only
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

    @pl.when(i == c1)
    def _chunk_fold():
        # the chunk attends itself causally, straight from VMEM — its K/V
        # never round-trips through HBM before being scored
        kc = kc_ref[0, 0].astype(jnp.float32)  # [t, Dh]
        vc = vc_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kc, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 0) // g
        col = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 1)
        valid = (col <= qpos) & (col < ln)     # causal + pad rows masked
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=-1, keepdims=True))
        c = jnp.exp(m_ref[...] - m_new)
        p = jnp.exp(s - m_new) * valid
        z2 = z_ref[...] * c + jnp.sum(p, axis=-1, keepdims=True)
        acc2 = acc_ref[...] * c + jax.lax.dot(
            p, vc, preferred_element_type=jnp.float32)
        o_ref[0, 0] = (acc2 / jnp.maximum(z2, 1e-30)).astype(o_ref.dtype)

    @pl.when((i >= c0) & (i <= c1))
    def _splice():
        # fused scatter: rewrite this block with the chunk rows that land in
        # it spliced in (pool outputs alias the pool inputs; blocks outside
        # [c0, c1] are never written).  One-hot [bs, t] matmul instead of a
        # dynamic row gather.
        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, t), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (bs, t), 1)
        sel = (pos - start == col) & (col < ln)
        own = jnp.any(sel, axis=1, keepdims=True)          # [bs, 1]
        self_f = sel.astype(jnp.float32)
        kn = jax.lax.dot(self_f, kc_ref[0, 0].astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        vn = jax.lax.dot(self_f, vc_ref[0, 0].astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        ko_ref[0, 0] = jnp.where(own, kn.astype(ko_ref.dtype), kp_ref[0, 0])
        vo_ref[0, 0] = jnp.where(own, vn.astype(vo_ref.dtype), vp_ref[0, 0])


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
def paged_prefill_chunk_kernel(
        q: jax.Array, k_chunk: jax.Array, v_chunk: jax.Array,
        k_pool: jax.Array, v_pool: jax.Array,
        block_tables: jax.Array, start: jax.Array, lens: jax.Array,
        scale: float, softcap: float = 0.0, interpret: bool = False):
    """q [B, Hkv, T*g, Dh] (query row r = chunk position r//g);
    k_chunk/v_chunk [B, Hkv, T, Dh] (the chunk's projected KV); pools
    [N, Hkv, bs, Dh]; block_tables int32 [B, L]; start/lens int32 [B]
    (first write position / valid chunk length, lens >= 1).

    Returns (out [B, Hkv, T*g, Dh] in pool dtype, k_pool', v_pool') with the
    chunk's KV scattered into each row's blocks in place."""
    bq, hkv, tg, dh = q.shape
    t = k_chunk.shape[2]
    bs = k_pool.shape[2]
    n_log = block_tables.shape[1]
    g = tg // t

    def kv_map(b, h, i, start_ref, lens_ref, bt_ref):
        c1 = jnp.minimum((start_ref[b] + lens_ref[b] - 1) // bs, n_log - 1)
        return (bt_ref[b, jnp.minimum(i, c1)], h, 0, 0)

    def kv_out_map(b, h, i, start_ref, lens_ref, bt_ref):
        c0 = jnp.minimum(start_ref[b] // bs, n_log - 1)
        c1 = jnp.minimum((start_ref[b] + lens_ref[b] - 1) // bs, n_log - 1)
        return (bt_ref[b, jnp.clip(i, c0, c1)], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bq, hkv, n_log),
        in_specs=[
            pl.BlockSpec((1, 1, tg, dh), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, t, dh), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, t, dh), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh), kv_map),
            pl.BlockSpec((1, 1, bs, dh), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, tg, dh), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, dh), kv_out_map),
            pl.BlockSpec((1, 1, bs, dh), kv_out_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((tg, 1), jnp.float32),          # m
            pltpu.VMEM((tg, 1), jnp.float32),          # z
            pltpu.VMEM((tg, dh), jnp.float32),         # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, n_log=n_log, t=t, g=g, scale=scale,
                          softcap=softcap),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bq, hkv, tg, dh), k_pool.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # pool operands (positions 6/7 incl. the three scalar-prefetch args)
        # alias the pool outputs: the chunk scatter is in place, untouched
        # blocks keep their contents
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret,
        name=PREFILL_KERNEL,       # the instruction name profile readers match
    )(start, lens, block_tables, q, k_chunk, v_chunk, k_pool, v_pool)
