"""Fused paged-prefill chunk kernel (serving admission hot path).

One chunked-prefill step of paged attention: each row advances a chunk of up
to T prompt tokens at once (positions ``start[b] .. start[b] + lens[b] - 1``)
against the shared block pools, instead of a token-at-a-time ``lax.scan`` of
decode steps on a private contiguous cache.  Decode rows are the ``lens == 1``
special case (the chunk is the row's last sampled token), so one grid scheme
serves Sarathi-style piggybacked steps that mix prefilling and decoding rows.

What a row reads
----------------
``start``, ``lens`` and the block table ride in as scalar-prefetch operands,
with a fourth, each row's resident block count (:func:`resident_blocks`):
the leading real (non-trash) table entries that hold positions
``< start[b]``.  Only those blocks are fetched and scored, and only the real
blocks ``c0 .. c1`` the chunk lands in are spliced.  Table entries equal to
``TRASH_BLOCK`` are never fetched, scored or written: a live row's resident
entries are real blocks (admission and growth allocate them before anything
attends them), and a write the table sends to trash is a discard.  So an idle
row (table all trash, parked start) only folds its chunk from VMEM and
produces finite output the caller discards.  KV bytes read and scores
computed per chunk step follow the live KV, not ``B * L * bs``;
``ops.fetched_blocks`` names them on the host by the same rule.

Grid / blocking scheme
----------------------
Where the head size fills whole 128-lane rows (``Dh % 128 == 0``): grid
``(B, Hkv)``, one step per (row, KV head) whatever the table width.  The
pools stay in HBM (``pl.ANY``).  A step walks its row's resident blocks in
tiles of ``P = min(L, max(1, 128 // bs))`` blocks (128 positions at ``bs``
16): each block of a tile is copied by manual DMA into one half of a
double-buffered VMEM tile, the next tile's copies start before this one is
scored, and its ``[T*g, P*bs]`` scores fold into an fp32 online-softmax carry
(m, z, acc) in VMEM scratch.  The blocks the chunk lands in are copied into
VMEM while the tiles are scored, spliced, copied back to the pool output
(which aliases the pool input), and waited on before the step ends.

Otherwise (qwen1.5-0.5b's ``Dh`` 64): the pool's rows are padded to 128
lanes in HBM and Mosaic refuses any DMA slice of it, so the pools are walked
by BlockSpec index maps, one block per grid step: grid ``(B, Hkv, L)``, the
K/V map reads ``bt[b, min(i, c1)]`` (a repeated index is not re-fetched, so
a row reads blocks ``0 .. c1`` once and an idle row its trash block once)
and the output map ``bt[b, clip(i, c0, c1)]``.  Scoring and splicing follow
the rule above.

In-kernel semantics (mirrors nn/attention.py's chunk-gather fallback):

  * resident positions ``p < start[b]`` are attended by every chunk token;
    positions past the resident prefix, and tile slots no block was copied
    into, are masked by zeroing their softmax weight (and, in a tile, their
    value rows).  Trie-shared prefix blocks are read in place;
  * the chunk attends itself causally (token ``j`` sees tokens ``<= j``)
    straight from the VMEM chunk operands after the resident blocks — the
    chunk's K/V is folded into the carry without an HBM round-trip;
  * each block the chunk lands in is rewritten with the chunk rows that
    belong to it spliced in by a one-hot ``[bs, T]`` matmul (no dynamic
    gather); every other block is untouched and pad rows ``j >= lens[b]``
    are never written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.serving.paged import TRASH_BLOCK
from repro.serving.tracing import PREFILL_KERNEL

NEG_INF = -2.0e38
LANES = 128                   # a tile scores one MXU width of KV positions


def resident_blocks(start, table, block_size: int, xp=jnp):
    """Blocks the kernel fetches and scores for each row: the leading real
    (non-``TRASH_BLOCK``) entries of the row's table among those holding
    positions ``< start``.  ``xp`` is ``jnp`` in the kernel's wrapper and
    ``numpy`` on the host (``ops.fetched_blocks``)."""
    need = (start + block_size - 1) // block_size
    real = xp.cumprod(table != TRASH_BLOCK, axis=1).sum(axis=1)
    return xp.minimum(need, real).astype(xp.int32)


def _scores(q, k, scale, softcap):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    return s


def _fold(m_ref, z_ref, acc_ref, s, v, valid):
    """Online-softmax step of the (m, z, acc) carry over scores ``s`` and
    values ``v``; returns the new carry without storing it.  Masks by
    zeroing the exp term (not by NEG_INF scores alone): masked columns must
    contribute exactly nothing even while m is still at its NEG_INF init
    (exp(NEG-NEG)=1 would leak)."""
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=-1, keepdims=True))
    c = jnp.exp(m_ref[...] - m_new)
    pw = jnp.exp(s - m_new) * valid
    z = z_ref[...] * c + jnp.sum(pw, axis=-1, keepdims=True)
    acc = acc_ref[...] * c + jax.lax.dot(pw, v,
                                         preferred_element_type=jnp.float32)
    return m_new, z, acc


def _store(m_ref, z_ref, acc_ref, carry):
    m_ref[...], z_ref[...], acc_ref[...] = carry


def _init(m_ref, z_ref, acc_ref):
    _store(m_ref, z_ref, acc_ref, (jnp.full_like(m_ref, NEG_INF),
                                   jnp.zeros_like(z_ref),
                                   jnp.zeros_like(acc_ref)))


def _chunk_out(q, kc_ref, vc_ref, o_ref, m_ref, z_ref, acc_ref, ln, *, t, g,
               scale, softcap):
    # the chunk attends itself causally, straight from VMEM — its K/V never
    # round-trips through HBM before being scored
    kc = kc_ref[0, 0].astype(jnp.float32)      # [t, Dh]
    vc = vc_ref[0, 0].astype(jnp.float32)
    qpos = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 0) // g
    col = jax.lax.broadcasted_iota(jnp.int32, (t * g, t), 1)
    _, z, acc = _fold(m_ref, z_ref, acc_ref, _scores(q, kc, scale, softcap),
                      vc, (col <= qpos) & (col < ln))
    o_ref[0, 0] = (acc / jnp.maximum(z, 1e-30)).astype(o_ref.dtype)


def _splice(i, start, ln, kc_ref, vc_ref, old_k, old_v, *, bs, t):
    """Block ``i`` of the row with the chunk rows that land in it spliced
    over ``old_k``/``old_v`` (one-hot [bs, t] matmul, no dynamic gather)."""
    pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bs, t), 1)
    sel = (pos - start == col) & (col < ln)
    own = jnp.any(sel, axis=1, keepdims=True)              # [bs, 1]
    self_f = sel.astype(jnp.float32)
    out = []
    for new_ref, old in ((kc_ref, old_k), (vc_ref, old_v)):
        rows = jax.lax.dot(self_f, new_ref[0, 0].astype(jnp.float32),
                           preferred_element_type=jnp.float32)
        out.append(jnp.where(own, rows.astype(old.dtype), old))
    return out


def _tiled_kernel(start_ref, lens_ref, bt_ref, nres_ref,
                  q_ref, kc_ref, vc_ref, kp_hbm, vp_hbm,
                  o_ref, ko_hbm, vo_hbm,
                  kbuf, vbuf, kspl, vspl, m_ref, z_ref, acc_ref,
                  tile_sems, read_sems, write_sem,
                  *, bs: int, n_log: int, t: int, g: int, p: int, scale: float,
                  softcap: float):
    b, h = pl.program_id(0), pl.program_id(1)
    start = start_ref[b]
    ln = lens_ref[b]
    nres = nres_ref[b]                         # resident blocks to score
    n_tiles = (nres + p - 1) // p
    c0 = jnp.minimum(start // bs, n_log - 1)   # first block the chunk writes
    c1 = jnp.minimum((start + ln - 1) // bs, n_log - 1)   # last it touches

    def for_tile(j, slot, op):
        # copies of tile j's blocks that hold resident positions; the rest
        # of the tile's slots are neither fetched nor read.  Loops, not
        # unrolled code, keep the kernel quick to lower in every program
        def block(k, carry):
            blk = bt_ref[b, j * p + k]
            dst = pl.ds(pl.multiple_of(k * bs, bs), bs)
            for pool, buf in ((kp_hbm, kbuf), (vp_hbm, vbuf)):
                op(pltpu.make_async_copy(pool.at[blk, h], buf.at[slot, dst],
                                         tile_sems.at[slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(p, nres - j * p), block, 0)

    def for_splice(op):
        # the real blocks the chunk lands in, with their splice slot
        def block(s, carry):
            blk = bt_ref[b, c0 + s]

            @pl.when(blk != TRASH_BLOCK)
            def _():
                op(s, c0 + s, blk)
            return carry

        jax.lax.fori_loop(0, c1 - c0 + 1, block, 0)

    def splice_reads(s, blk):
        # a semaphore per block: its wait must not be met by another's copy
        return [pltpu.make_async_copy(pool.at[blk, h], buf.at[s],
                                      read_sems.at[s])
                for pool, buf in ((kp_hbm, kspl), (vp_hbm, vspl))]

    def splice_writes(s, blk):
        return [pltpu.make_async_copy(buf.at[s], out.at[blk, h], write_sem)
                for out, buf in ((ko_hbm, kspl), (vo_hbm, vspl))]

    # the blocks the chunk lands in: read now, spliced after the scores
    for_splice(lambda s, i, blk: [cp.start() for cp in splice_reads(s, blk)])

    _init(m_ref, z_ref, acc_ref)
    q = q_ref[0, 0].astype(jnp.float32)        # [t*g, Dh], row r = j*g + gi
    limit = jnp.minimum(start, nres * bs)      # resident positions scored

    @pl.when(n_tiles > 0)
    def _():
        for_tile(0, 0, lambda cp: cp.start())

    def tile(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_tiles)
        def _():
            for_tile(j + 1, 1 - slot, lambda cp: cp.start())

        for_tile(j, slot, lambda cp: cp.wait())
        pos = j * (p * bs) + jax.lax.broadcasted_iota(
            jnp.int32, (t * g, p * bs), 1)
        kv_pos = j * (p * bs) + jax.lax.broadcasted_iota(
            jnp.int32, (p * bs, 1), 0)
        # slots past the resident prefix hold whatever VMEM held: zero
        # their value rows too, so 0 * garbage cannot reach the carry
        vt = jnp.where(kv_pos < limit, vbuf[slot].astype(jnp.float32), 0.0)
        s = _scores(q, kbuf[slot].astype(jnp.float32), scale, softcap)
        _store(m_ref, z_ref, acc_ref,
               _fold(m_ref, z_ref, acc_ref, s, vt, pos < limit))
        return carry

    jax.lax.fori_loop(0, n_tiles, tile, 0)
    _chunk_out(q, kc_ref, vc_ref, o_ref, m_ref, z_ref, acc_ref, ln, t=t,
               g=g, scale=scale, softcap=softcap)

    def splice(s, i, blk):
        for cp in splice_reads(s, blk):
            cp.wait()
        kspl[s], vspl[s] = _splice(i, start, ln, kc_ref, vc_ref, kspl[s],
                                   vspl[s], bs=bs, t=t)
        for cp in splice_writes(s, blk):
            cp.start()

    for_splice(splice)
    for_splice(lambda s, i, blk: [cp.wait() for cp in splice_writes(s, blk)])


def _block_kernel(start_ref, lens_ref, bt_ref, nres_ref,
                  q_ref, kc_ref, vc_ref, kp_ref, vp_ref,
                  o_ref, ko_ref, vo_ref, m_ref, z_ref, acc_ref,
                  *, bs: int, n_log: int, t: int, g: int, scale: float,
                  softcap: float):
    b, i = pl.program_id(0), pl.program_id(2)
    start = start_ref[b]
    ln = lens_ref[b]
    c0 = jnp.minimum(start // bs, n_log - 1)
    c1 = jnp.minimum((start + ln - 1) // bs, n_log - 1)

    @pl.when(i == 0)
    def _():
        _init(m_ref, z_ref, acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)

    @pl.when(i < nres_ref[b])
    def _resident():
        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (t * g, bs), 1)
        s = _scores(q, kp_ref[0, 0].astype(jnp.float32), scale, softcap)
        _store(m_ref, z_ref, acc_ref,
               _fold(m_ref, z_ref, acc_ref, s,
                     vp_ref[0, 0].astype(jnp.float32), pos < start))

    @pl.when(i == c1)
    def _():
        _chunk_out(q, kc_ref, vc_ref, o_ref, m_ref, z_ref, acc_ref, ln,
                   t=t, g=g, scale=scale, softcap=softcap)

    @pl.when((i >= c0) & (i <= c1) & (bt_ref[b, i] != TRASH_BLOCK))
    def _():
        ko_ref[0, 0], vo_ref[0, 0] = _splice(
            i, start, ln, kc_ref, vc_ref, kp_ref[0, 0], vp_ref[0, 0],
            bs=bs, t=t)


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
    nres = resident_blocks(start, block_tables, bs)
    static = dict(bs=bs, n_log=n_log, t=t, g=g, scale=scale, softcap=softcap)
    carry = [pltpu.VMEM((tg, 1), jnp.float32),             # m
             pltpu.VMEM((tg, 1), jnp.float32),             # z
             pltpu.VMEM((tg, dh), jnp.float32)]            # acc

    if dh % LANES == 0:
        p = min(n_log, max(1, LANES // bs))            # blocks per tile
        # blocks a span of t positions can touch, wherever it starts
        n_spl = min((t + bs - 2) // bs + 1, n_log)
        row = lambda b, h, *_: (b, h, 0, 0)                 # noqa: E731
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        grid, pool_in, pool_out = (bq, hkv), hbm, hbm
        scratch = [
            pltpu.VMEM((2, p * bs, dh), k_pool.dtype),     # K tiles
            pltpu.VMEM((2, p * bs, dh), v_pool.dtype),     # V tiles
            pltpu.VMEM((n_spl, bs, dh), k_pool.dtype),     # K splice blocks
            pltpu.VMEM((n_spl, bs, dh), v_pool.dtype),     # V splice blocks
            *carry,
            pltpu.SemaphoreType.DMA((2,)),                 # tile halves
            pltpu.SemaphoreType.DMA((n_spl,)),             # splice reads
            pltpu.SemaphoreType.DMA(()),                   # splice writes
        ]
        body = functools.partial(_tiled_kernel, p=p, **static)
    else:
        def kv_map(b, h, i, start_ref, lens_ref, bt_ref, _):
            c1 = jnp.minimum((start_ref[b] + lens_ref[b] - 1) // bs,
                             n_log - 1)
            return (bt_ref[b, jnp.minimum(i, c1)], h, 0, 0)

        def kv_out_map(b, h, i, start_ref, lens_ref, bt_ref, _):
            c0 = jnp.minimum(start_ref[b] // bs, n_log - 1)
            c1 = jnp.minimum((start_ref[b] + lens_ref[b] - 1) // bs,
                             n_log - 1)
            return (bt_ref[b, jnp.clip(i, c0, c1)], h, 0, 0)

        row = lambda b, h, i, *_: (b, h, 0, 0)              # noqa: E731
        block = (1, 1, bs, dh)
        grid = (bq, hkv, n_log)
        pool_in = pl.BlockSpec(block, kv_map)
        pool_out = pl.BlockSpec(block, kv_out_map)
        scratch = carry
        body = functools.partial(_block_kernel, **static)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tg, dh), row),
            pl.BlockSpec((1, 1, t, dh), row),
            pl.BlockSpec((1, 1, t, dh), row),
            pool_in, pool_in,
        ],
        out_specs=[pl.BlockSpec((1, 1, tg, dh), row), pool_out, pool_out],
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bq, hkv, tg, dh), k_pool.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # pool operands (positions 7/8 incl. the four scalar-prefetch args)
        # alias the pool outputs: the chunk scatter is in place, untouched
        # blocks keep their contents
        input_output_aliases={7: 1, 8: 2},
        interpret=interpret,
        name=PREFILL_KERNEL,       # the instruction name profile readers match
    )(start, lens, block_tables, nres, q, k_chunk, v_chunk, k_pool, v_pool)
