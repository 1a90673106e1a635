"""Public wrapper: fused paged-prefill chunk attention over the block pools.

``paged_prefill_chunk`` is the serving entry point
(nn/attention.py:Attention.decode_chunk with ``attn_impl="fused"``):
model-layout q/k_chunk/v_chunk in, per-chunk-token attention context plus
in-place-updated pools out.  On CPU the kernel runs in interpret mode
(correctness path; the chunk-gather fallback is what "auto" serving selects
there).  Inference only — no VJP.

``fetched_blocks`` is the kernel's fetch rule on the host: which table
entries of each row the kernel copies in per KV head per layer (its resident
real blocks, scored, and the real blocks its chunk is spliced into).  The
engine's ``StepWork.kv_blocks_scored`` counts them; ``prefill_kv_bytes``, the
per-chunk-step KV-traffic model of benchmarks/speed_memory.py, prices them.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np

from repro.kernels.paged_prefill.kernel import (paged_prefill_chunk_kernel,
                                                resident_blocks)
from repro.serving.paged import TRASH_BLOCK


def _interpret_default() -> bool:
    # the kernel uses pltpu-only machinery (PrefetchScalarGridSpec, VMEM
    # scratch, DMA semaphores): any non-TPU backend must take the
    # interpreter, not a doomed native lowering
    return jax.default_backend() != "tpu"


def paged_prefill_chunk(q: jax.Array, k_chunk: jax.Array, v_chunk: jax.Array,
                        k_pool: jax.Array, v_pool: jax.Array,
                        block_tables: jax.Array, start: jax.Array,
                        lens: jax.Array, softcap: float = 0.0,
                        interpret: Optional[bool] = None,
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q [B, T, Hq, Dh] (RoPE'd); k_chunk/v_chunk [B, T, Hkv, Dh] (the
    chunk's projected KV); pools [N, Hkv, bs, Dh]; block_tables int32 [B, L];
    start/lens int32 [B].

    Chunk token ``j`` of row ``b`` is written at position ``start[b] + j``
    (valid iff ``j < lens[b]``) and attends stored positions
    ``<= start[b] + j``.  Returns (ctx [B, T, Hq, Dh] in pool dtype,
    k_pool', v_pool'); the chunk KV is scattered into each row's blocks in
    place (pass donated pools)."""
    itp = _interpret_default() if interpret is None else interpret
    b, t, hq, dh = q.shape
    hkv = k_pool.shape[1]
    g = hq // hkv
    # query row r = j*g + gi for chunk position j, grouped head gi
    qg = q.reshape(b, t, hkv, g, dh).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, hkv, t * g, dh)
    kc = k_chunk.transpose(0, 2, 1, 3)              # [B, Hkv, T, Dh]
    vc = v_chunk.transpose(0, 2, 1, 3)
    scale = float(1.0 / (dh ** 0.5))
    out, k_pool, v_pool = paged_prefill_chunk_kernel(
        qg, kc, vc, k_pool, v_pool, block_tables, start, lens,
        scale=scale, softcap=float(softcap), interpret=itp)
    out = out.reshape(b, hkv, t, g, dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, t, hq, dh), k_pool, v_pool


def fetched_blocks(start, lens, table, block_size: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """The kernel's fetch rule, on the host: bool ``[B, L]`` masks of the
    table entries each row copies in per KV head per layer — ``resident``,
    the leading real blocks that hold positions ``< start`` (scored), and
    ``splice``, the real blocks among ``c0 .. c1`` that the chunk
    ``[start, start + lens)`` lands in (read, spliced, written back).  An
    idle row (table all trash) fetches nothing; a block can be in both (a
    partly filled last block is scored and spliced).  ``start``/``lens``
    [B] and ``table`` [B, L] are host arrays."""
    start = np.asarray(start, np.int64)
    lens = np.asarray(lens, np.int64)
    table = np.asarray(table)
    width = table.shape[1]
    col = np.arange(width)[None]
    resident = col < resident_blocks(start, table, block_size, np)[:, None]
    c0 = np.minimum(start // block_size, width - 1)[:, None]
    c1 = np.minimum((start + lens - 1) // block_size, width - 1)[:, None]
    splice = (col >= c0) & (col <= c1) & (table != TRASH_BLOCK)
    return resident, splice


def prefill_kv_bytes(starts: Sequence[int], lens: Sequence[int],
                     chunked: Sequence[int], table_width: int,
                     block_size: int, n_kv_heads: int, head_dim: int,
                     n_layers: int, itemsize: int, fused: bool) -> int:
    """KV bytes read by one chunked-prefill step over the slot batch.

    ``starts``/``lens`` are the per-slot chunk start positions and valid
    lengths, ``chunked`` the slot indices that ran a chunk (prefilling or
    decoding — both attend; their tables hold real blocks through the
    chunk), ``table_width`` the bucketed block-table width the engine passed
    down.  Gather: every slot pays the dense window.  Fused: the blocks
    :func:`fetched_blocks` names — each chunked row's resident blocks plus
    the blocks its chunk is spliced into; idle rows (table all trash) read
    nothing; the chunk's own KV is scored from VMEM and never re-read."""
    per_token = 2 * n_kv_heads * head_dim * itemsize * n_layers   # K and V
    n_slots = len(starts)
    if not fused:
        return n_slots * table_width * block_size * per_token
    table = np.full((n_slots, table_width), TRASH_BLOCK, np.int32)
    table[list(chunked)] = TRASH_BLOCK + 1              # any real block
    blocks = sum(int(m.sum()) for m in fetched_blocks(starts, lens, table,
                                                       block_size))
    return blocks * block_size * per_token
