"""Public wrapper: fused paged-attention decode over the serving block pools.

``paged_attention_decode`` is the serving entry point
(nn/attention.py:Attention.decode with ``attn_impl="fused"``): model-layout
q/k_new/v_new in, attention context plus in-place-updated pools out.  On CPU
the kernel runs in interpret mode (correctness path; the gather fallback is
what "auto" serving selects there).  Inference only — no VJP.

``decode_kv_bytes`` is the shared per-step KV-traffic model used by
benchmarks/speed_memory.py and launch/roofline.py: the fused kernel reads
``O(tokens resident)`` (one pass over each active row's resident blocks,
plus one trash block per idle row), the gather fallback reads the dense
``B * table_width * block_size`` window.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_attention_decode_kernel


def _interpret_default() -> bool:
    # the kernel uses pltpu-only machinery (PrefetchScalarGridSpec, VMEM
    # scratch): any non-TPU backend must take the interpreter, not a
    # doomed native lowering
    return jax.default_backend() != "tpu"


def paged_attention_decode(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                           k_pool: jax.Array, v_pool: jax.Array,
                           block_tables: jax.Array, idx: jax.Array,
                           softcap: float = 0.0,
                           interpret: Optional[bool] = None,
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q [B, Hq, Dh] (RoPE'd); k_new/v_new [B, Hkv, Dh] (the step's KV);
    pools [N, Hkv, bs, Dh]; block_tables int32 [B, L]; idx int32 [B].

    Returns (ctx [B, Hq, Dh] in pool dtype, k_pool', v_pool'); the new K/V
    is scattered into each row's current block in place (pass donated
    pools)."""
    itp = _interpret_default() if interpret is None else interpret
    b, hq, dh = q.shape
    hkv = k_pool.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, dh)
    scale = float(1.0 / (dh ** 0.5))
    out, k_pool, v_pool = paged_attention_decode_kernel(
        qg, k_new[:, :, None], v_new[:, :, None], k_pool, v_pool,
        block_tables, idx,
        scale=scale, softcap=float(softcap), interpret=itp)
    return out.reshape(b, hq, dh), k_pool, v_pool


def decode_kv_bytes(positions: Sequence[int], active: Sequence[int],
                    table_width: int, block_size: int, n_kv_heads: int,
                    head_dim: int, n_layers: int, itemsize: int,
                    fused: bool) -> int:
    """KV bytes read by one decode step over the slot batch.

    ``positions`` are the per-slot write positions, ``active`` the occupied
    slot indices, ``table_width`` the bucketed block-table width the engine
    passed down.  Gather: every row pays the dense window.  Fused: each
    active row streams its resident blocks once; idle rows re-read a single
    trash block (consecutive same-block fetches are skipped)."""
    per_token = 2 * n_kv_heads * head_dim * itemsize * n_layers   # K and V
    n_slots = len(positions)
    if not fused:
        return n_slots * table_width * block_size * per_token
    blocks = 0
    active = set(active)
    for s in range(n_slots):
        if s in active:
            blocks += min(int(positions[s]) // block_size,
                          table_width - 1) + 1
        else:
            blocks += 1                       # trash block, fetched once
    return blocks * block_size * per_token
