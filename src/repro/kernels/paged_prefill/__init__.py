"""Fused paged-prefill chunk attention: T prompt tokens per step against the
block pools.

Each (row, kv-head) fetches only its row's resident real blocks — the
leading non-trash table entries holding positions before the chunk — and the
blocks the chunk lands in, and folds them into an online-softmax (m, z, acc)
carry in VMEM scratch; per-row chunk starts/lengths, the block table and the
resident block counts arrive as scalar-prefetch operands.  Where the head
size fills whole 128-lane rows the grid is (B, Hkv) and resident blocks come
in tiles of up to 128 positions by double-buffered manual DMA; otherwise the
grid walks the table one block per step through BlockSpec index maps.
Trie-shared prefix blocks are read in place; the chunk's own K/V never
round-trips through HBM: its causal T x T scores fold into the carry from
VMEM and it is spliced into the row's pool blocks through aliased pool
outputs.  Idle rows (all-trash tables) fetch nothing, so KV bytes read and
scores computed per chunk step follow the live KV, not
B * table_width * block_size.  See kernel.py for the full scheme.
"""
from repro.kernels.paged_prefill import kernel, ops, ref  # noqa: F401
