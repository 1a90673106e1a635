"""Flash-style MiniLM relation-KL kernel (Algorithm 1, Eq. 11-12).

Computes, per relation row i:
    KL_i = sum_j P_t(i,j) * (log P_t(i,j) - log P_s(i,j))
where P_t = softmax_j(t_i·t_j / temp) and P_s = softmax_j(s_i·s_j / temp),
WITHOUT materializing the L×L relation matrices.  Streaming over j-blocks
with online (rescaled) accumulators:

    m_t, z_t   — running max / sum of exp for the teacher row
    m_s, z_s   — same for the student row
    u          — running sum of exp(t_rel - m_t) * (t_rel - s_rel)

then  KL_i = u/z_t - (m_t + log z_t) + (m_s + log z_s).

(The identity: sum_j p_j (t_j - s_j) - logZt + logZs with p the teacher
softmax; u accumulates the unnormalized first term.)

Inputs are the already L2-normalized, head-resplit states [BH, L, D]
(ops.py does that cheap prep).  HBM traffic: O(BH·L·D) instead of
O(BH·L²) — at L = 4096, split_heads·B = 32, that is ~0.5 GB of relation
matrices per relation per model that never exist.

Grid (BH, L/bl, L/bj); j innermost; accumulators live in VMEM scratch and
the per-row KL is written on the last j step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BL = 256
DEFAULT_BJ = 256
NEG = -1e30


def _kernel(s_i_ref, t_i_ref, s_j_ref, t_j_ref, o_ref,
            mt_ref, zt_ref, ms_ref, zs_ref, u_ref,
            *, n_j: int, temp: float, l: int):
    j_idx = pl.program_id(2)

    @pl.when(j_idx == 0)
    def _init():
        mt_ref[...] = jnp.full_like(mt_ref, NEG)
        ms_ref[...] = jnp.full_like(ms_ref, NEG)
        zt_ref[...] = jnp.zeros_like(zt_ref)
        zs_ref[...] = jnp.zeros_like(zs_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    s_i = s_i_ref[0].astype(jnp.float32)          # [bl, D]
    t_i = t_i_ref[0].astype(jnp.float32)
    s_j = s_j_ref[0].astype(jnp.float32)          # [bj, D]
    t_j = t_j_ref[0].astype(jnp.float32)
    bj = s_j.shape[0]

    t_rel = jax.lax.dot_general(t_i, t_j, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) / temp
    s_rel = jax.lax.dot_general(s_i, s_j, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) / temp

    # mask padded j columns (L not divisible by bj): exp(NEG - m) == 0
    col_ok = (j_idx * bj + jax.lax.broadcasted_iota(jnp.int32, (1, bj), 1)) < l
    t_rel = jnp.where(col_ok, t_rel, NEG)
    s_rel = jnp.where(col_ok, s_rel, NEG)

    # online rescale of the three accumulators
    mt_old, ms_old = mt_ref[...], ms_ref[...]              # [bl, 1]
    mt_new = jnp.maximum(mt_old, jnp.max(t_rel, axis=-1, keepdims=True))
    ms_new = jnp.maximum(ms_old, jnp.max(s_rel, axis=-1, keepdims=True))
    ct = jnp.exp(mt_old - mt_new)
    cs = jnp.exp(ms_old - ms_new)

    pt = jnp.exp(t_rel - mt_new)                           # [bl, bj]
    zt_ref[...] = zt_ref[...] * ct + jnp.sum(pt, axis=-1, keepdims=True)
    zs_ref[...] = zs_ref[...] * cs + jnp.sum(jnp.exp(s_rel - ms_new),
                                             axis=-1, keepdims=True)
    u_ref[...] = u_ref[...] * ct + jnp.sum(pt * (t_rel - s_rel),
                                           axis=-1, keepdims=True)
    mt_ref[...] = mt_new
    ms_ref[...] = ms_new

    @pl.when(j_idx == n_j - 1)
    def _finish():
        zt = jnp.maximum(zt_ref[...], 1e-30)
        zs = jnp.maximum(zs_ref[...], 1e-30)
        kl = (u_ref[...] / zt
              - (mt_ref[...] + jnp.log(zt))
              + (ms_ref[...] + jnp.log(zs)))
        o_ref[0, 0] = kl[:, 0].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bl", "bj", "temp", "interpret"))
def relation_kl_rows_kernel(s: jax.Array, t: jax.Array, temp: float = 1.0,
                            bl: int = DEFAULT_BL, bj: int = DEFAULT_BJ,
                            interpret: bool = False) -> jax.Array:
    """s, t: [BH, L, D] L2-normalized relation vectors -> KL rows [BH, L]."""
    bh, l, d = s.shape
    bl, bj = min(bl, l), min(bj, l)
    grid = (bh, pl.cdiv(l, bl), pl.cdiv(l, bj))
    return pl.pallas_call(
        functools.partial(_kernel, n_j=grid[2], temp=temp, l=l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bl, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bl, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bj, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bj, d), lambda b, i, j: (b, j, 0)),
        ],
        # rows come out as [BH, 1, L]: a (1, bl) block over [BH, L] would
        # put a 1 in the sublane dim out of BH, which the TPU tiling refuses
        out_specs=pl.BlockSpec((1, 1, bl), lambda b, i, j: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, l), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bl, 1), jnp.float32) for _ in range(5)],
        interpret=interpret,
    )(s, t, s, t)[:, 0]
