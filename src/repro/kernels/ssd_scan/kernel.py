"""Mamba2 SSD chunked-scan kernel (used by mamba2 / jamba archs).

One (batch·head, chunk) grid cell computes a full SSD chunk:

  la        = cumsum(log a)                       (VPU, fp32)
  y_intra   = ((C Bᵀ) ⊙ decay ⊙ dt) x            (two MXU matmuls)
  y_off     = (C h_prev) ⊙ exp(la)               (MXU)
  h_next    = h_prev·exp(la_last) + Bᵀ(dt·exp(la_last-la) ⊙ x)

The recurrent state h [P, N] lives in a VMEM scratch that persists across the
sequential chunk dimension of the grid (TPU grids iterate in order), so the
inter-chunk recurrence costs no HBM round-trips.  B/C are pre-broadcast per
head by ops.py (n_groups=1 in all our configs; the N=128 copies are small
next to x).

Grid (BH, S/Q); Q = chunk length (128/256 keeps every matmul MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 256


def _kernel(x_ref, a_ref, dt_ref, b_ref, c_ref, y_ref, hout_ref, h_ref,
            *, n_chunks: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)        # [Q, P]
    a = a_ref[0, 0].astype(jnp.float32)     # [Q]
    dt = dt_ref[0, 0].astype(jnp.float32)   # [Q]
    B = b_ref[0].astype(jnp.float32)        # [Q, N]
    C = c_ref[0].astype(jnp.float32)        # [Q, N]
    q = x.shape[0]

    la = jnp.cumsum(jnp.log(jnp.maximum(a, 1e-20)))            # [Q]
    seg = la[:, None] - la[None, :]                            # [Q, Q]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    decay = jnp.where(causal, jnp.exp(seg), 0.0)

    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    m = cb * decay * dt[None, :]
    y = jax.lax.dot(m, x, preferred_element_type=jnp.float32)     # intra

    h = h_ref[...]                                                # [N, P]
    y = y + jnp.exp(la)[:, None] * jax.lax.dot(
        C, h, preferred_element_type=jnp.float32)                 # off-diag

    la_last = la[q - 1]
    wk = dt * jnp.exp(la_last - la)                               # [Q]
    h_ref[...] = h * jnp.exp(la_last) + jax.lax.dot_general(
        B, wk[:, None] * x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                       # [N, P]

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == n_chunks - 1)
    def _finish():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_kernel(x: jax.Array, a: jax.Array, dt: jax.Array, B: jax.Array,
                    C: jax.Array, chunk: int = DEFAULT_CHUNK,
                    interpret: bool = False):
    """x [BH, S, P]; a/dt [BH, S]; B/C [BH, S, N] -> (y [BH, S, P], h [BH, N, P])."""
    bh, s, p = x.shape
    n = B.shape[-1]
    q = min(chunk, s)
    while s % q:
        q -= 1
    # the loop above shrank q until it divides s exactly, so // drops nothing
    grid = (bh, s // q)  # lint: allow(pallas-grid-div)
    # a/dt ride as [BH, 1, S]: a (1, q) block over [BH, S] would put a 1 in
    # the sublane dim out of BH, which the TPU tiling refuses
    return pl.pallas_call(
        functools.partial(_kernel, n_chunks=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, q), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, 1, q), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, q, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, q, n), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, n, p), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(x, a[:, None], dt[:, None], B, C)
