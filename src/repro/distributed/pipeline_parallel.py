"""GPipe-style pipeline parallelism over shard_map + collective_permute.

The stack's repeats are split into `n_stages` contiguous groups; stage s owns
the stacked params slice [s].  Microbatches flow through a skewed schedule of
T = n_micro + n_stages - 1 ticks; at each tick every stage runs its group on
the activation it holds, then `ppermute`s it to the next stage.  Bubble
fraction = (S-1)/(T) as usual for GPipe; activations for the backward are
saved per-tick by jax.checkpoint exactly as in the non-PP stack.

This module is deliberately model-agnostic: `stage_fn(stage_params, x,
stage_id)` is any per-stage function.  launch/train.py wires it to the Stack;
tests validate PP-vs-dense equivalence on a toy MLP over 4 host devices.

The production dry-run mesh fixes axes (pod, data, model) per the assignment,
so PP here is an optional alternative factorization (e.g. reuse `pod` as the
stage axis for cross-DCN pipelining, where its point-to-point ppermute
traffic pattern is DCN-friendly — one transfer per tick vs all-reduce).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_forward(stage_fn: Callable, mesh: Mesh, axis_name: str,
                  n_stages: int):
    """Returns f(stage_params, microbatches) -> outputs.

    stage_params: pytree with leading stage dim (sharded over axis_name).
    microbatches: [n_micro, mb, ...] (replicated; every stage sees the
    stream but only stage 0 consumes it).
    """

    def run(params, xs):
        sid = jax.lax.axis_index(axis_name)
        # P(axis_name)-sharded stage params arrive with a local leading dim
        # of size 1 — drop it so stage_fn sees its own slice.
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        n_micro = xs.shape[0]
        ticks = n_micro + n_stages - 1
        mb_shape = xs.shape[1:]
        hold = jnp.zeros(mb_shape, xs.dtype)          # activation in flight
        outs = jnp.zeros((n_micro,) + mb_shape, xs.dtype)

        def tick(carry, t):
            hold, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            fresh = xs[jnp.clip(t, 0, n_micro - 1)]
            x_in = jnp.where(sid == 0, fresh, hold)
            active = (t - sid >= 0) & (t - sid < n_micro)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, hold)
            # last stage banks its finished microbatch
            mb_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            done = (sid == n_stages - 1) & (t >= n_stages - 1)
            outs = jax.lax.cond(
                done,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, y[None], mb_idx, axis=0),
                lambda o: o, outs)
            # rotate activations stage s -> s+1
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt = jax.lax.ppermute(y, axis_name, perm)
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (hold, outs), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis_name)
        return outs

    params_spec = P(axis_name)
    return jax.shard_map(run, mesh=mesh,
                         in_specs=(params_spec, P()),
                         out_specs=P(), check_vma=False)


def pipeline_stage_from_stack(stack, reps_per_stage: int):
    """Adapter: one pipeline stage = `reps_per_stage` repeats of a Stack."""

    def stage_fn(stage_params, x):
        def body(h, rep_params):
            for i, blk in enumerate(stack.blocks()):
                h, _, _ = blk.apply(rep_params[f"pos{i}"], h)
            return h, None
        h, _ = jax.lax.scan(body, x, stage_params)
        return h

    return stage_fn
