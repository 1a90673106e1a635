"""Production meshes.

Functions, not module-level constants, so importing never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).

Topology (TPU v5e): 16x16 = 256 chips per pod; the multi-pod mesh adds a
leading DCN-connected "pod" axis (2 pods = 512 chips).  "data" carries
DP/FSDP traffic, "model" carries TP collectives (densest ICI axis).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # jax.make_mesh defaults to Explicit axes, under which the sharding
    # rules' with_sharding_constraint and the embedding gather raise; the
    # plans in distributed/sharding.py are written for Auto axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(dp: int, tp: int, pods: int = 1):
    """Elastic variant: any (pods, dp, tp) factorization of the live devices."""
    n = jax.device_count()
    want = pods * dp * tp
    if want > n:
        raise ValueError(f"mesh {pods}x{dp}x{tp}={want} exceeds {n} devices")
    if pods > 1:
        return _auto_mesh((pods, dp, tp), ("pod", "data", "model"))
    return _auto_mesh((dp, tp), ("data", "model"))


def largest_feasible_mesh(tp: int = 16, pods: int = 1):
    """Elastic downscale: keep TP fixed (model must fit), shrink DP to the
    largest value the surviving device count supports."""
    n = jax.device_count()
    dp = max(1, n // (tp * pods))
    return make_mesh(dp, tp, pods)
