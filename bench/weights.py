"""Weights from the seed, for the served model and for the reference alike.

Every tensor is drawn from a key derived from ``(seed, layer, name)`` alone,
so the served model (all layers at once, in one jitted call, in the layout
and dtype the program serves) and the plain reference (one layer at a time,
in float32) draw the same numbers without the reference ever reading what
the program holds.

Names are the architecture's, not the program's: the configuration's
architecture module (``bench/arch/<family>.py``) draws each layer's tensors
and the ones outside the layers with the helpers here, and maps the
program's leaves to its names.

Linear weights are ``[..., in, out]``.  In the ``bf16`` form a weight is
``k * 2**-e`` for ``k`` uniform over -127..127 and ``e`` the power of two
that brings its spread nearest ``1 / sqrt(in)``, exact in bfloat16.  In the
``packed`` form it is
ternary, ``t * delta`` with ``t`` uniform over {-1, 0, 1} and
``delta = sqrt(1.5 / in)`` (the same variance), handed to the program as its
2-bit packing of ``t`` and ``delta`` (one per matrix, so one per expert
of an expert tensor ``[experts, in, out]``).  The embedding is drawn
the same way with a spread near 0.02, norm scales are ``1 + k / 64`` and
biases ``k / 2048`` for small random integers ``k``.  Every value is exact
in bfloat16 and made without rounding, so no two programs that draw it can
part, and a norm or bias that the program drops shows in the comparison.

A tensor of rank 1 or 2 is drawn whole from its key.  One of higher rank,
such as an expert tensor ``[experts, in, out]``, is drawn one ``[in, out]``
matrix at a time: matrix ``i``, counted row-major over the leading axes,
from ``jax.random.fold_in(key, i)``, so that no draw is larger than one
matrix.  A new family draws every tensor with these helpers and gets the
rule with them.  The program's parameters are drawn one layer at a time
and each tensor it serves packed is packed matrix by matrix as soon as it
is drawn, so seeding holds the packed parameters and one layer's unpacked
tensors, never an unpacked stack of layers.
"""
from __future__ import annotations

import math
import zlib
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """Raw threefry key of a whole-number seed of up to 64 bits."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"--seed {seed} is not a whole number below 2**64")
    return jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def tensor_key(key: jax.Array, layer, name: str) -> jax.Array:
    """Key of one tensor; ``layer`` -1 for the tensors outside the stack.
    ``layer`` may be traced, so one compiled layer generator serves all."""
    key = jax.random.fold_in(key, jnp.asarray(layer, jnp.int32) + 1)
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _per_matrix(draw: Callable, key, shape) -> jax.Array:
    """``draw(key, shape)`` for a rank of 2 or less; above, ``[..., K, N]``
    one matrix at a time, matrix ``i`` (row-major over the leading axes)
    ``draw(fold_in(key, i), (K, N))``."""
    shape = tuple(shape)
    if len(shape) <= 2:
        return draw(key, shape)
    mats = jax.lax.map(
        lambda i: draw(jax.random.fold_in(key, i), shape[-2:]),
        jnp.arange(math.prod(shape[:-2]), dtype=jnp.int32))
    return mats.reshape(shape)


def ternary(key, shape) -> jax.Array:
    """int8 values in {-1, 0, 1}."""
    return _per_matrix(lambda k, s: jax.random.randint(k, s, -1, 2, jnp.int8),
                       key, shape)


def ternary_delta(fan_in: int) -> float:
    return float(np.sqrt(1.5 / fan_in))


def spread_bf16(key, shape, std: float) -> jax.Array:
    """Uniform ``k * 2**-e``, ``|k| <= 127``, with a spread near ``std``:
    only integer draws and a power-of-two scale, so every program that
    draws it gets the same bits."""
    e = round(float(np.log2(np.sqrt((127 * 128) / 3) / std)))
    return steps_bf16(key, shape, 127, 2.0 ** -e)


def steps_bf16(key, shape, half_range: int, step: float) -> jax.Array:
    """``k * step`` for integers ``|k| <= half_range``, exact in bfloat16."""
    def draw(k, s):
        k = jax.random.randint(k, s, -half_range, half_range + 1, jnp.int32)
        return (k.astype(jnp.float32) * step).astype(jnp.bfloat16)
    return _per_matrix(draw, key, shape)


# -- the program's layout ------------------------------------------------------

def _path(p) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in p)


def _packed(pack: Callable, t: jax.Array) -> jax.Array:
    """``pack`` of every ``[K, N]`` matrix of ``t [..., K, N]``, one matrix
    at a time."""
    if t.ndim <= 2:
        return pack(t)
    mats = jax.lax.map(pack, t.reshape((-1,) + t.shape[-2:]))
    return mats.reshape(t.shape[:-2] + mats.shape[1:])


def generator(family, arch: Dict, form: str, shapes,
              pack: Callable[[jax.Array], jax.Array]):
    """The function of a raw key (``base_key``) that ``program_params``
    jits: it returns the program's leaves in tree order.  Lowering it
    without a call shows what seeding holds on the device."""
    kinds = family.layer_kinds(arch)
    leaves = []     # (path, shape, layers, kind, layer list, tensor name)
    packed = {}     # the tensors served packed, per kind (None: no layer)
    for p, sds in jax.tree_util.tree_leaves_with_path(shapes):
        path = _path(p)
        got = family.program_leaf(path, arch)
        if got is None:
            raise KeyError(f"no seeded tensor for program leaf {path}")
        layers, name = got
        kind = want = None
        if layers is not None:
            want = (layers,) if isinstance(layers, int) else tuple(layers)
            if len({kinds[i] for i in want}) != 1:
                raise KeyError(f"{path}: layers {list(want)} are of "
                               "different kinds")
            kind = kinds[want[0]]
        leaves.append((path, sds, layers, kind, want, name))
        if path.rsplit("/", 1)[-1] == "w_packed":
            packed.setdefault(kind, set()).add(name)

    def layer(key, l, kind):
        return family.layer_tensors(key, l, arch, form, kind)

    def top(key):
        return family.top_tensors(key, arch, form)

    def served(kind, tensors):
        return {n: _packed(pack, t) if n in packed.get(kind, ()) else t
                for n, t in tensors.items()}

    # Each tensor's shape as drawn, whose fan-in sets its ternary scale.
    raw = jax.ShapeDtypeStruct((2,), jnp.uint32)
    drawn_shapes = {None: jax.eval_shape(top, raw)}
    for kind in set(kinds):
        drawn_shapes[kind] = jax.eval_shape(
            lambda k, kind=kind: layer(k, jnp.int32(0), kind), raw)

    def make(key):
        groups = {None: served(None, top(key))}  # by the layers leaves stack
        out = []
        for path, sds, layers, kind, want, name in leaves:
            if want not in groups:
                groups[want] = jax.lax.map(
                    lambda l, kind=kind: served(kind, layer(key, l, kind)),
                    jnp.asarray(want, jnp.int32))
            if name not in groups[want]:
                raise KeyError(f"no seeded tensor {name!r} for program leaf "
                               f"{path}")
            val = groups[want][name]
            if isinstance(layers, int):
                val = val[0]
            if path.rsplit("/", 1)[-1] == "delta":
                fan_in = drawn_shapes[kind][name].shape[-2]
                val = jnp.full(sds.shape, ternary_delta(fan_in), jnp.float32)
            if tuple(val.shape) != tuple(sds.shape):
                raise ValueError(f"{path}: seeded shape {val.shape}, program "
                                 f"shape {sds.shape}")
            out.append(val.astype(sds.dtype))
        return out

    return make


def program_params(seed: int, family, arch: Dict, form: str, shapes,
                   pack: Callable[[jax.Array], jax.Array]):
    """The program's parameter tree, made on the device in one jitted call.

    ``family`` is the configuration's architecture module; ``shapes`` the
    program's own tree of ``ShapeDtypeStruct`` (from ``jax.eval_shape`` of
    its init); ``pack`` the program's 2-bit packing of an int8 ternary
    ``[K, N]`` matrix.  ``family.program_leaf`` names each leaf's tensor and
    the layers stacked on its leading axis (None: outside the layers; an
    int: one layer, no layer axis), wherever the program keeps it.  A leaf
    ``w_packed`` is the packing of its tensor, ``delta`` its ternary scale
    (one per matrix), any other leaf the tensor itself.  Layers are drawn
    one at a time by one compiled layer generator for each list of layers
    that leaves stack (one per kind, ``family.layer_kinds``, where the
    program stacks a kind's layers together), and a tensor served packed is
    packed in that generator, matrix by matrix.  A leaf the family does not
    name is an error, so a change of the program's layout cannot leave a
    tensor at anything but the seed's values unnoticed."""
    flat = jax.jit(generator(family, arch, form, shapes, pack))(
        base_key(seed))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shapes),
                                        flat)
