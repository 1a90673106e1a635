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
"""
from __future__ import annotations

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


def ternary(key, shape) -> jax.Array:
    """int8 values in {-1, 0, 1}."""
    return jax.random.randint(key, shape, -1, 2, jnp.int8)


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
    k = jax.random.randint(key, shape, -half_range, half_range + 1, jnp.int32)
    return (k.astype(jnp.float32) * step).astype(jnp.bfloat16)


# -- the program's layout ------------------------------------------------------

def _path(p) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in p)


def _packed(pack: Callable, t: jax.Array) -> jax.Array:
    """``pack`` of every ``[K, N]`` matrix of ``t [..., K, N]``."""
    for _ in range(t.ndim - 2):
        pack = jax.vmap(pack)
    return pack(t)


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
    one kind at a time (``family.layer_kinds``), each kind by one compiled
    generator.  A leaf the family does not name is an error, so a change of
    the program's layout cannot leave a tensor at anything but the seed's
    values unnoticed."""
    kinds = family.layer_kinds(arch)
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    where = []
    for p, _ in leaves:
        got = family.program_leaf(_path(p), arch)
        if got is None:
            raise KeyError(f"no seeded tensor for program leaf {_path(p)}")
        where.append(got)

    def make(key):
        drawn = {}
        for kind in dict.fromkeys(kinds):
            layers = [i for i, k in enumerate(kinds) if k == kind]
            drawn[kind] = (layers, jax.lax.map(
                lambda l, kind=kind: family.layer_tensors(key, l, arch, form,
                                                          kind),
                jnp.asarray(layers, jnp.int32)))
        top = family.top_tensors(key, arch, form)

        def tensor(path, layers, name):
            if layers is None:
                group = top
            else:
                want = [layers] if isinstance(layers, int) else list(layers)
                if len({kinds[i] for i in want}) != 1:
                    raise KeyError(f"{path}: layers {want} are of different "
                                   "kinds")
                have, group = drawn[kinds[want[0]]]
                if want != have:
                    rows = jnp.asarray([have.index(i) for i in want])
                    group = {n: t[rows] for n, t in group.items()}
                if isinstance(layers, int):
                    group = {n: t[0] for n, t in group.items()}
            if name not in group:
                raise KeyError(f"no seeded tensor {name!r} for program leaf "
                               f"{path}")
            return group[name]

        out = []
        for (p, sds), (layers, name) in zip(leaves, where):
            path = _path(p)
            val = tensor(path, layers, name)
            leaf = path.rsplit("/", 1)[-1]
            if leaf == "w_packed":
                val = _packed(pack, val)
            elif leaf == "delta":
                val = jnp.full(sds.shape, ternary_delta(val.shape[-2]),
                               jnp.float32)
            if tuple(val.shape) != tuple(sds.shape):
                raise ValueError(f"{path}: seeded shape {val.shape}, program "
                                 f"shape {sds.shape}")
            out.append(val.astype(sds.dtype))
        return out

    flat = jax.jit(make)(base_key(seed))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shapes),
                                        flat)
