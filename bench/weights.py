"""Weights from the seed, for the served model and for the reference alike.

Every tensor is drawn from a key derived from ``(seed, layer, name)`` alone,
so the served model (all layers at once, in one jitted call, in the layout
and dtype the program serves) and the plain reference (one layer at a time,
in float32) draw the same numbers without the reference ever reading what
the program holds.

Names are the architecture's, not the program's:

    embed, final_norm                      (no layer)
    norm1, norm2, wq, bq, wk, bk, wv, bv, wo, attn_subln,
    up, gate, down, mlp_subln              (per layer)

Linear weights are ``[in, out]``.  In the ``bf16`` form a weight is
``k * 2**-e`` for ``k`` uniform over -127..127 and ``e`` the power of two
that brings its spread nearest ``1 / sqrt(in)``, exact in bfloat16.  In the
``packed`` form it is
ternary, ``t * delta`` with ``t`` uniform over {-1, 0, 1} and
``delta = sqrt(1.5 / in)`` (the same variance), handed to the program as its
2-bit packing of ``t`` and the scalar ``delta``.  The embedding is drawn
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

LINEARS = ("wq", "wk", "wv", "wo", "up", "gate", "down")
BIASES = {"bq": "wq", "bk": "wk", "bv": "wv"}
NORMS = ("norm1", "norm2", "attn_subln", "mlp_subln")


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


def linear_shape(arch: Dict, name: str):
    d, f = arch["hidden_size"], arch["intermediate_size"]
    q = arch["num_attention_heads"] * arch["head_dim"]
    kv = arch["num_key_value_heads"] * arch["head_dim"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "up": (d, f), "gate": (d, f), "down": (f, d)}[name]


def norm_dim(arch: Dict, name: str) -> int:
    return {"norm1": arch["hidden_size"], "norm2": arch["hidden_size"],
            "attn_subln": arch["num_attention_heads"] * arch["head_dim"],
            "mlp_subln": arch["intermediate_size"]}[name]


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


def layer_tensors(key, layer, arch: Dict, form: str) -> Dict[str, jax.Array]:
    """One layer's tensors as drawn: linear weights as int8 ternary (packed)
    or bfloat16 (bf16), norm scales and biases in bfloat16."""
    out = {}
    for name in LINEARS:
        shape = linear_shape(arch, name)
        k = tensor_key(key, layer, name)
        out[name] = (ternary(k, shape) if form == "packed"
                     else spread_bf16(k, shape, shape[0] ** -0.5))
    if arch["attention_bias"]:
        for name, lin in BIASES.items():
            out[name] = steps_bf16(tensor_key(key, layer, name),
                                   (linear_shape(arch, lin)[1],), 48, 1 / 2048)
    norms = NORMS if form == "packed" else NORMS[:2]    # SubLN: student only
    for name in norms:
        out[name] = 1 + steps_bf16(tensor_key(key, layer, name),
                                   (norm_dim(arch, name),), 8, 1 / 64)
    return out


def top_tensors(key, arch: Dict) -> Dict[str, jax.Array]:
    d, v = arch["hidden_size"], arch["vocab_size"]
    return {"embed": spread_bf16(tensor_key(key, -1, "embed"), (v, d), 0.02),
            "final_norm": 1 + steps_bf16(tensor_key(key, -1, "final_norm"),
                                         (d,), 8, 1 / 64)}


# -- the program's layout ------------------------------------------------------

# program path (below stack/pos0) -> architecture name, per leaf kind
_PROGRAM_NAMES = {
    "attn/wq": "wq", "attn/wk": "wk", "attn/wv": "wv", "attn/wo": "wo",
    "mlp/up": "up", "mlp/gate": "gate", "mlp/down": "down",
    "attn/subln": "attn_subln", "mlp/subln": "mlp_subln",
    "norm1": "norm1", "norm2": "norm2",
}


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in p)


def program_params(seed: int, arch: Dict, form: str, shapes,
                   pack: Callable[[jax.Array], jax.Array]):
    """The program's parameter tree, made on the device in one jitted call.

    ``shapes`` is the program's own tree of ``ShapeDtypeStruct`` (from
    ``jax.eval_shape`` of its init); ``pack`` is the program's 2-bit packing
    of an int8 ternary ``[K, N]`` matrix.  Each leaf is filled by its
    architecture name; a leaf of an unknown name is an error, so a change of
    the program's layout cannot leave a tensor at anything but the seed's
    values unnoticed."""
    n_layers = arch["num_hidden_layers"]
    leaves = jax.tree_util.tree_leaves_with_path(shapes)

    def make(key):
        per_layer = jax.lax.map(
            lambda l: layer_tensors(key, l, arch, form),
            jnp.arange(n_layers, dtype=jnp.int32))
        top = top_tensors(key, arch)
        out = []
        for p, sds in leaves:
            path = _path(p)
            if path == "embed/table":
                val = top["embed"]
            elif path == "final_norm/scale":
                val = top["final_norm"]
            elif path.startswith("stack/pos0/"):
                mod, leaf = path[len("stack/pos0/"):].rsplit("/", 1)
                name = _PROGRAM_NAMES.get(mod)
                if name is None:
                    raise KeyError(f"no seeded tensor for program leaf {path}")
                if leaf == "w":
                    val = per_layer[name]
                elif leaf == "w_packed":
                    val = jax.vmap(pack)(per_layer[name])
                elif leaf == "delta":
                    val = jnp.full(sds.shape, ternary_delta(
                        linear_shape(arch, name)[0]), jnp.float32)
                elif leaf == "b":
                    val = per_layer["b" + name[1]]
                elif leaf == "scale":
                    val = per_layer[name]
                else:
                    raise KeyError(f"no seeded tensor for program leaf {path}")
            else:
                raise KeyError(f"no seeded tensor for program leaf {path}")
            if tuple(val.shape) != tuple(sds.shape):
                raise ValueError(f"{path}: seeded shape {val.shape}, program "
                                 f"shape {sds.shape}")
            out.append(val.astype(sds.dtype))
        return out

    flat = jax.jit(make)(base_key(seed))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shapes),
                                        flat)
