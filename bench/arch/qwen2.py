"""Qwen2 dense decoder (Qwen2.5): everything the benchmark knows of its
shapes and names, for the configurations that name ``"arch": "qwen2"``.

Pre-norm RMSNorm, rotary positions in the split-half convention,
grouped-query causal attention with QKV biases (``attention_bias``), SwiGLU
MLP, tied embedding head.  The ``packed`` form is the BitDistill student:
SubLN before the attention and MLP output projections (arXiv:2510.13998,
Eqs. 4-5); its linear layers are the reference's ``linear``.

Tensor names (seeded by ``bench/weights.py`` from ``(seed, layer, name)``):

    embed, final_norm                      (no layer)
    norm1, norm2, wq, bq, wk, bk, wv, bv, wo, attn_subln,
    up, gate, down, mlp_subln              (per layer)

Linear weights are ``[in, out]``.  Every layer is of one kind, ``decoder``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HI, rms
from bench.weights import spread_bf16, steps_bf16, tensor_key, ternary

LINEARS = ("wq", "wk", "wv", "wo", "up", "gate", "down")
BIASES = {"bq": "wq", "bk": "wk", "bv": "wv"}
NORMS = ("norm1", "norm2", "attn_subln", "mlp_subln")

# program module path (below stack/pos0) -> tensor name
PROGRAM_NAMES = {
    "attn/wq": "wq", "attn/wk": "wk", "attn/wv": "wv", "attn/wo": "wo",
    "mlp/up": "up", "mlp/gate": "gate", "mlp/down": "down",
    "attn/subln": "attn_subln", "mlp/subln": "mlp_subln",
    "norm1": "norm1", "norm2": "norm2",
}
LAYER_LEAVES = ("w", "w_packed", "delta", "b", "scale")


# -- the program's configuration -----------------------------------------------

def architecture(config: Dict) -> Dict:
    """The architecture's numbers: the file's ``architecture`` group, the
    keys of the source's ``config.json``."""
    return config["architecture"]


def program_config(config: Dict):
    """The program's ModelConfig: the registry's entry with the file's
    architecture numbers written over it, so the file is the whole truth of
    what runs."""
    from repro.models.base import get_config

    a = architecture(config)
    return get_config(config["program_arch"]).replace(
        vocab=a["vocab_size"], d_model=a["hidden_size"],
        n_layers=a["num_hidden_layers"], n_heads=a["num_attention_heads"],
        n_kv_heads=a["num_key_value_heads"], head_dim=a["head_dim"],
        d_ff=a["intermediate_size"], qkv_bias=a["attention_bias"],
        rope_theta=a["rope_theta"], tie_embeddings=a["tie_word_embeddings"])


# -- seeded tensors ------------------------------------------------------------

def layer_kinds(arch: Dict):
    return ["decoder"] * arch["num_hidden_layers"]


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


def layer_tensors(key, layer, arch: Dict, form: str,
                  kind: str = "decoder") -> Dict[str, jax.Array]:
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


def top_tensors(key, arch: Dict, form: str) -> Dict[str, jax.Array]:
    d, v = arch["hidden_size"], arch["vocab_size"]
    return {"embed": spread_bf16(tensor_key(key, -1, "embed"), (v, d), 0.02),
            "final_norm": 1 + steps_bf16(tensor_key(key, -1, "final_norm"),
                                         (d,), 8, 1 / 64)}


def program_leaf(path: str, arch: Dict, names: Dict = PROGRAM_NAMES):
    """``(layers, tensor name)`` of a program leaf, ``layers`` None for the
    tensors outside the stack; None for a leaf this family does not seed.
    The stack scans one pattern position over every layer."""
    if path == "embed/table":
        return None, "embed"
    if path == "final_norm/scale":
        return None, "final_norm"
    if not path.startswith("stack/pos0/"):
        return None
    mod, leaf = path[len("stack/pos0/"):].rsplit("/", 1)
    name = names.get(mod)
    if name is None or leaf not in LAYER_LEAVES:
        return None
    if leaf == "b":
        name = "b" + name[1]
    return range(arch["num_hidden_layers"]), name


# -- the float32 reference -----------------------------------------------------

def rope(x, pos, theta):
    """x [T, H, Dh], pos [T]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def embed(top, tokens):
    return top["embed"][tokens].astype(jnp.float32)


def reference_layer(arch: Dict, form: str, kind: str, linear, w, x,
                    head_norm=None):
    """One decoder layer over x [S, T, D] (each row a whole sequence);
    ``linear(a, w)`` is the configuration's linear layer.  ``head_norm(w,
    q, k)``, where given, normalises each head's queries and keys [T, H,
    Dh] before the rotary positions."""
    h, hkv, dh = (arch["num_attention_heads"], arch["num_key_value_heads"],
                  arch["head_dim"])
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    lin = lambda a, name: linear(a, w[name])
    s, t, _ = x.shape
    pos = jnp.arange(t, dtype=jnp.int32)
    a = rms(x, w["norm1"])
    q = lin(a, "wq") + w.get("bq", 0.0)
    k = lin(a, "wk") + w.get("bk", 0.0)
    v = lin(a, "wv") + w.get("bv", 0.0)

    def attend(qkv):
        q1, k1, v1 = qkv
        q1, k1 = q1.reshape(t, h, dh), k1.reshape(t, hkv, dh)
        if head_norm is not None:
            q1, k1 = head_norm(w, q1, k1)
        q1 = rope(q1, pos, arch["rope_theta"])
        k1 = rope(k1, pos, arch["rope_theta"])
        v1 = v1.reshape(t, hkv, dh)
        g = h // hkv                          # query head i reads kv head i//g
        k1, v1 = jnp.repeat(k1, g, axis=1), jnp.repeat(v1, g, axis=1)
        sc = jnp.einsum("thd,uhd->htu", q1, k1, precision=HI) / np.sqrt(dh)
        sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("htu,uhd->thd", p, v1, precision=HI).reshape(t, -1)

    ctx = jax.lax.map(attend, (q, k, v))
    if form == "packed":
        ctx = rms(ctx, w["attn_subln"])
    x = x + lin(ctx, "wo")
    a = rms(x, w["norm2"])
    up, gate = lin(a, "up"), lin(a, "gate")
    m = up * (gate * jax.nn.sigmoid(gate))
    if form == "packed":
        m = rms(m, w["mlp_subln"])
    return x + lin(m, "down")


def final_norm(top, h):
    return rms(h, top["final_norm"].astype(jnp.float32))


def head_table(top):
    """The head's weights [vocab, hidden]: the embedding (tied)."""
    return top["embed"]


# -- the work the step and kernel metrics count --------------------------------

def linear_params(arch: Dict) -> int:
    """Weights of every linear layer of the stack a position passes through
    (not the head)."""
    d, f = arch["hidden_size"], arch["intermediate_size"]
    q = arch["num_attention_heads"] * arch["head_dim"]
    kv = arch["num_key_value_heads"] * arch["head_dim"]
    return arch["num_hidden_layers"] * (2 * d * q + 2 * d * kv + 3 * d * f)


def attention_ops(arch: Dict, context: int) -> int:
    """QK and PV operations of one query position over ``context`` keys, all
    layers."""
    return (4 * arch["num_attention_heads"] * arch["head_dim"] * context
            * arch["num_hidden_layers"])


def head_ops(arch: Dict) -> int:
    """Tied head of one position."""
    return 2 * arch["hidden_size"] * arch["vocab_size"]


def kv_bytes_per_token(arch: Dict, itemsize: int) -> int:
    """K and V of one position, all layers, in the pool's dtype."""
    return (2 * arch["num_key_value_heads"] * arch["head_dim"] * itemsize
            * arch["num_hidden_layers"])


def attention_io_bytes(arch: Dict, itemsize: int) -> int:
    """The attention kernel's query read (bfloat16) and context written (the
    pool's dtype) for one position, all layers."""
    return (arch["num_attention_heads"] * arch["head_dim"] * (2 + itemsize)
            * arch["num_hidden_layers"])
