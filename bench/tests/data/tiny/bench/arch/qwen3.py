"""Qwen3 dense decoder: Qwen2's layer with RMSNorm of each head's queries
and keys (``q_norm``, ``k_norm``, over the head dimension, before the
rotary positions) and no QKV bias.  Built on ``qwen2.py`` beside it."""
import pathlib

from bench.harness import load_family
from bench.reference import rms
from bench.weights import steps_bf16, tensor_key

qwen2 = load_family(pathlib.Path(__file__).resolve().parent, "qwen2")

architecture = qwen2.architecture
program_config = qwen2.program_config
layer_kinds = qwen2.layer_kinds
top_tensors = qwen2.top_tensors
embed, final_norm, head_table = qwen2.embed, qwen2.final_norm, qwen2.head_table
linear_params, attention_ops, head_ops = (qwen2.linear_params,
                                          qwen2.attention_ops, qwen2.head_ops)
kv_bytes_per_token = qwen2.kv_bytes_per_token
attention_io_bytes = qwen2.attention_io_bytes

HEAD_NORMS = ("q_norm", "k_norm")
PROGRAM_NAMES = dict(qwen2.PROGRAM_NAMES, **{"attn/q_norm": "q_norm",
                                             "attn/k_norm": "k_norm"})


def layer_tensors(key, layer, arch, form, kind="decoder"):
    out = qwen2.layer_tensors(key, layer, arch, form, kind)
    for name in HEAD_NORMS:
        out[name] = 1 + steps_bf16(tensor_key(key, layer, name),
                                   (arch["head_dim"],), 8, 1 / 64)
    return out


def program_leaf(path, arch):
    return qwen2.program_leaf(path, arch, PROGRAM_NAMES)


def head_norm(w, q, k):
    return rms(q, w["q_norm"]), rms(k, w["k_norm"])


def reference_layer(arch, form, kind, linear, w, x):
    return qwen2.reference_layer(arch, form, kind, linear, w, x, head_norm)
