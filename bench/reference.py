"""Plain reference of the served model, and the comparison that decides
``correct``.

The reference is the architecture written out in ``jax.numpy``, float32,
matrix products at ``Precision.HIGHEST``, with no kernel, cache or batching
of the program's: a Qwen2.5 decoder (pre-norm RMSNorm, rotary positions in
the split-half convention, grouped-query causal attention with QKV biases,
SwiGLU MLP, tied embedding head).  The ``packed`` form is the BitDistill
student: SubLN before the attention and MLP output projections, and every
linear layer as ``round_clip(127 x / absmax(x)) @ t * absmax(x) / 127 *
delta`` with int8 activations per token and ternary ``t`` (BitNet b1.58,
arXiv:2402.17764; BitDistill, arXiv:2510.13998, Eqs. 1-5).

It imports nothing of the program and reads nothing the program made: the
weights are drawn again from the seed (``weights.py``), one layer at a
time, and the sequences are the prompts and the tokens the client received.

``compare`` teacher-forces each sequence and, at every served position,
reads how far the served token's logit lies below the reference's best.
With ``control=True`` it also runs the same forward in the precision one
step below the configuration's (``bf16``: weights and activations in
float8 e4m3 with per-tensor and per-token scales; ``packed``: activations
in int4 instead of int8) and reads the gap of the token that lower
precision puts first: the reading that a program computing in that
precision would give.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .weights import base_key, layer_tensors, ternary_delta, top_tensors

HI = jax.lax.Precision.HIGHEST
ACT_EPS = 1e-5          # absmax activation quantizer (Eq. 3)
RMS_EPS = 1e-6
LOGIT_ROWS = 256        # logits are taken this many positions at a time


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, scale):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS)
    return x * scale


def _fp8(x, axis):
    """x rounded to float8 e4m3 under a scale that maps its absmax (per
    ``axis`` slice, or the whole tensor for ``axis=None``) to 448."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = 448.0 / jnp.maximum(amax, 1e-12)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _linear(x, w, form: str, fan_in: int, control: bool):
    if form == "packed":
        levels = 7.0 if control else 127.0
        gamma = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        xq = jnp.clip(jnp.round(levels / (gamma + ACT_EPS) * x),
                      -levels - 1, levels)
        return _mm(xq, w) * (gamma / levels) * ternary_delta(fan_in)
    if control:
        return _mm(_fp8(x, -1), _fp8(w, None))
    return _mm(x, w)


def _rope(x, pos, theta):
    """x [T, H, Dh], pos [T]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(arch: Dict, form: str, control: bool, w, x):
    """One decoder layer over x [S, T, D] (each row a whole sequence)."""
    h, hkv, dh = (arch["num_attention_heads"], arch["num_key_value_heads"],
                  arch["head_dim"])
    d, f = arch["hidden_size"], arch["intermediate_size"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    lin = lambda a, name, k: _linear(a, w[name], form, k, control)
    s, t, _ = x.shape
    pos = jnp.arange(t, dtype=jnp.int32)
    a = _rms(x, w["norm1"])
    q = lin(a, "wq", d) + w.get("bq", 0.0)
    k = lin(a, "wk", d) + w.get("bk", 0.0)
    v = lin(a, "wv", d) + w.get("bv", 0.0)

    def attend(qkv):
        q1, k1, v1 = qkv
        q1 = _rope(q1.reshape(t, h, dh), pos, arch["rope_theta"])
        k1 = _rope(k1.reshape(t, hkv, dh), pos, arch["rope_theta"])
        v1 = v1.reshape(t, hkv, dh)
        g = h // hkv                          # query head i reads kv head i//g
        k1, v1 = jnp.repeat(k1, g, axis=1), jnp.repeat(v1, g, axis=1)
        sc = jnp.einsum("thd,uhd->htu", q1, k1, precision=HI) / np.sqrt(dh)
        sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("htu,uhd->thd", p, v1, precision=HI).reshape(t, -1)

    ctx = jax.lax.map(attend, (q, k, v))
    if form == "packed":
        ctx = _rms(ctx, w["attn_subln"])
    x = x + lin(ctx, "wo", h * dh)
    a = _rms(x, w["norm2"])
    up, gate = lin(a, "up", d), lin(a, "gate", d)
    m = up * (gate * jax.nn.sigmoid(gate))
    if form == "packed":
        m = _rms(m, w["mlp_subln"])
    return x + lin(m, "down", f)


@functools.lru_cache(maxsize=None)
def _programs(arch_items: Tuple, form: str, control: bool):
    arch = dict(arch_items)
    weights = jax.jit(lambda key, l: layer_tensors(key, l, arch, form))
    layer = jax.jit(functools.partial(_layer, arch, form, control))
    top = jax.jit(lambda key: top_tensors(key, arch))
    embed = jax.jit(lambda table, tok: table[tok].astype(jnp.float32))

    @jax.jit
    def head(norm, x, idx):
        """Final norm of the hidden states at positions ``idx`` [S, N]."""
        hsel = jnp.take_along_axis(x, idx[..., None], axis=1)   # [S, N, D]
        return _rms(hsel, norm.astype(jnp.float32))

    return weights, layer, top, embed, head


def forward_hidden(seed: int, arch: Dict, form: str, tokens: np.ndarray,
                   idx: np.ndarray, control: bool = False):
    """Normed final hidden states [S, N, D] at positions ``idx`` of the
    padded token rows ``tokens`` [S, T]; also returns the embedding table."""
    weights, layer, top, embed, head = _programs(
        tuple(sorted(arch.items())), form, control)
    key = base_key(seed)
    t = top(key)
    x = embed(t["embed"], jnp.asarray(tokens, jnp.int32))
    for l in range(arch["num_hidden_layers"]):
        x = layer(weights(key, jnp.int32(l)), x)
    return head(t["final_norm"], x, jnp.asarray(idx, jnp.int32)), t["embed"]


def compare(seed: int, arch: Dict, form: str,
            seqs: Sequence[Tuple[List[int], List[int]]], pad_len: int,
            pad_out: int, control: bool = False) -> Dict[str, float]:
    """Teacher-force each (prompt, served tokens) pair through the reference.

    Returns ``gap``: the widest gap, over every served token, between the
    reference's best logit and the served token's logit at that position;
    with ``control`` also ``control_gap``, the same for the token the
    lower-precision forward puts first.  ``pad_len`` and ``pad_out`` fix the
    padded row length and served-token count, so that every run compiles
    the same reference programs."""
    s, n = len(seqs), pad_out
    tokens = np.zeros((s, pad_len), np.int32)
    idx = np.zeros((s, n), np.int32)
    served = np.zeros((s, n), np.int32)
    mask = np.zeros((s, n), bool)
    for i, (prompt, out) in enumerate(seqs):
        seq = list(prompt) + list(out)
        if len(seq) > pad_len or len(out) > pad_out:
            raise ValueError(f"{len(prompt)} + {len(out)} tokens do not fit "
                             f"the reference's pad {pad_len} / {pad_out}")
        tokens[i, :len(seq)] = seq
        p = len(prompt)
        idx[i, :len(out)] = np.arange(p - 1, p - 1 + len(out))
        served[i, :len(out)] = out
        mask[i, :len(out)] = True
    h, table = forward_hidden(seed, arch, form, tokens, idx)
    hc = (forward_hidden(seed, arch, form, tokens, idx, control=True)[0]
          if control else h)
    best, chosen, pick = _score(table, _blocks(h), _blocks(hc),
                                _blocks(jnp.asarray(served)[..., None])[..., 0])
    rows = lambda a: np.asarray(a).reshape(-1)[:s * n].reshape(s, n)
    best, chosen, pick = rows(best), rows(chosen), rows(pick)
    out = {"gap": float(np.max((best - chosen)[mask])),
           "positions": int(mask.sum())}
    if control:
        out["control_gap"] = float(np.max((best - pick)[mask]))
    return out


def _blocks(a: jax.Array) -> jax.Array:
    """[S, N, ...] -> [blocks, LOGIT_ROWS, ...], zero-padded."""
    a = a.reshape((-1,) + a.shape[2:])
    pad = -a.shape[0] % LOGIT_ROWS
    a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a.reshape((-1, LOGIT_ROWS) + a.shape[1:])


@jax.jit
def _score(table, h, hc, served):
    """Per position: the reference's best logit, the served token's logit,
    and the reference logit of the token that ``hc`` puts first."""
    w = table.astype(jnp.float32).T

    def block(args):
        hb, hcb, sb = args
        lg = _mm(hb, w)
        pick = jnp.argmax(_mm(hcb, w), axis=-1)
        return (jnp.max(lg, -1), jnp.take_along_axis(lg, sb[:, None], -1)[:, 0],
                jnp.take_along_axis(lg, pick[:, None], -1)[:, 0])

    return jax.lax.map(block, (h, hc, served))
