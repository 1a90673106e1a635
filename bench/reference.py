"""Plain reference of the served model, and the comparison that decides
``correct``.

The reference is the architecture written out in ``jax.numpy``, float32,
matrix products at ``Precision.HIGHEST``, with no kernel, cache or batching
of the program's.  What is generic lives here: the linear layer of each
form, the layer-by-layer forward pass, the scoring and the control.  The
layers themselves, the embedding, the final norm and the head are the
configuration's architecture module's (``bench/arch/<family>.py``).  The
``packed`` form is the BitDistill student: every linear layer is
``round_clip(127 x / absmax(x)) @ t * absmax(x) / 127 * delta`` with int8
activations per token and ternary ``t`` (BitNet b1.58, arXiv:2402.17764;
BitDistill, arXiv:2510.13998, Eqs. 1-5).

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
import json
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .weights import base_key, ternary_delta

HI = jax.lax.Precision.HIGHEST
ACT_EPS = 1e-5          # absmax activation quantizer (Eq. 3)
RMS_EPS = 1e-6
LOGIT_ROWS = 256        # logits are taken this many positions at a time


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms(x, scale):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS)
    return x * scale


def _fp8(x, axis):
    """x rounded to float8 e4m3 under a scale that maps its absmax (per
    ``axis`` slice, or the whole tensor for ``axis=None``) to 448."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = 448.0 / jnp.maximum(amax, 1e-12)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def linear(x, w, form: str, control: bool):
    """The configuration's linear layer of ``x [..., in]`` by ``w [in,
    out]``."""
    if form == "packed":
        levels = 7.0 if control else 127.0
        gamma = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        xq = jnp.clip(jnp.round(levels / (gamma + ACT_EPS) * x),
                      -levels - 1, levels)
        return mm(xq, w) * (gamma / levels) * ternary_delta(w.shape[-2])
    if control:
        return mm(_fp8(x, -1), _fp8(w, None))
    return mm(x, w)


def _draw(family, key, layer, arch, form, kind):
    return family.layer_tensors(key, layer, arch, form, kind)


@functools.lru_cache(maxsize=None)
def _programs(family, arch_json: str, form: str, control: bool):
    """Jitted pieces of the forward pass: per layer kind, the layer's
    weights from the seed and the layer; the tensors outside the layers;
    the embedding; the final norm at chosen positions."""
    arch = json.loads(arch_json)
    lin = functools.partial(linear, form=form, control=control)
    weights, layers = {}, {}
    for kind in set(family.layer_kinds(arch)):
        weights[kind] = jax.jit(functools.partial(
            _draw, family, arch=arch, form=form, kind=kind))
        layers[kind] = jax.jit(functools.partial(
            family.reference_layer, arch, form, kind, lin))
    top = jax.jit(lambda key: family.top_tensors(key, arch, form))
    embed = jax.jit(family.embed)

    @jax.jit
    def head(top, x, idx):
        """Final norm of the hidden states at positions ``idx`` [S, N]."""
        hsel = jnp.take_along_axis(x, idx[..., None], axis=1)   # [S, N, D]
        return family.final_norm(top, hsel)

    return weights, layers, top, embed, head


def forward_hidden(seed: int, family, arch: Dict, form: str,
                   tokens: np.ndarray, idx: np.ndarray,
                   control: bool = False):
    """Normed final hidden states [S, N, D] at positions ``idx`` of the
    padded token rows ``tokens`` [S, T]; also returns the head's weights
    [vocab, hidden]."""
    weights, layers, top, embed, head = _programs(
        family, json.dumps(arch, sort_keys=True), form, control)
    key = base_key(seed)
    t = top(key)
    x = embed(t, jnp.asarray(tokens, jnp.int32))
    for l, kind in enumerate(family.layer_kinds(arch)):
        x = layers[kind](weights[kind](key, jnp.int32(l)), x)
    return head(t, x, jnp.asarray(idx, jnp.int32)), family.head_table(t)


def compare(seed: int, family, arch: Dict, form: str,
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
    h, table = forward_hidden(seed, family, arch, form, tokens, idx)
    hc = (forward_hidden(seed, family, arch, form, tokens, idx,
                         control=True)[0]
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
        lg = mm(hb, w)
        pick = jnp.argmax(mm(hcb, w), axis=-1)
        return (jnp.max(lg, -1), jnp.take_along_axis(lg, sb[:, None], -1)[:, 0],
                jnp.take_along_axis(lg, pick[:, None], -1)[:, 0])

    return jax.lax.map(block, (h, hc, served))
