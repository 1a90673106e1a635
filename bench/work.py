"""Operations and bytes the served model needs, from its shapes: the
arithmetic the step and kernel metrics share.  Counts are of the work the
algorithm needs for the rows that carry a request, never of the padding or
idle rows the program also runs."""
from __future__ import annotations

from typing import Dict


def linear_params(arch: Dict) -> int:
    """Weights of every linear layer of the stack (not the head)."""
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


def least_seconds(linear_ops: float, other_ops: float, form: str,
                  peaks: Dict) -> float:
    """Least time of a step's model operations on the chip: the linear
    layers at the form's peak (int8 for the packed student), the rest at
    the bf16 peak."""
    lin_peak = peaks["int8_ops_per_s"] if form == "packed" \
        else peaks["bf16_flops_per_s"]
    return linear_ops / lin_peak + other_ops / peaks["bf16_flops_per_s"]


def rows(shape: Dict):
    """(start, positions) of every row that carries a request in one launched
    step: a chunk row its chunk, a decode row one position."""
    chunks = shape.get("chunks") or {}
    out = []
    for slot in shape["active"]:
        n = chunks.get(slot, chunks.get(str(slot)))
        out.append((int(shape["positions"][slot]), int(n) if n else 1))
    return out
