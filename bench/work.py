"""The arithmetic the step and kernel metrics share.  The operations and
bytes of one position come from the configuration's architecture module
(``linear_params``, ``attention_ops``, ``head_ops``, ``kv_bytes_per_token``,
``attention_io_bytes``); counts are of the work the algorithm needs for the
rows that carry a request, never of the padding or idle rows the program
also runs."""
from __future__ import annotations

from typing import Dict


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
