"""Operations and bytes the algorithm needs, from shapes alone.

FLOPs follow the PaLM convention for a decoder's training step:
``6 * N`` per token for the N matmul parameters (forward, and twice
that backward; the embedding lookup is not a matmul) plus
``12 * layers * seq * attention width`` per token for the score and
value products. Recomputation (remat, flash attention's backward) is
not counted.

Codec bytes are those the format must move: every element read or
written once at its width, plus one float32 absmax per block. Padding
that an implementation adds is not counted, so it lowers the share.
"""
from __future__ import annotations

import math

from refmodel import head_dim, leaf_specs

BLOCK8 = 4096
BLOCK4 = 64


def param_count(model: dict) -> tuple[int, int]:
    """(parameters, tensors) of the model as the reference builds it."""
    specs = leaf_specs(model)
    return sum(math.prod(shape) for shape, _ in specs.values()), len(specs)


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matmul (all but the embedding
    table, the norms and the biases)."""
    L, d, f, V = (model[k] for k in ("num_layers", "d_model", "d_ff", "vocab_size"))
    qf = model["num_heads"] * head_dim(model)
    kvf = model["num_kv_heads"] * head_dim(model)
    return L * (d * qf + 2 * d * kvf + qf * d + 3 * d * f) + d * V


def step_flops(model: dict, batch: int, seq: int) -> float:
    """Forward + backward FLOPs of one local step on batch x seq tokens."""
    attn_width = model["num_heads"] * head_dim(model)
    per_token = 6 * matmul_params(model) + 12 * model["num_layers"] * seq * attn_width
    return float(per_token) * batch * seq


def codec_bytes(kind: str, elems: int) -> float:
    """Bytes one codec or fold call over ``elems`` elements must move.

    q8/d8: float32 <-> int8 + absmax per 4096; q4/d4: float32 <-> 4 bits
    + absmax per 64; fold8: read the float32 sum, the int8 codes and the
    absmaxes, write the sum back."""
    if kind in ("q8", "d8"):
        return 4.0 * elems + elems + 4.0 * math.ceil(elems / BLOCK8)
    if kind in ("q4", "d4"):
        return 4.0 * elems + elems / 2.0 + 4.0 * math.ceil(elems / BLOCK4)
    if kind == "fold8":
        return 9.0 * elems + 4.0 * math.ceil(elems / BLOCK8)
    raise ValueError(f"unknown codec kind {kind!r}")
