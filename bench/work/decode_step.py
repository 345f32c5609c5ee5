"""Work of decode steps of the whole model.

FLOPs: 2 per weight of every layer's projections and FFN and of the
head for each decoded token, plus its attention over its live context.
The engine computes every lane of `max_batch`, but only live tokens
count.  Bytes, per step: every layer's packed weights and the head as
stored (codes and f32 scales; the norm gains and biases in bfloat16),
the f32 logits of every lane written out; per token: its live KV rows
read (`paged_flash_attention`) and its new K and V rows written.  The
embedding table is not streamed: a step gathers one row per lane.
"""
from __future__ import annotations

from typing import Dict, Sequence

from . import paged_flash_attention as attn

GROUP = 128


def _packed(k: int, n: int) -> int:
    return k * n // 2 + 4 * (k // GROUP) * n


def layer_matrices(dims: Dict) -> list:
    d, h, g, hd, f = (dims[k] for k in ("d", "h", "g", "hd", "f"))
    return [(d, h * hd), (d, g * hd), (d, g * hd), (h * hd, d),
            (d, f), (d, f), (f, d)]


def streamed_bytes(dims: Dict) -> int:
    """Weights a decode step reads once, as stored."""
    d, L = dims["d"], dims["L"]
    mats = sum(_packed(k, n) for k, n in layer_matrices(dims)) * L
    small = 2 * (2 * d * L + d)
    if dims["bias"]:
        small += 2 * (dims["h"] + 2 * dims["g"]) * dims["hd"] * L
    return mats + _packed(d, dims["v"]) + small


def matmul_weights(dims: Dict) -> int:
    return (sum(k * n for k, n in layer_matrices(dims)) * dims["L"]
            + dims["d"] * dims["v"])


def work(dims: Dict, contexts: Sequence[int], steps: int, lanes: int,
         kv_bytes: int) -> Dict:
    """`steps` decode steps that produced tokens with these `contexts`;
    `lanes` is the engine's `max_batch` (rows of logits written)."""
    a = attn.total(dims, contexts, kv_bytes)
    row = dims["hd"] * kv_bytes + (attn.SCALE_BYTES if kv_bytes == 1 else 0)
    new_rows = 2 * len(contexts) * dims["g"] * row * dims["L"]
    per_step = streamed_bytes(dims) + 4 * lanes * dims["v"]
    return {"flops": 2.0 * matmul_weights(dims) * len(contexts) + a["flops"],
            "bytes": float(steps * per_step + a["bytes"] + new_rows)}
