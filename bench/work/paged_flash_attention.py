"""Work of `paged_flash_attention` for decoded tokens: what the live KV
rows need, whatever number of pages the kernel walks.

A token with context c (KV rows in its cache, its own included) reads c
rows of K and of V for each of the g KV heads of a layer, in the pool's
storage type, plus one f32 scale per row and head when the pool is int8.
Its h query heads do 2 * c * hd FLOPs for the scores and as many for the
weighted sum.  Its query is read and its output written once, in
bfloat16.
"""
from __future__ import annotations

from typing import Dict, Sequence

SCALE_BYTES = 4


def per_layer(dims: Dict, contexts: Sequence[int], kv_bytes: int) -> Dict:
    c = sum(int(x) for x in contexts)
    g, h, hd = dims["g"], dims["h"], dims["hd"]
    row = hd * kv_bytes + (SCALE_BYTES if kv_bytes == 1 else 0)
    return {"flops": 4.0 * c * h * hd,
            "bytes": float(2 * c * g * row + 2 * 2 * len(contexts) * h * hd)}


def total(dims: Dict, contexts: Sequence[int], kv_bytes: int) -> Dict:
    """Over every layer."""
    one = per_layer(dims, contexts, kv_bytes)
    return {k: v * dims["L"] for k, v in one.items()}
