"""Pure-jnp oracles for every Pallas kernel (and the lowering path used by
the dry-run on the CPU backend — identical math, identical shardability)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant.qarray import (QTensor, count_dequant, dequantize,
                                maybe_dequantize, unpack_int4)


def ref_qmatmul(x: jax.Array, w, out_dtype=None) -> jax.Array:
    """x @ W with W dense or QTensor (dequant-then-matmul oracle)."""
    wd = maybe_dequantize(w, jnp.bfloat16 if out_dtype is None else out_dtype)
    return jnp.dot(x, wd.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(
        out_dtype or x.dtype)


def _int_weight(qt: QTensor) -> jax.Array:
    """Packed data -> int8 values at full size (scales NOT applied)."""
    return unpack_int4(qt.data, qt.axis) if qt.bits == 4 else qt.data


def ref_qmatmul_fused(x: jax.Array, w, out_dtype=None) -> jax.Array:
    """x @ W with W held as integers end-to-end: per-group partial sums
    contracted against the per-group scales — the CPU-backend image of the
    `cim_gemv` in-kernel dequant.  Never materializes the float weight
    (a whole-tensor `dequantize` would bump the `full_dequant` trace
    counter; this path bumps `fused_dequant` instead).

    Handles the three serve-path layouts: 2D (K, N) axis=-2 projections,
    batched (E, K, N) axis=-2 expert stacks (x: (E, ..., K)), and the
    axis=-1 (V, K) tied-embedding table contracted over K for logits.

    Shapes are inferred from the DATA arrays, never `orig_shape`: under
    `lax.scan` a stacked QTensor's leaves are sliced per layer while the
    static orig_shape aux keeps the layer dim (the same reason `axis` is
    stored negative).
    """
    if not isinstance(w, QTensor):
        return ref_qmatmul(x, w, out_dtype)
    count_dequant("fused_dequant")
    g = w.group
    q = _int_weight(w)
    xf = x.astype(jnp.float32)
    sf = w.scales.astype(jnp.float32)
    if w.axis == -1:
        # (V, K) table, contraction over K: logits = h @ embed.T
        V, K = q.shape[-2], q.shape[-1]
        xg = xf.reshape(*x.shape[:-1], K // g, g)
        qg = q.reshape(V, K // g, g).astype(jnp.float32)
        partial = jnp.einsum("...ag,vag->...av", xg, qg)
        out = jnp.einsum("...av,va->...v", partial, sf)
        return out.astype(out_dtype or x.dtype)
    assert w.axis == -2, w.axis
    K, N = q.shape[-2], q.shape[-1]
    lead = q.shape[:-2]
    xg = xf.reshape(*x.shape[:-1], K // g, g)
    qg = q.reshape(*lead, K // g, g, N).astype(jnp.float32)
    if not lead:
        partial = jnp.einsum("...ag,agn->...an", xg, qg)
        out = jnp.einsum("...an,an->...n", partial, sf)
    else:
        # batched expert stack: W's leading dim pairs with x's leading dim
        assert len(lead) == 1 and x.shape[0] == lead[0], (x.shape, q.shape)
        partial = jnp.einsum("e...ag,eagn->e...an", xg, qg)
        out = jnp.einsum("e...an,ean->e...n", partial, sf)
    return out.astype(out_dtype or x.dtype)


def ref_flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, window: int = 0,
                     attn_cap: float = 0.0) -> jax.Array:
    """Single-token decode attention oracle.

    q: (b, g, qpk, hd); k, v: (b, S, g, hd); pos scalar; returns
    (b, g, qpk, hd).
    """
    hd = q.shape[-1]
    S = k.shape[1]
    scores = jnp.einsum("bgph,bkgh->bgpk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    if attn_cap:
        scores = attn_cap * jnp.tanh(scores / attn_cap)
    k_pos = jnp.arange(k.shape[1])
    mask = k_pos <= pos
    if window:
        mask = mask & (pos - k_pos < window)
    scores = jnp.where(mask[None, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bgpk,bkgh->bgph", w.astype(v.dtype), v)


def gather_pages(pages: jax.Array, tables: jax.Array,
                 scales: Optional[jax.Array] = None) -> jax.Array:
    """Gather head-major pool pages (n_pages, g, ps, hd) by block table
    into a contiguous (b, S, g, hd) view; with `scales` (per-token INT8
    pool, scales (n_pages, g, ps)) dequantize ONLY the gathered rows —
    the full pool never exists in float."""
    b, mp = tables.shape
    _, g, ps, hd = pages.shape
    x = pages[tables].transpose(0, 1, 3, 2, 4).reshape(b, mp * ps, g, hd)
    if scales is None:
        return x
    s = scales[tables].transpose(0, 1, 3, 2).reshape(b, mp * ps, g)
    return x.astype(jnp.float32) * s[..., None].astype(jnp.float32)


def ref_paged_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     tables: jax.Array, lengths: jax.Array,
                     window: int = 0, attn_cap: float = 0.0,
                     k_scales: Optional[jax.Array] = None,
                     v_scales: Optional[jax.Array] = None) -> jax.Array:
    """Paged single-token decode attention oracle (block-table gather).

    q: (b, g, qpk, hd); k_pages, v_pages: (n_pages, g, page_size, hd);
    tables: (b, max_pages) int32 page ids (padded entries must be valid
    indices — they are masked out); lengths: (b,) int32 tokens valid per
    sequence INCLUSIVE of the current one.  With k_scales/v_scales the
    pools are per-token INT8 (scales (n_pages, g, page_size)) and are
    dequantized after the gather.  Returns (b, g, qpk, hd).
    """
    hd = q.shape[-1]
    k = gather_pages(k_pages, tables, k_scales)
    v = gather_pages(v_pages, tables, v_scales)
    scores = jnp.einsum("bgph,bkgh->bgpk", q, k.astype(q.dtype),
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    if attn_cap:
        scores = attn_cap * jnp.tanh(scores / attn_cap)
    k_pos = jnp.arange(k.shape[1])
    mask = k_pos[None, :] < lengths[:, None]
    if window:
        mask = mask & ((lengths[:, None] - 1) - k_pos[None, :] < window)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bgpk,bkgh->bgph", w.astype(q.dtype),
                      v.astype(q.dtype))


def ref_paged_verify(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     tables: jax.Array, lengths: jax.Array,
                     window: int = 0, attn_cap: float = 0.0,
                     k_scales: Optional[jax.Array] = None,
                     v_scales: Optional[jax.Array] = None) -> jax.Array:
    """Multi-query paged verify oracle (speculative-decode windows).

    q: (b, s, g, qpk, hd) — query j of lane i sits at absolute position
    lengths[i] + j (its K/V rows are already scattered into the pool);
    lengths: (b,) int32 tokens cached BEFORE the window (EXCLUSIVE of
    the window, unlike `ref_paged_decode`).  Intra-window causal mask:
    query j sees k_pos <= lengths[i] + j.  Returns (b, s, g, qpk, hd).
    """
    s = q.shape[1]
    hd = q.shape[-1]
    k = gather_pages(k_pages, tables, k_scales)
    v = gather_pages(v_pages, tables, v_scales)
    scores = jnp.einsum("bqgph,bkgh->bgpqk", q, k.astype(q.dtype),
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    if attn_cap:
        scores = attn_cap * jnp.tanh(scores / attn_cap)
    k_pos = jnp.arange(k.shape[1])
    q_pos = lengths[:, None] + jnp.arange(s)[None, :]           # (b, s)
    mask = k_pos[None, None, :] <= q_pos[:, :, None]            # (b, s, S)
    if window:
        mask = mask & (q_pos[:, :, None] - k_pos[None, None, :] < window)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bgpqk,bkgh->bqgph", w.astype(q.dtype),
                      v.astype(q.dtype))


def ref_swiglu_qgemv(x: jax.Array, w_gate, w_up) -> jax.Array:
    """Fused gate/up GEMV + SiLU*mul oracle. x: (m, d) -> (m, f).

    Uses the fused grouped contraction so the CPU serving path keeps
    packed weights integer end-to-end, matching `swiglu_qgemv`."""
    g = ref_qmatmul_fused(x, w_gate, out_dtype=jnp.float32)
    u = ref_qmatmul_fused(x, w_up, out_dtype=jnp.float32)
    return (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
