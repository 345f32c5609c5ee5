"""Attention layers: GQA (+bias/QK-norm/softcap/local-global) and MLA.

Two execution paths per flavor:
  * `*_forward`  — full-sequence training/prefill; query-chunked so the
                   32k-prefill score matrix is never fully materialized.
  * `*_decode`   — one-token decode against a KV cache.  For MLA the cache
                   is the compressed latent (EdgeCIM's KV-block streaming
                   applies to a 9x smaller stream — see DESIGN.md SS4).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import (FSDP, NONE, TP, ParamSpec, apply_rope, rms_norm,
                     rope_tables, softcap)
from repro.kernels.ops import projection, qmatmul_xla as qmm
from repro.kernels.ref import gather_pages
from repro.quant.qarray import maybe_dequantize as deq
from .config import ModelConfig

Params = Dict[str, jax.Array]

Q_CHUNK = 2048      # query-block size for chunked attention
NEG_INF = -1.0e30


# ----------------------------------------------------------------------------
# parameter specs
# ----------------------------------------------------------------------------
def gqa_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.hd()
    sp: Dict[str, ParamSpec] = {
        "wq": ParamSpec((d, cfg.n_heads * hd), axes=(FSDP, TP)),
        "wk": ParamSpec((d, cfg.n_kv_heads * hd), axes=(FSDP, TP)),
        "wv": ParamSpec((d, cfg.n_kv_heads * hd), axes=(FSDP, TP)),
        "wo": ParamSpec((cfg.n_heads * hd, d), axes=(TP, FSDP)),
    }
    if cfg.qkv_bias:
        sp["bq"] = ParamSpec((cfg.n_heads * hd,), axes=(TP,), init="zeros")
        sp["bk"] = ParamSpec((cfg.n_kv_heads * hd,), axes=(TP,), init="zeros")
        sp["bv"] = ParamSpec((cfg.n_kv_heads * hd,), axes=(TP,), init="zeros")
    if cfg.qk_norm:
        sp["q_norm"] = ParamSpec((hd,), axes=(NONE,), init="ones")
        sp["k_norm"] = ParamSpec((hd,), axes=(NONE,), init="ones")
    return sp


def mla_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": ParamSpec((d, H * qk_dim), axes=(FSDP, TP)),
        "w_dkv": ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim),
                           axes=(FSDP, NONE)),
        "ckv_norm": ParamSpec((m.kv_lora_rank,), axes=(NONE,), init="ones"),
        "w_uk": ParamSpec((m.kv_lora_rank, H * m.qk_nope_head_dim),
                          axes=(NONE, TP)),
        "w_uv": ParamSpec((m.kv_lora_rank, H * m.v_head_dim),
                          axes=(NONE, TP)),
        "wo": ParamSpec((H * m.v_head_dim, d), axes=(TP, FSDP)),
    }


def attention_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    return mla_specs(cfg) if cfg.attn_kind == "mla" else gqa_specs(cfg)


# ----------------------------------------------------------------------------
# masked, query-chunked softmax attention core
# ----------------------------------------------------------------------------
def _softmax_attend(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: jax.Array, scale: float,
                    attn_cap: float) -> jax.Array:
    """q (b,qs,g,qpk,hd) k/v (b,ks,g,hd) mask (qs,ks) -> (b,qs,g,qpk,hd)."""
    scores = jnp.einsum("bqgph,bkgh->bgpqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if attn_cap:
        scores = softcap(scores, attn_cap)
    scores = jnp.where(mask[None, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgpqk,bkgh->bqgph", w.astype(v.dtype), v)
    return out


def _chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                       q_pos: jax.Array, k_pos: jax.Array, window: jax.Array,
                       scale: float, attn_cap: float,
                       unroll: bool = False) -> jax.Array:
    """Causal (optionally windowed) attention, scanned over query chunks.

    q: (b, qs, g, qpk, hd); k, v: (b, ks, g, hd);
    q_pos (qs,), k_pos (ks,) absolute positions; window: scalar (0 = global).
    """
    b, qs, g, qpk, hd = q.shape
    hd_v = v.shape[-1]                    # MLA: value dim != query dim

    def mask_for(qp):
        causal = qp[:, None] >= k_pos[None, :]
        local = jnp.where(window > 0,
                          qp[:, None] - k_pos[None, :] < window, True)
        return causal & local

    if qs <= Q_CHUNK:
        return _softmax_attend(q, k, v, mask_for(q_pos), scale, attn_cap)

    n_chunks = math.ceil(qs / Q_CHUNK)
    pad = n_chunks * Q_CHUNK - qs
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=q_pos[-1])
    qc = q.reshape(b, n_chunks, Q_CHUNK, g, qpk, hd).swapaxes(0, 1)
    pc = q_pos.reshape(n_chunks, Q_CHUNK)

    def body(_, args):
        qi, pi = args
        return None, _softmax_attend(qi, k, v, mask_for(pi), scale, attn_cap)

    from .common import scan_layers
    _, out = scan_layers(body, None, (qc, pc), unroll)
    out = out.swapaxes(0, 1).reshape(b, n_chunks * Q_CHUNK, g, qpk, hd_v)
    return out[:, :qs]


# ----------------------------------------------------------------------------
# GQA
# ----------------------------------------------------------------------------
def _qkv(p: Params, cfg: ModelConfig, x: jax.Array
         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, s, _ = x.shape
    hd = cfg.hd()
    q = projection("q_proj", x, p["wq"])
    k = projection("k_proj", x, p["wk"])
    v = projection("v_proj", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, hd)
    k = k.reshape(b, s, cfg.n_kv_heads, hd)
    v = v.reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def gqa_forward(p: Params, cfg: ModelConfig, x: jax.Array,
                positions: jax.Array, is_local) -> jax.Array:
    """Full-sequence attention. positions: (s,) int32; is_local: scalar bool."""
    b, s, _ = x.shape
    hd, g, qpk = cfg.hd(), cfg.n_kv_heads, cfg.q_per_kv()
    q, k, v = _qkv(p, cfg, x)

    theta_local = cfg.rope_theta_local or cfg.rope_theta
    theta = jnp.where(is_local, theta_local, cfg.rope_theta)
    # rope tables depend on a traced theta -> compute inline
    freqs = jnp.exp(jnp.arange(0, hd, 2, dtype=jnp.float32) / hd
                    * -jnp.log(theta))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    qg = q.reshape(b, s, g, qpk, hd)
    window = jnp.where(is_local, cfg.local_window, 0)
    out = _chunked_attention(qg, k, v, positions, positions, window,
                             1.0 / math.sqrt(hd), cfg.attn_softcap,
                             unroll=cfg.unroll)
    out = out.reshape(b, s, cfg.n_heads * hd)
    return projection("o_proj", out, p["wo"]), {"k": k, "v": v}


def gqa_decode(p: Params, cfg: ModelConfig, x: jax.Array, cache: Dict,
               pos: jax.Array, is_local) -> Tuple[jax.Array, Dict]:
    """One-token decode. x: (b, 1, d); cache {k,v}: (b, S, g, hd); pos scalar."""
    b = x.shape[0]
    hd, g, qpk = cfg.hd(), cfg.n_kv_heads, cfg.q_per_kv()
    S = cache["k"].shape[1]
    q, k, v = _qkv(p, cfg, x)

    theta_local = cfg.rope_theta_local or cfg.rope_theta
    theta = jnp.where(is_local, theta_local, cfg.rope_theta)
    freqs = jnp.exp(jnp.arange(0, hd, 2, dtype=jnp.float32) / hd
                    * -jnp.log(theta))
    posf = pos.astype(jnp.float32)
    cos = jnp.cos(posf * freqs)[None, :]
    sin = jnp.sin(posf * freqs)[None, :]
    q = apply_rope(q, cos[None], sin[None])
    k = apply_rope(k, cos[None], sin[None])

    # Attend over the STALE cache (positions < pos) plus a rank-1 term for
    # the fresh token, so the cache update is a pure output write that
    # never feeds the attention einsum (SSPerf iteration c4: keeps SPMD
    # from materializing converted copies of the cache around the DUS).
    k_pos = jnp.arange(S)
    valid = k_pos < pos                                 # strictly stale
    window = jnp.where(is_local, cfg.local_window, 0)
    local_ok = jnp.where(window > 0, pos - k_pos < window, True)
    mask = valid & local_ok                             # (S,)

    qg = q.reshape(b, 1, g, qpk, hd)
    scale = 1.0 / math.sqrt(hd)
    scores_c = jnp.einsum("bqgph,bkgh->bgpqk", qg,
                          cache["k"].astype(qg.dtype),
                          preferred_element_type=jnp.float32) * scale
    scores_n = jnp.einsum("bqgph,bqgh->bgpq", qg.astype(jnp.float32),
                          k.astype(jnp.float32))[..., None] * scale
    # (b,g,p,1,1): the fresh token's score per query head
    if cfg.attn_softcap:
        scores_c = softcap(scores_c, cfg.attn_softcap)
        scores_n = softcap(scores_n, cfg.attn_softcap)
    scores_c = jnp.where(mask[None, None, None, None, :], scores_c, NEG_INF)

    m = jnp.maximum(jnp.max(scores_c, axis=-1, keepdims=True), scores_n)
    e_c = jnp.exp(scores_c - m)
    e_n = jnp.exp(scores_n - m)
    denom = jnp.sum(e_c, axis=-1, keepdims=True) + e_n
    out = jnp.einsum("bgpqk,bkgh->bqgph", (e_c / denom).astype(qg.dtype),
                     cache["v"].astype(qg.dtype))
    w_n = (e_n / denom)[..., 0]                         # (b,g,p,1)
    out = out + jnp.einsum("bgpq,bqgh->bqgph", w_n.astype(qg.dtype),
                           v.astype(qg.dtype))

    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, pos, 0, 0))
    out = projection("o_proj", out.reshape(b, 1, cfg.n_heads * hd),
                     p["wo"])
    return out, {"k": ck, "v": cv}


def _page_scatter(pool: jax.Array, vals: jax.Array, tables: jax.Array,
                  slots: jax.Array, n_new: jax.Array,
                  head_major: bool = False) -> jax.Array:
    """Write per-token rows into a paged pool.

    pool: (n_pages, page_size, ...), or with `head_major` (n_pages, g,
    page_size, ...); vals: (b, s, ...) (b, s, g, ... when head-major);
    tables: (b, max_pages); slots: (b, s) absolute positions; n_new: (b,)
    valid new tokens per sequence (padding lanes write out-of-bounds and
    drop).
    """
    b, s = vals.shape[0], vals.shape[1]
    n_pages, ps = pool.shape[0], pool.shape[2 if head_major else 1]
    page = tables[jnp.arange(b)[:, None], slots // ps]           # (b, s)
    page = jnp.where(jnp.arange(s)[None, :] < n_new[:, None], page, n_pages)
    off = slots % ps
    # head-major: the advanced indices straddle the head axis, so the
    # indexed view is (b, s, g, ...) — vals' own layout
    idx = (page, slice(None), off) if head_major else (page, off)
    return pool.at[idx].set(vals.astype(pool.dtype), mode="drop")


def _quantize_kv_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-(token, kv-head) symmetric INT8: x (b, s, g, hd) -> (values
    rounded to [-127, 127] still in float, scales (b, s, g) f16-rounded).
    The STORED scale is what divides, so pool int8 x pool scale
    round-trips without a second rounding."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = (jnp.maximum(absmax, 1e-8) / 127.0).astype(jnp.float16)
    q = jnp.clip(jnp.round(x.astype(jnp.float32)
                           / scale[..., None].astype(jnp.float32)),
                 -127.0, 127.0)
    return q, scale


def gqa_paged_step(p: Params, cfg: ModelConfig, x: jax.Array, cache: Dict,
                   tables: jax.Array, lengths: jax.Array, n_new: jax.Array,
                   is_local, verify: bool = False) -> Tuple[jax.Array, Dict]:
    """Chunked prefill / decode against a paged KV pool.

    x: (b, s, d) — s == 1 is decode, s > 1 a prefill chunk (right-padded;
    `n_new[i]` of the s tokens are real).  cache {k, v}:
    head-major (n_pages, g, page_size, hd) page pools shared by the whole
    batch;
    tables: (b, max_pages) int32; lengths: (b,) tokens already cached.
    Per-sequence positions — no shared `pos` scalar, so one sequence's
    prefill can never clobber another's rows (the dense engine's
    `_prefill_slot` bug).

    verify=True (speculative decode) routes the s > 1 window through the
    multi-query flash kernel — one pass over the sequence's pages
    scores all s draft positions — instead of the chunk path's full
    page gather.  Same math (the intra-window causal mask is identical);
    sliding-window models carry a traced `is_local` and keep the masked
    gather path.
    """
    b, s, _ = x.shape
    hd, g, qpk = cfg.hd(), cfg.n_kv_heads, cfg.q_per_kv()
    q, k, v = _qkv(p, cfg, x)

    theta_local = cfg.rope_theta_local or cfg.rope_theta
    theta = jnp.where(is_local, theta_local, cfg.rope_theta)
    slots = lengths[:, None] + jnp.arange(s)[None, :]            # (b, s)
    cos, sin = rope_tables(slots, hd, theta)                     # (b, s, hd/2)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    quant_kv = "k_scale" in cache
    if quant_kv:
        # per-token INT8 pools: scale pages ride the same block tables
        # (COW/fork/trim move them with their K/V pages for free)
        kq, ks = _quantize_kv_rows(k)
        vq, vs = _quantize_kv_rows(v)
        ck = _page_scatter(cache["k"], kq, tables, slots, n_new, True)
        cv = _page_scatter(cache["v"], vq, tables, slots, n_new, True)
        cks = _page_scatter(cache["k_scale"], ks, tables, slots, n_new, True)
        cvs = _page_scatter(cache["v_scale"], vs, tables, slots, n_new, True)
        out_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    else:
        ck = _page_scatter(cache["k"], k, tables, slots, n_new, True)
        cv = _page_scatter(cache["v"], v, tables, slots, n_new, True)
        cks = cvs = None
        out_cache = {"k": ck, "v": cv}
    total = lengths + n_new                                      # (b,)
    window = int(cfg.local_window or 0)
    scale = 1.0 / math.sqrt(hd)

    if s == 1 and not window:
        # decode fast path: block-table Pallas kernel on TPU, gather
        # reference elsewhere.  Models with sliding-window layers carry a
        # traced `is_local`, which needs the masked gather path below.
        from repro.kernels.ops import paged_decode_attention
        qg = q.reshape(b, g, qpk, hd)
        out_g = paged_decode_attention(qg, ck, cv, tables, total, 0,
                                       cfg.attn_softcap,
                                       k_scales=cks, v_scales=cvs)
        out = out_g.reshape(b, 1, cfg.n_heads * hd).astype(x.dtype)
        return projection("o_proj", out, p["wo"]), out_cache

    if verify and not window:
        # speculative-verify fast path: all s window positions in one
        # multi-query pass, no (b, S, ...) gather materialized
        from repro.kernels.ops import paged_verify_attention
        qg = q.reshape(b, s, g, qpk, hd)
        out_g = paged_verify_attention(qg, ck, cv, tables, lengths, 0,
                                       cfg.attn_softcap,
                                       k_scales=cks, v_scales=cvs)
        out = out_g.reshape(b, s, cfg.n_heads * hd).astype(x.dtype)
        return projection("o_proj", out, p["wo"]), out_cache

    # chunk path: gather the sequence's pages back to a contiguous view
    kg = gather_pages(ck, tables, cks)
    vg = gather_pages(cv, tables, cvs)
    S = kg.shape[1]
    qg = q.reshape(b, s, g, qpk, hd)
    scores = jnp.einsum("bqgph,bkgh->bgpqk", qg, kg.astype(qg.dtype),
                        preferred_element_type=jnp.float32) * scale
    if cfg.attn_softcap:
        scores = softcap(scores, cfg.attn_softcap)
    k_pos = jnp.arange(S)
    mask = (k_pos[None, None, :] <= slots[:, :, None]) \
        & (k_pos[None, None, :] < total[:, None, None])          # (b, s, S)
    if window:
        local_ok = slots[:, :, None] - k_pos[None, None, :] < window
        mask = mask & jnp.where(is_local, local_ok, True)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgpqk,bkgh->bqgph", w.astype(vg.dtype), vg)
    out = out.reshape(b, s, cfg.n_heads * hd).astype(x.dtype)
    return projection("o_proj", out, p["wo"]), out_cache


def mla_paged_step(p: Params, cfg: ModelConfig, x: jax.Array, cache: Dict,
                   tables: jax.Array, lengths: jax.Array, n_new: jax.Array,
                   is_local, verify: bool = False) -> Tuple[jax.Array, Dict]:
    """Paged absorbed-MLA step over latent pools.

    cache {c_kv: (n_pages, ps, r), k_rope: (n_pages, ps, rope_d)}.
    The latent gather already scores every window position with the
    correct intra-window causal mask, so `verify` needs no separate
    path (the latent stream is ~9x smaller than GQA K/V — the gather
    the multi-query kernel exists to avoid is cheap here).
    """
    m = cfg.mla
    b, s, _ = x.shape
    H = cfg.n_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    r = m.kv_lora_rank
    ps = cache["c_kv"].shape[1]
    S = tables.shape[1] * ps

    q = qmm(x, p["wq"]).reshape(b, s, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    dkv = qmm(x, p["w_dkv"])
    c_new = rms_norm(dkv[..., :r], p["ckv_norm"], cfg.norm_eps)
    kr_new = dkv[..., r:][:, :, None, :]                         # (b,s,1,rd)

    slots = lengths[:, None] + jnp.arange(s)[None, :]
    cos, sin = rope_tables(slots, rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    kr_new = apply_rope(kr_new, cos, sin)

    c_pool = _page_scatter(cache["c_kv"], c_new, tables, slots, n_new)
    kr_pool = _page_scatter(cache["k_rope"], kr_new[:, :, 0, :], tables,
                            slots, n_new)
    c_all = c_pool[tables].reshape(b, S, r)
    kr_all = kr_pool[tables].reshape(b, S, rope_d)

    w_uk = deq(p["w_uk"]).reshape(r, H, nope)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
    scores = (jnp.einsum("bqhr,bkr->bhqk", q_lat, c_all.astype(q_lat.dtype),
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bkr->bhqk", q_rope,
                           kr_all.astype(q_rope.dtype),
                           preferred_element_type=jnp.float32))
    scores = scores / math.sqrt(nope + rope_d)
    k_pos = jnp.arange(S)
    total = lengths + n_new
    mask = (k_pos[None, None, :] <= slots[:, :, None]) \
        & (k_pos[None, None, :] < total[:, None, None])
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)

    o_lat = jnp.einsum("bhqk,bkr->bqhr", w.astype(c_all.dtype), c_all)
    w_uv = deq(p["w_uv"]).reshape(r, H, vd)
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat.astype(x.dtype), w_uv)
    out = qmm(out.reshape(b, s, H * vd), p["wo"])
    return out, {"c_kv": c_pool, "k_rope": kr_pool}


# ----------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ----------------------------------------------------------------------------
def mla_forward(p: Params, cfg: ModelConfig, x: jax.Array,
                positions: jax.Array, is_local) -> jax.Array:
    """Training path: decompress the latent into per-head K/V."""
    m = cfg.mla
    b, s, _ = x.shape
    H = cfg.n_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q = qmm(x, p["wq"]).reshape(b, s, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    dkv = qmm(x, p["w_dkv"])
    c_kv = rms_norm(dkv[..., :m.kv_lora_rank], p["ckv_norm"], cfg.norm_eps)
    k_rope = dkv[..., m.kv_lora_rank:]                  # (b, s, rope_d)

    cos, sin = rope_tables(positions, rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)  # (b,s,1,rope_d)

    k_nope = qmm(c_kv, p["w_uk"]).reshape(b, s, H, nope)
    v = qmm(c_kv, p["w_uv"]).reshape(b, s, H, vd)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, H, rope_d))],
                        axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)

    qg = qf.reshape(b, s, H, 1, nope + rope_d)
    out = _chunked_attention(qg, k, v, positions, positions,
                             jnp.int32(0), 1.0 / math.sqrt(nope + rope_d),
                             cfg.attn_softcap, unroll=cfg.unroll)
    out = out.reshape(b, s, H * vd)
    return qmm(out, p["wo"]), {"c_kv": c_kv, "k_rope": k_rope[:, :, 0, :]}


def mla_decode(p: Params, cfg: ModelConfig, x: jax.Array, cache: Dict,
               pos: jax.Array, is_local) -> Tuple[jax.Array, Dict]:
    """Absorbed decode over the compressed cache {c_kv: (b,S,r), k_rope}."""
    m = cfg.mla
    b = x.shape[0]
    H = cfg.n_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    r = m.kv_lora_rank
    S = cache["c_kv"].shape[1]

    q = qmm(x, p["wq"]).reshape(b, 1, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    dkv = qmm(x, p["w_dkv"])
    c_new = rms_norm(dkv[..., :r], p["ckv_norm"], cfg.norm_eps)
    krope_new = dkv[..., r:][:, :, None, :]

    posf = pos.astype(jnp.float32)
    freqs = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rope_d, 2,
                                                 dtype=jnp.float32) / rope_d))
    cos = jnp.cos(posf * freqs)[None, :]
    sin = jnp.sin(posf * freqs)[None, :]
    q_rope = apply_rope(q_rope, cos[None], sin[None])
    krope_new = apply_rope(krope_new, cos[None], sin[None])

    c_cache = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, pos, 0))
    kr_cache = jax.lax.dynamic_update_slice(
        cache["k_rope"], krope_new[:, :, 0, :].astype(cache["k_rope"].dtype),
        (0, pos, 0))

    # absorb: q_lat = q_nope @ W_UK^T  (per head)
    w_uk = deq(p["w_uk"]).reshape(r, H, nope)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)

    scores = (jnp.einsum("bqhr,bkr->bhqk", q_lat,
                         c_cache.astype(q_lat.dtype),
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bkr->bhqk", q_rope,
                           kr_cache.astype(q_rope.dtype),
                           preferred_element_type=jnp.float32))
    scores = scores / math.sqrt(nope + rope_d)
    valid = jnp.arange(S) <= pos
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)

    o_lat = jnp.einsum("bhqk,bkr->bqhr", w.astype(c_cache.dtype), c_cache)
    w_uv = deq(p["w_uv"]).reshape(r, H, vd)
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat.astype(x.dtype), w_uv)
    out = qmm(out.reshape(b, 1, H * vd), p["wo"])
    return out, {"c_kv": c_cache, "k_rope": kr_cache}


# ----------------------------------------------------------------------------
# dispatch + cache construction
# ----------------------------------------------------------------------------
def attn_forward(p, cfg, x, positions, is_local):
    fn = mla_forward if cfg.attn_kind == "mla" else gqa_forward
    return fn(p, cfg, x, positions, is_local)


def attn_decode(p, cfg, x, cache, pos, is_local):
    fn = mla_decode if cfg.attn_kind == "mla" else gqa_decode
    return fn(p, cfg, x, cache, pos, is_local)


def attn_paged_step(p, cfg, x, cache, tables, lengths, n_new, is_local,
                    verify: bool = False):
    fn = mla_paged_step if cfg.attn_kind == "mla" else gqa_paged_step
    return fn(p, cfg, x, cache, tables, lengths, n_new, is_local,
              verify=verify)


def paged_cache_spec(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> Dict[str, jax.ShapeDtypeStruct]:
    """Shape/dtype of one layer's paged KV pool (shared by all sequences).

    K/V pools are head-major, (n_pages, g, page_size, hd), so the paged
    kernels stream one head's page as a dense (page_size, hd) tile.
    dtype == int8 requests the quantized pool layout: int8 K/V plus
    per-(token, kv-head) scale pools (n_pages, g, page_size) keyed
    "k_scale"/"v_scale", f32 holding f16-rounded values.  Every leaf keeps
    the page axis first, so the allocator's page-copy (COW), fork, and
    trim move scales together with their pages — the block table stays
    the single source of truth.
    """
    if cfg.attn_kind == "mla":
        if dtype == jnp.int8:
            # the latent stream is already ~9x smaller than GQA K/V and
            # is consumed through matmuls (not per-token rows); keep fp
            raise ValueError(
                "int8 paged KV is not supported for MLA latent pools")
        m = cfg.mla
        return {
            "c_kv": jax.ShapeDtypeStruct((n_pages, page_size,
                                          m.kv_lora_rank), dtype),
            "k_rope": jax.ShapeDtypeStruct((n_pages, page_size,
                                            m.qk_rope_head_dim), dtype),
        }
    kv = jax.ShapeDtypeStruct((n_pages, cfg.n_kv_heads, page_size,
                               cfg.hd()), dtype)
    spec = {"k": kv, "v": kv}
    if dtype == jnp.int8:
        sc = jax.ShapeDtypeStruct((n_pages, cfg.n_kv_heads, page_size),
                                  jnp.float32)
        spec["k_scale"] = sc
        spec["v_scale"] = sc
    return spec


def empty_cache_spec(cfg: ModelConfig, batch: int, max_seq: int,
                     dtype=jnp.bfloat16) -> Dict[str, jax.ShapeDtypeStruct]:
    """Shape/dtype of one layer's KV cache."""
    if cfg.attn_kind == "mla":
        m = cfg.mla
        return {
            "c_kv": jax.ShapeDtypeStruct((batch, max_seq, m.kv_lora_rank), dtype),
            "k_rope": jax.ShapeDtypeStruct((batch, max_seq, m.qk_rope_head_dim),
                                           dtype),
        }
    return {
        "k": jax.ShapeDtypeStruct((batch, max_seq, cfg.n_kv_heads, cfg.hd()),
                                  dtype),
        "v": jax.ShapeDtypeStruct((batch, max_seq, cfg.n_kv_heads, cfg.hd()),
                                  dtype),
    }
