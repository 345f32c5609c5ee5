"""Feed-forward layers: gated dense (SwiGLU/GeGLU) and Mixture-of-Experts.

MoE uses a drop-on-overflow gather/scatter dispatch by default: tokens are
sorted by expert, packed into (E, capacity) buffers, processed by a batched
expert GEMM with the expert dim sharded over the `model` mesh axis (EP),
and combined with router weights.  FLOPs stay honest (no one-hot dispatch
matmuls polluting the roofline); a GShard-style one-hot einsum variant is
kept for the §Perf ablation (`cfg.moe.dispatch = "onehot"`).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .common import ACTIVATIONS, EXPERT, FSDP, NONE, TP, ParamSpec
from repro.kernels.ops import projection, qmatmul_xla as qmm
from repro.quant.qarray import maybe_dequantize as deq
from .config import ModelConfig

Params = Dict[str, jax.Array]


# ----------------------------------------------------------------------------
# dense gated FFN
# ----------------------------------------------------------------------------
def dense_ffn_specs(cfg: ModelConfig, d_ff: int = 0) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    sp = {
        "w_up": ParamSpec((d, f), axes=(FSDP, TP)),
        "w_down": ParamSpec((f, d), axes=(TP, FSDP)),
    }
    if cfg.ffn_gated:
        sp["w_gate"] = ParamSpec((d, f), axes=(FSDP, TP))
    return sp


def dense_ffn(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.ffn_gated and cfg.ffn_act == "silu":
        # fused SwiGLU: one pass over the packed gate/up weights
        # (swiglu_qgemv Pallas kernel on TPU, fused grouped einsum on CPU)
        from repro.kernels.ops import swiglu
        with jax.named_scope("gate_up"):
            h = swiglu(x, p["w_gate"], p["w_up"])
        return projection("down_proj", h, p["w_down"])
    act = ACTIVATIONS[cfg.ffn_act]
    with jax.named_scope("gate_up"):
        up = qmm(x, p["w_up"])
        if cfg.ffn_gated:
            h = act(qmm(x, p["w_gate"])) * up
        else:
            h = act(up)
    return projection("down_proj", h, p["w_down"])


# ----------------------------------------------------------------------------
# MoE
# ----------------------------------------------------------------------------
def moe_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    m = cfg.moe
    d, fe = cfg.d_model, m.d_ff_expert
    sp: Dict[str, ParamSpec] = {
        "router": ParamSpec((d, m.n_experts), axes=(FSDP, NONE),
                            scale=1.0 / math.sqrt(d)),
        # FSDP on the f dim (not the contracted d). NOTE: measured
        # byte-identical to d-dim FSDP at 256 devices (SSPerf cell b4,
        # refuted — GSPMD propagation picks its own expert resharding
        # either way); kept for the clearer annotation.
        "we_gate": ParamSpec((m.n_experts, d, fe), axes=(EXPERT, NONE, FSDP)),
        "we_up": ParamSpec((m.n_experts, d, fe), axes=(EXPERT, NONE, FSDP)),
        "we_down": ParamSpec((m.n_experts, fe, d), axes=(EXPERT, FSDP, NONE)),
    }
    if m.n_shared_experts > 0:
        fs = fe * m.n_shared_experts
        sp["ws_gate"] = ParamSpec((d, fs), axes=(FSDP, TP))
        sp["ws_up"] = ParamSpec((d, fs), axes=(FSDP, TP))
        sp["ws_down"] = ParamSpec((fs, d), axes=(TP, FSDP))
    return sp


def _router(p: Params, cfg: ModelConfig, xf: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """xf: (T, d) -> (weights (T,k), expert ids (T,k))."""
    m = cfg.moe
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, ids


def _expert_ffn(p: Params, cfg: ModelConfig, xe: jax.Array) -> jax.Array:
    """xe: (E, C, d) -> (E, C, d), batched over the expert dim.  Quantized
    expert stacks go through the fused grouped contraction (lead dim E),
    so packed experts stay integer on the serve path too."""
    from repro.kernels.ref import ref_qmatmul_fused
    from repro.quant.qarray import QTensor

    def mm(x, w):
        if isinstance(w, QTensor):
            return ref_qmatmul_fused(x, w, out_dtype=x.dtype)
        return jnp.einsum("ecd,edf->ecf", x, w.astype(x.dtype))

    act = ACTIVATIONS[cfg.ffn_act]
    h = act(mm(xe, p["we_gate"])) * mm(xe, p["we_up"])
    return mm(h, p["we_down"])


def _moe_gather(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Sort-based dispatch with per-expert capacity (drop on overflow)."""
    m = cfg.moe
    b, s, d = x.shape
    T = b * s
    k = m.top_k
    E = m.n_experts
    cap = max(8, int(math.ceil(T * k / E * m.capacity_factor)))

    xf = x.reshape(T, d)
    w, ids = _router(p, cfg, xf)                  # (T,k)

    flat_ids = ids.reshape(T * k)                 # expert id per slot
    order = jnp.argsort(flat_ids)                 # stable, groups by expert
    sorted_ids = flat_ids[order]
    # rank of each sorted slot within its expert group
    pos = jnp.arange(T * k, dtype=jnp.int32)
    first = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    rank_sorted = pos - first
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)  # unsorted

    keep = rank < cap
    slot = jnp.where(keep, flat_ids * cap + rank, E * cap)  # drop -> sentinel

    token_of_slot = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    buf = jnp.zeros((E * cap + 1, d), x.dtype)
    buf = buf.at[slot].set(xf[token_of_slot], mode="drop")
    xe = buf[:E * cap].reshape(E, cap, d)

    ye = _expert_ffn(p, cfg, xe).reshape(E * cap, d)
    ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], axis=0)

    gathered = ye[slot]                           # (T*k, d); dropped -> 0
    weighted = gathered * w.reshape(T * k, 1).astype(x.dtype)
    out = jnp.sum(weighted.reshape(T, k, d), axis=1)
    return out.reshape(b, s, d)


GROUP_TOKENS = 512      # GShard grouping: bounds the (G,S,E,C) dispatch
                        # tensor (SSPerf cell b2: ungrouped one-hot at 1M
                        # tokens built a (1M,128,82k) dispatch = refuted)


def _moe_onehot(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """GShard-style grouped one-hot einsum dispatch."""
    m = cfg.moe
    b, s, d = x.shape
    T = b * s
    k, E = m.top_k, m.n_experts
    if T > GROUP_TOKENS and T % GROUP_TOKENS == 0:
        return _moe_onehot_grouped(p, cfg, x)
    cap = max(8, int(math.ceil(T * k / E * m.capacity_factor)))

    xf = x.reshape(T, d)
    w, ids = _router(p, cfg, xf)

    onehot = jax.nn.one_hot(ids, E, dtype=jnp.float32)         # (T,k,E)
    # rank over the flattened (T*k) slot order so slots never collide on
    # the same capacity column (matches the gather dispatch ordering)
    flat_oh = onehot.reshape(T * k, E)
    pos_flat = jnp.cumsum(flat_oh, axis=0) - flat_oh           # (T*k,E)
    pos_in_e = jnp.sum(pos_flat.reshape(T, k, E) * onehot, axis=-1)  # (T,k)
    keep = pos_in_e < cap
    cap_oh = jax.nn.one_hot(jnp.where(keep, pos_in_e, cap), cap,
                            dtype=jnp.float32)                 # (T,k,C)
    dispatch = jnp.einsum("tke,tkc->tec", onehot, cap_oh)      # (T,E,C)
    combine = jnp.einsum("tk,tke,tkc->tec", w, onehot, cap_oh)

    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xf)
    ye = _expert_ffn(p, cfg, xe)
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), ye)
    return out.reshape(b, s, d)


def _moe_onehot_grouped(p: Params, cfg: ModelConfig, x: jax.Array
                        ) -> jax.Array:
    """Grouped GShard dispatch: tokens split into groups of GROUP_TOKENS,
    capacity per group — the dispatch/combine tensors stay
    (G, S_g, E, C_g) with C_g ~ S_g*k/E, and every einsum partitions
    cleanly (G over batch/data, E over model)."""
    m = cfg.moe
    b, s, d = x.shape
    T = b * s
    k, E = m.top_k, m.n_experts
    Sg = GROUP_TOKENS
    G = T // Sg
    cap = max(8, int(math.ceil(Sg * k / E * m.capacity_factor)))

    xg = x.reshape(G, Sg, d)
    w, ids = _router(p, cfg, xg.reshape(T, d))
    w = w.reshape(G, Sg, k)
    ids = ids.reshape(G, Sg, k)

    onehot = jax.nn.one_hot(ids, E, dtype=jnp.float32)      # (G,Sg,k,E)
    flat_oh = onehot.reshape(G, Sg * k, E)
    pos = jnp.cumsum(flat_oh, axis=1) - flat_oh
    pos_in_e = jnp.sum(pos.reshape(G, Sg, k, E) * onehot, axis=-1)
    keep = pos_in_e < cap
    cap_oh = jax.nn.one_hot(jnp.where(keep, pos_in_e, cap), cap,
                            dtype=jnp.float32)              # (G,Sg,k,C)
    dispatch = jnp.einsum("gske,gskc->gsec", onehot, cap_oh)
    combine = jnp.einsum("gsk,gske,gskc->gsec", w, onehot, cap_oh)

    xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xg)
    act = ACTIVATIONS[cfg.ffn_act]
    gme = jnp.einsum("gecd,edf->gecf", xe, deq(p["we_gate"]).astype(x.dtype))
    ume = jnp.einsum("gecd,edf->gecf", xe, deq(p["we_up"]).astype(x.dtype))
    ye = jnp.einsum("gecf,efd->gecd", act(gme) * ume,
                    deq(p["we_down"]).astype(x.dtype))
    out = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), ye)
    return out.reshape(b, s, d)


def moe_ffn(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    m = cfg.moe
    if m.dispatch == "onehot":
        out = _moe_onehot(p, cfg, x)
    else:
        out = _moe_gather(p, cfg, x)
    if m.n_shared_experts > 0:
        act = ACTIVATIONS[cfg.ffn_act]
        shared = qmm(act(qmm(x, p["ws_gate"])) * qmm(x, p["ws_up"]),
                     p["ws_down"])
        out = out + shared
    return out


def ffn_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    if cfg.moe is not None:
        return moe_specs(cfg)
    return dense_ffn_specs(cfg)


def ffn_forward(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.moe is not None:
        return moe_ffn(p, cfg, x)
    return dense_ffn(p, cfg, x)
