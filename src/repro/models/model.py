"""Unified decoder-only LM across all assigned architecture families.

API:
    model = DecoderLM(cfg)
    specs  = model.param_specs()              # ParamSpec pytree
    params = init_params(specs, key)          # materialize (smoke/examples)
    loss   = model.loss(params, batch)        # training loss
    logits, cache = model.prefill(params, inputs)
    logits, cache = model.decode_step(params, cache, inputs, pos)
    cache_specs   = model.cache_specs(batch, max_seq)  # ParamSpec pytree

All paths are pure jnp/lax — lowerable under pjit on any mesh; sharding
comes from ParamSpec logical axes + dist.constrain boundary hints.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.shard import constrain
from repro.kernels.ops import qmatmul_xla as qmm
from repro.quant.qarray import QTensor, dequant_rows, maybe_dequantize as deq

from .attention import empty_cache_spec, paged_cache_spec
from .blocks import (mamba_block, mamba_block_decode, mamba_block_serve,
                     mamba_block_specs, mlstm_block, mlstm_block_decode,
                     mlstm_block_serve, mlstm_block_specs, norm_specs,
                     apply_norm, slstm_block, slstm_block_decode,
                     slstm_block_serve, slstm_block_specs, transformer_block,
                     transformer_block_decode, transformer_block_paged,
                     transformer_block_specs, zamba_lora_specs,
                     zamba_shared_block, zamba_shared_block_decode,
                     zamba_shared_block_paged, zamba_shared_specs)
from .common import (BATCH, FSDP, KV_SEQ, NONE, TP, ParamSpec,
                     cross_entropy_loss, init_params, param_count,
                     scan_layers, softcap, stack_specs)
from .config import ModelConfig
from .ssm import mamba2_cache_spec, mlstm_cache_spec, slstm_cache_spec

Params = Dict[str, Any]


def _cache_param_specs(struct_tree, batch_axes_map) -> Any:
    """ShapeDtypeStruct tree + per-leaf-name axes -> ParamSpec tree."""
    return jax.tree_util.tree_map(
        lambda s, ax: ParamSpec(tuple(s.shape), s.dtype, ax, init="zeros"),
        struct_tree, batch_axes_map)


class DecoderLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ==================================================================
    # parameter specs
    # ==================================================================
    def param_specs(self) -> Params:
        cfg = self.cfg
        sp: Params = {}
        # (for frontend-stub archs the table still serves as the LM head)
        sp["embed"] = ParamSpec((cfg.vocab, cfg.d_model), axes=(TP, FSDP),
                                init="embed", scale=cfg.d_model ** -0.5)
        if not cfg.tie_embeddings:
            sp["head"] = ParamSpec((cfg.d_model, cfg.vocab), axes=(FSDP, TP))
        sp["ln_final"] = norm_specs(cfg)

        if cfg.family in ("dense", "moe"):
            n_first = (cfg.moe.first_dense_layers
                       if (cfg.moe and cfg.moe.first_dense_layers) else 0)
            if n_first:
                dense_ff = getattr(cfg.moe, "first_dense_d_ff", cfg.d_ff)
                sp["first_blocks"] = stack_specs(
                    transformer_block_specs(cfg, dense_ffn_override=dense_ff),
                    n_first)
            sp["blocks"] = stack_specs(transformer_block_specs(cfg),
                                       cfg.n_layers - n_first)
        elif cfg.family == "xlstm":
            per = cfg.ssm.slstm_every
            n_groups = cfg.n_layers // per
            assert n_groups * per == cfg.n_layers
            sp["mlstm"] = stack_specs(
                stack_specs(mlstm_block_specs(cfg), per - 1), n_groups)
            sp["slstm"] = stack_specs(slstm_block_specs(cfg), n_groups)
        elif cfg.family == "zamba":
            per = cfg.zamba.shared_every
            n_groups = cfg.n_layers // per
            n_tail = cfg.n_layers - n_groups * per
            sp["mamba"] = stack_specs(
                stack_specs(mamba_block_specs(cfg), per), n_groups)
            if n_tail:
                sp["mamba_tail"] = stack_specs(mamba_block_specs(cfg), n_tail)
            sp["shared"] = zamba_shared_specs(cfg)
            sp["lora"] = stack_specs(zamba_lora_specs(cfg), n_groups)
        else:
            raise ValueError(cfg.family)
        return sp

    def n_params(self) -> int:
        return param_count(self.param_specs())

    # ==================================================================
    # embedding / head
    # ==================================================================
    def _embed(self, params: Params, inputs: Dict[str, jax.Array]
               ) -> jax.Array:
        cfg = self.cfg
        if cfg.embed_inputs:
            emb = params["embed"]
            with jax.named_scope("embed"):
                if isinstance(emb, QTensor):
                    h = dequant_rows(emb, inputs["tokens"],
                                     cfg.activation_dtype())
                else:
                    h = emb[inputs["tokens"]]
        else:
            h = inputs["embeddings"].astype(cfg.activation_dtype())
        if cfg.embed_scale:
            h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
        return h.astype(cfg.activation_dtype())

    def _logits(self, params: Params, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        h = apply_norm(params["ln_final"], cfg, h)
        w = params["embed"] if (cfg.tie_embeddings or "head" not in params) \
            else params["head"]
        with jax.named_scope("lm_head"):
            if isinstance(w, QTensor):
                # fused grouped contraction: the packed vocab table is
                # never materialized in float (the tied table groups
                # along d — the contraction axis — exactly so this works)
                from repro.kernels.ref import ref_qmatmul_fused
                logits = ref_qmatmul_fused(h, w, out_dtype=jnp.float32)
            elif cfg.tie_embeddings or "head" not in params:
                logits = jnp.einsum("bsd,vd->bsv", h, w.astype(h.dtype),
                                    preferred_element_type=jnp.float32)
            else:
                logits = jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype),
                                    preferred_element_type=jnp.float32)
        if cfg.final_softcap:
            logits = softcap(logits, cfg.final_softcap)
        return constrain(logits, "batch", None, "tp")

    def _local_flags(self, n: int) -> jnp.ndarray:
        cfg = self.cfg
        return jnp.array([cfg.is_local_layer(i) for i in range(n)],
                         dtype=bool)

    # ==================================================================
    # full-sequence forward (training / prefill)
    # ==================================================================
    def forward(self, params: Params, inputs: Dict[str, jax.Array],
                return_kv: bool = False):
        cfg = self.cfg
        h = self._embed(params, inputs)
        b, s = h.shape[0], h.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        h = constrain(h, "batch", None, "tp")

        if cfg.family in ("dense", "moe"):
            h, kv = self._forward_transformer(params, h, positions, return_kv)
        elif cfg.family == "xlstm":
            h, kv = self._forward_xlstm(params, h), None
        elif cfg.family == "zamba":
            h, kv = self._forward_zamba(params, h, positions, return_kv)
        logits = self._logits(params, h)
        return (logits, kv) if return_kv else logits

    def _maybe_remat(self, fn):
        return jax.checkpoint(fn, prevent_cse=False) if self.cfg.remat else fn

    def _forward_transformer(self, params, h, positions, return_kv):
        cfg = self.cfg
        n_first = (cfg.moe.first_dense_layers
                   if (cfg.moe and cfg.moe.first_dense_layers) else 0)
        kvs = {}

        if n_first:
            def first_body(x, layer_p):
                x, kv = transformer_block(layer_p, cfg, x, positions,
                                          jnp.bool_(False),
                                          dense_override=True)
                x = constrain(x, "batch", None, "tp")
                return x, kv if return_kv else None
            h, kv_f = scan_layers(self._maybe_remat(first_body), h,
                                  params["first_blocks"], cfg.unroll)
            if return_kv:
                kvs["attn_first"] = kv_f

        flags = self._local_flags(cfg.n_layers)[n_first:]

        def body(x, inp):
            layer_p, is_local = inp
            x, kv = transformer_block(layer_p, cfg, x, positions, is_local)
            x = constrain(x, "batch", None, "tp")
            return x, kv if return_kv else None

        h, kv_main = scan_layers(self._maybe_remat(body), h,
                                 (params["blocks"], flags), cfg.unroll)
        if return_kv:
            kvs["attn"] = kv_main
        return h, kvs

    def _forward_xlstm(self, params, h):
        cfg = self.cfg

        def group_body(x, group_p):
            mlstm_p, slstm_p = group_p

            def inner(xi, lp):
                xi = mlstm_block(lp, cfg, xi)
                return constrain(xi, "batch", None, "tp"), None

            x, _ = scan_layers(self._maybe_remat(inner), x, mlstm_p,
                               cfg.unroll)
            x = slstm_block(slstm_p, cfg, x)
            return constrain(x, "batch", None, "tp"), None

        h, _ = scan_layers(self._maybe_remat(group_body), h,
                           (params["mlstm"], params["slstm"]), cfg.unroll)
        return h

    def _forward_zamba(self, params, h, positions, return_kv):
        cfg = self.cfg
        shared = params["shared"]

        def group_body(x, group_p):
            mamba_p, lora_p = group_p

            def inner(xi, lp):
                xi = mamba_block(lp, cfg, xi)
                return constrain(xi, "batch", None, "tp"), None

            x, _ = scan_layers(self._maybe_remat(inner), x, mamba_p,
                               cfg.unroll)
            x, kv = zamba_shared_block(shared, lora_p, cfg, x, positions)
            return constrain(x, "batch", None, "tp"), \
                kv if return_kv else None

        h, kv = scan_layers(self._maybe_remat(group_body), h,
                            (params["mamba"], params["lora"]), cfg.unroll)

        if "mamba_tail" in params:
            def tail(xi, lp):
                xi = mamba_block(lp, cfg, xi)
                return constrain(xi, "batch", None, "tp"), None
            h, _ = scan_layers(self._maybe_remat(tail), h,
                               params["mamba_tail"], cfg.unroll)
        return h, ({"attn": kv} if return_kv else {})

    # ==================================================================
    # loss
    # ==================================================================
    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        logits = self.forward(params, batch)
        return cross_entropy_loss(logits, batch["labels"])

    # ==================================================================
    # prefill: forward + return caches sized to the prompt
    # ==================================================================
    def prefill(self, params: Params, inputs: Dict[str, jax.Array]):
        logits, kv = self.forward(params, inputs, return_kv=True)
        return logits[:, -1:, :], kv

    # ==================================================================
    # decode
    # ==================================================================
    def decode_step(self, params: Params, cache: Any,
                    inputs: Dict[str, jax.Array], pos: jax.Array):
        """One token for every sequence in the batch.

        inputs: {tokens: (b,1)} or {embeddings: (b,1,d)}; pos: scalar int32.
        cache layout from `cache_specs`.
        """
        cfg = self.cfg
        h = self._embed(params, inputs)
        h = constrain(h, "batch", None, "tp")

        if cfg.family in ("dense", "moe"):
            h, cache = self._decode_transformer(params, h, cache, pos)
        elif cfg.family == "xlstm":
            h, cache = self._decode_xlstm(params, h, cache)
        elif cfg.family == "zamba":
            h, cache = self._decode_zamba(params, h, cache, pos)
        logits = self._logits(params, h)
        return logits, cache

    def _decode_transformer(self, params, h, cache, pos):
        cfg = self.cfg
        n_first = (cfg.moe.first_dense_layers
                   if (cfg.moe and cfg.moe.first_dense_layers) else 0)
        if n_first:
            def first_body(x, inp):
                layer_p, c = inp
                x, c = transformer_block_decode(layer_p, cfg, x, c, pos,
                                                jnp.bool_(False),
                                                dense_override=True)
                return constrain(x, "batch", None, "tp"), c
            h, cf = scan_layers(first_body, h,
                                (params["first_blocks"],
                                 cache["attn_first"]), cfg.unroll)
            cache = dict(cache, attn_first=cf)

        flags = self._local_flags(cfg.n_layers)[n_first:]

        def body(x, inp):
            layer_p, c, is_local = inp
            x, c = transformer_block_decode(layer_p, cfg, x, c, pos, is_local)
            return constrain(x, "batch", None, "tp"), c

        h, cm = scan_layers(body, h, (params["blocks"], cache["attn"],
                                      flags), cfg.unroll)
        return h, dict(cache, attn=cm)

    def _decode_xlstm(self, params, h, cache):
        cfg = self.cfg

        def group_body(x, inp):
            (mlstm_p, slstm_p), (mc, sc) = inp

            def inner(xi, lp_c):
                lp, c = lp_c
                xi, c = mlstm_block_decode(lp, cfg, xi, c)
                return constrain(xi, "batch", None, "tp"), c

            x, mc = scan_layers(inner, x, (mlstm_p, mc), cfg.unroll)
            x, sc = slstm_block_decode(slstm_p, cfg, x, sc)
            return constrain(x, "batch", None, "tp"), (mc, sc)

        h, (mc, sc) = scan_layers(
            group_body, h,
            ((params["mlstm"], params["slstm"]),
             (cache["mlstm"], cache["slstm"])), cfg.unroll)
        return h, dict(cache, mlstm=mc, slstm=sc)

    def _decode_zamba(self, params, h, cache, pos):
        cfg = self.cfg
        shared = params["shared"]

        def group_body(x, inp):
            (mamba_p, lora_p), (mc, ac) = inp

            def inner(xi, lp_c):
                lp, c = lp_c
                xi, c = mamba_block_decode(lp, cfg, xi, c)
                return constrain(xi, "batch", None, "tp"), c

            x, mc = scan_layers(inner, x, (mamba_p, mc), cfg.unroll)
            x, ac = zamba_shared_block_decode(shared, lora_p, cfg, x, ac, pos)
            return constrain(x, "batch", None, "tp"), (mc, ac)

        h, (mc, ac) = scan_layers(
            group_body, h,
            ((params["mamba"], params["lora"]),
             (cache["mamba"], cache["attn"])), cfg.unroll)
        cache = dict(cache, mamba=mc, attn=ac)

        if "mamba_tail" in params:
            def tail(xi, lp_c):
                lp, c = lp_c
                xi, c = mamba_block_decode(lp, cfg, xi, c)
                return constrain(xi, "batch", None, "tp"), c
            h, tc = scan_layers(tail, h, (params["mamba_tail"],
                                          cache["mamba_tail"]), cfg.unroll)
            cache = dict(cache, mamba_tail=tc)
        return h, cache

    # ==================================================================
    # unified decode-state serve step (the serve-v2 runtime path)
    # ==================================================================
    def supports_paged(self) -> bool:
        """True when EVERY decode-state layer is paged attention KV —
        the full paged feature set (prefix sharing, fork/COW,
        speculative decoding) applies.  Families carrying recurrent
        per-lane state (xlstm, zamba) serve through the same engine via
        `serve_step` + a `StateArena`, but those capabilities stay off:
        adopting or rolling back attention pages cannot adopt or roll
        back a recurrent state."""
        return self.cfg.family in ("dense", "moe")

    def has_recurrent_state(self) -> bool:
        """Any layer carrying constant-size per-lane recurrent state
        (conv buffers, SSM/LSTM cells) — served from a `StateArena`."""
        return self.cfg.family in ("xlstm", "zamba")

    def n_paged_layers(self) -> int:
        """Attention layers backed by paged KV pools in `serve_step`
        (zamba: one shared-block invocation per mamba group)."""
        cfg = self.cfg
        if cfg.family in ("dense", "moe"):
            return cfg.n_layers
        if cfg.family == "zamba":
            return cfg.n_layers // cfg.zamba.shared_every
        return 0

    def validate_tp(self, tp: int) -> None:
        """Raise unless every tensor-parallel hot-path dim divides
        evenly across `tp` shards.  `sanitize_pspec` would silently
        replicate a non-dividing dim instead of sharding it — correct,
        but it defeats the point of paying for tp devices, so a
        misconfigured ServeConfig(tp=...) fails loudly here with the
        offending dims named."""
        if tp <= 1:
            return
        cfg = self.cfg
        bad = []
        if cfg.n_heads % tp:
            bad.append(f"n_heads={cfg.n_heads}")
        if cfg.attn_kind != "mla" and cfg.n_kv_heads % tp:
            # MLA keeps one replicated latent pool; there is no sharded
            # KV-head group dim to divide
            bad.append(f"n_kv_heads={cfg.n_kv_heads}")
        if cfg.d_ff % tp:
            bad.append(f"d_ff={cfg.d_ff}")
        if cfg.family == "moe" and cfg.moe and cfg.moe.d_ff_expert % tp:
            bad.append(f"moe.d_ff_expert={cfg.moe.d_ff_expert}")
        if bad:
            raise ValueError(
                f"tp={tp} does not divide the tensor-parallel dims of "
                f"{cfg.name!r}: " + ", ".join(bad)
                + " (pick a tp that divides the head and FFN widths)")

    def paged_step(self, params: Params, cache: Any,
                   inputs: Dict[str, jax.Array], tables: jax.Array,
                   lengths: jax.Array, n_new: jax.Array):
        """Advance a dynamic batch against the paged KV pool.

        inputs: {tokens: (b, s)} — s == 1 is a decode step for the whole
        batch; s > 1 is a chunked BATCH PREFILL (each lane consumes
        `n_new[i] <= s` prompt tokens this call; lanes with n_new == 0
        are inactive padding).  tables: (b, max_pages) page ids per lane;
        lengths: (b,) tokens already in cache per lane.

        Returns (logits (b, s, vocab), cache); the caller samples lane i
        from logits[i, n_new[i] - 1].  Per-lane positions mean one
        lane's writes can never touch another lane's pages.

        Attention-only alias of `serve_step` (kept for the spec drafter
        and kernel tests, which are paged-KV by construction).
        """
        return self._paged_forward(params, cache, inputs, tables, lengths,
                                   n_new, verify=False)

    def serve_step(self, params: Params, cache: Any,
                   inputs: Dict[str, jax.Array], tables: jax.Array,
                   lengths: jax.Array, n_new: jax.Array):
        """Family-agnostic engine step: one call advances a dynamic
        batch for ANY family, s == 1 decode or s > 1 chunked prefill.

        `cache` is the unified per-layer decode state from
        `decode_state_specs`, flattened into one dict: paged KV page
        pools for attention layers (addressed via `tables`/`lengths`,
        exactly `paged_step`) and per-lane StateArena slots for
        recurrent layers (row i of every arena leaf's batch axis is
        lane i).  Recurrent layers derive a (b, s) validity mask from
        `n_new` — masked positions update nothing, so one lane's
        padding can never corrupt another lane's state and lanes may
        enter/leave the batch at any chunk boundary (continuous
        batching for every family).
        """
        cfg = self.cfg
        if cfg.family in ("dense", "moe"):
            return self._paged_forward(params, cache, inputs, tables,
                                       lengths, n_new, verify=False)
        h = self._embed(params, inputs)
        h = constrain(h, "batch", None, "tp")
        s = h.shape[1]
        valid = jnp.arange(s, dtype=jnp.int32)[None, :] < n_new[:, None]
        if cfg.family == "xlstm":
            h, cache = self._serve_xlstm(params, h, cache, valid)
        elif cfg.family == "zamba":
            h, cache = self._serve_zamba(params, h, cache, tables, lengths,
                                         n_new, valid)
        else:
            raise ValueError(cfg.family)
        logits = self._logits(params, h)
        return logits, cache

    def _serve_xlstm(self, params, h, cache, valid):
        cfg = self.cfg

        def group_body(x, inp):
            (mlstm_p, slstm_p), (mc, sc) = inp

            def inner(xi, lp_c):
                lp, c = lp_c
                xi, c = mlstm_block_serve(lp, cfg, xi, c, valid)
                return constrain(xi, "batch", None, "tp"), c

            x, mc = scan_layers(inner, x, (mlstm_p, mc), cfg.unroll)
            x, sc = slstm_block_serve(slstm_p, cfg, x, sc, valid)
            return constrain(x, "batch", None, "tp"), (mc, sc)

        h, (mc, sc) = scan_layers(
            group_body, h,
            ((params["mlstm"], params["slstm"]),
             (cache["mlstm"], cache["slstm"])), cfg.unroll)
        return h, dict(cache, mlstm=mc, slstm=sc)

    def _serve_zamba(self, params, h, cache, tables, lengths, n_new, valid):
        cfg = self.cfg
        shared = params["shared"]
        n_groups = self.n_paged_layers()

        if n_groups:
            def group_body(x, inp):
                (mamba_p, lora_p), (mc, ac) = inp

                def inner(xi, lp_c):
                    lp, c = lp_c
                    xi, c = mamba_block_serve(lp, cfg, xi, c, valid)
                    return constrain(xi, "batch", None, "tp"), c

                x, mc = scan_layers(inner, x, (mamba_p, mc), cfg.unroll)
                x, ac = zamba_shared_block_paged(shared, lora_p, cfg, x, ac,
                                                 tables, lengths, n_new)
                return constrain(x, "batch", None, "tp"), (mc, ac)

            h, (mc, ac) = scan_layers(
                group_body, h,
                ((params["mamba"], params["lora"]),
                 (cache["mamba"], cache["attn"])), cfg.unroll)
            cache = dict(cache, mamba=mc, attn=ac)

        if "mamba_tail" in params:
            def tail(xi, lp_c):
                lp, c = lp_c
                xi, c = mamba_block_serve(lp, cfg, xi, c, valid)
                return constrain(xi, "batch", None, "tp"), c
            h, tc = scan_layers(tail, h, (params["mamba_tail"],
                                          cache["mamba_tail"]), cfg.unroll)
            cache = dict(cache, mamba_tail=tc)
        return h, cache

    def paged_verify_step(self, params: Params, cache: Any,
                          inputs: Dict[str, jax.Array], tables: jax.Array,
                          lengths: jax.Array, n_new: jax.Array):
        """Speculative-decode verify: score a k-token draft window in one
        pass.

        inputs: {tokens: (b, s)} — lane i's row is [last_emitted,
        d_1, ..., d_{n_new[i]-1}, pad...]; `lengths` counts tokens
        already cached (the window's KV rows are written by this call,
        exactly like chunked prefill).  Returns logits (b, s, vocab):
        logits[i, j] is the target distribution for the token AFTER
        window position j — the acceptance rule walks it left to right.
        Identical math to `paged_step` (same intra-window causal mask);
        the difference is routing: attention runs the multi-query flash
        kernel instead of gathering every page the lane owns, which is
        what turns decode GEMV into small-batch GEMM.
        """
        return self._paged_forward(params, cache, inputs, tables, lengths,
                                   n_new, verify=True)

    def _paged_forward(self, params, cache, inputs, tables, lengths, n_new,
                       verify: bool):
        cfg = self.cfg
        assert self.supports_paged(), cfg.family
        h = self._embed(params, inputs)
        h = constrain(h, "batch", None, "tp")

        n_first = (cfg.moe.first_dense_layers
                   if (cfg.moe and cfg.moe.first_dense_layers) else 0)
        if n_first:
            def first_body(x, inp):
                layer_p, c = inp
                x, c = transformer_block_paged(
                    layer_p, cfg, x, c, tables, lengths, n_new,
                    jnp.bool_(False), dense_override=True, verify=verify)
                return constrain(x, "batch", None, "tp"), c
            h, cf = scan_layers(first_body, h,
                                (params["first_blocks"],
                                 cache["attn_first"]), cfg.unroll)
            cache = dict(cache, attn_first=cf)

        flags = self._local_flags(cfg.n_layers)[n_first:]

        def body(x, inp):
            layer_p, c, is_local = inp
            x, c = transformer_block_paged(layer_p, cfg, x, c, tables,
                                           lengths, n_new, is_local,
                                           verify=verify)
            return constrain(x, "batch", None, "tp"), c

        h, cm = scan_layers(body, h, (params["blocks"], cache["attn"],
                                      flags), cfg.unroll)
        logits = self._logits(params, h)
        return logits, dict(cache, attn=cm)

    # ==================================================================
    # cache specs (ParamSpec pytree: shapes + dtypes + logical axes)
    # ==================================================================
    def cache_specs(self, batch: int, max_seq: int,
                    kv_dtype=jnp.bfloat16) -> Any:
        cfg = self.cfg

        def attn_axes(struct):
            if len(struct.shape) == 4:          # (b, S, g, hd)
                return (BATCH, KV_SEQ, NONE, NONE)
            return (BATCH, KV_SEQ, NONE)        # (b, S, r) MLA latent

        def to_spec(struct, axes):
            return ParamSpec(tuple(struct.shape), struct.dtype, axes,
                             init="zeros")

        def stack(spec: ParamSpec, n: int) -> ParamSpec:
            return spec.stacked(n)

        if cfg.family in ("dense", "moe"):
            one = empty_cache_spec(cfg, batch, max_seq, kv_dtype)
            one_specs = {k: to_spec(v, attn_axes(v)) for k, v in one.items()}
            n_first = (cfg.moe.first_dense_layers
                       if (cfg.moe and cfg.moe.first_dense_layers) else 0)
            out = {"attn": {k: stack(v, cfg.n_layers - n_first)
                            for k, v in one_specs.items()}}
            if n_first:
                out["attn_first"] = {k: stack(v, n_first)
                                     for k, v in one_specs.items()}
            return out

        if cfg.family == "xlstm":
            return self.arena_state_specs(batch)

        if cfg.family == "zamba":
            n_groups = cfg.n_layers // cfg.zamba.shared_every
            a_one = {k: to_spec(v, attn_axes(v))
                     for k, v in empty_cache_spec(cfg, batch, max_seq,
                                                  kv_dtype).items()}
            out = dict(self.arena_state_specs(batch))
            out["attn"] = {k: stack(v, n_groups) for k, v in a_one.items()}
            if "mamba" not in out:      # pure-mamba: zero-group stack so
                mb_axes = {"state": (BATCH, TP, NONE, NONE),   # decode_step
                           "conv": (BATCH, NONE, TP)}          # still scans
                out["mamba"] = {
                    k: stack(stack(to_spec(v, mb_axes[k]),
                                   cfg.zamba.shared_every), 0)
                    for k, v in mamba2_cache_spec(cfg, batch).items()}
            return out

        raise ValueError(cfg.family)

    def arena_state_specs(self, batch: int) -> Any:
        """ParamSpec pytree of the RECURRENT per-lane decode state for a
        `batch`-lane StateArena ({} for attention-only families).  Row i
        of every leaf's `BATCH` axis is lane i — the serve engine
        gathers/scatters that axis for lane reset, host save/restore on
        preemption, and admission into a running batch."""
        cfg = self.cfg

        def to_spec(struct, axes):
            # conv ring buffers hold raw activation projections; the
            # serve cells carry them at the promoted dtype (a scan carry
            # is dtype-stable), so the arena starts there — zeros
            # promote exactly, and the engine's jitted step never
            # retraces on a dtype flip
            dt = jnp.promote_types(struct.dtype, cfg.activation_dtype())
            return ParamSpec(tuple(struct.shape), dt, axes, init="zeros")

        if cfg.family == "xlstm":
            per = cfg.ssm.slstm_every
            n_groups = cfg.n_layers // per
            m_axes = {"C": (BATCH, NONE, TP, NONE), "n": (BATCH, NONE, TP),
                      "m": (BATCH, NONE), "conv": (BATCH, NONE, TP)}
            s_axes = {"c": (BATCH, TP), "n": (BATCH, TP), "h": (BATCH, TP),
                      "m": (BATCH, NONE)}
            m_one = {k: to_spec(v, m_axes[k])
                     for k, v in mlstm_cache_spec(cfg, batch).items()}
            s_one = {k: to_spec(v, s_axes[k])
                     for k, v in slstm_cache_spec(cfg, batch).items()}
            return {
                "mlstm": {k: v.stacked(per - 1).stacked(n_groups)
                          for k, v in m_one.items()},
                "slstm": {k: v.stacked(n_groups)
                          for k, v in s_one.items()},
            }

        if cfg.family == "zamba":
            per = cfg.zamba.shared_every
            n_groups = cfg.n_layers // per
            n_tail = cfg.n_layers - n_groups * per
            mb_axes = {"state": (BATCH, TP, NONE, NONE),
                       "conv": (BATCH, NONE, TP)}
            m_one = {k: to_spec(v, mb_axes[k])
                     for k, v in mamba2_cache_spec(cfg, batch).items()}
            out = {}
            if n_groups:
                out["mamba"] = {k: v.stacked(per).stacked(n_groups)
                                for k, v in m_one.items()}
            if n_tail:
                out["mamba_tail"] = {k: v.stacked(n_tail)
                                     for k, v in m_one.items()}
            return out

        return {}

    def paged_cache_specs(self, n_pages: int, page_size: int,
                          kv_dtype=jnp.bfloat16) -> Any:
        """ParamSpec pytree for the paged KV pool: per-layer page pools
        stacked over layers (scan layout), shared by every sequence via
        block tables.  Total KV memory is n_pages * page_size rows —
        sized to the WORKLOAD, not to n_slots * max_seq.  Families
        without attention layers (xlstm, pure-mamba zamba) return {} —
        their whole decode state lives in the StateArena instead."""
        cfg = self.cfg
        n_attn = self.n_paged_layers()
        if n_attn == 0:
            return {}

        def pool_axes(name, struct):
            if len(struct.shape) == 4:          # (n_pages, g, ps, hd)
                return (NONE, TP, NONE, NONE)
            if name.endswith("_scale"):         # (n_pages, g, ps) INT8 scales
                return (NONE, TP, NONE)
            return (NONE, NONE, NONE)           # (n_pages, ps, r) MLA latent

        pool_cfg = cfg
        if cfg.family == "zamba":               # shared attn block's shape
            pool_cfg = cfg.replace(d_ff=cfg.zamba.shared_d_ff, moe=None)
        one = paged_cache_spec(pool_cfg, n_pages, page_size, kv_dtype)
        one_specs = {k: ParamSpec(tuple(v.shape), v.dtype, pool_axes(k, v),
                                  init="zeros") for k, v in one.items()}
        n_first = (cfg.moe.first_dense_layers
                   if (cfg.moe and cfg.moe.first_dense_layers) else 0)
        if cfg.family == "zamba":
            n_first = 0
        out = {"attn": {k: v.stacked(n_attn - n_first)
                        for k, v in one_specs.items()}}
        if n_first:
            out["attn_first"] = {k: v.stacked(n_first)
                                 for k, v in one_specs.items()}
        return out

    def decode_state_specs(self, max_batch: int, n_pages: int,
                           page_size: int, kv_dtype=jnp.bfloat16) -> Any:
        """Unified per-layer decode state for the serve runtime,
        generalizing `paged_cache_specs`:

          {"paged": per-layer KV page pools (attention layers; {} when
                    the family has none),
           "arena": per-lane recurrent-state slots, batch = max_batch
                    ({} for attention-only families)}

        The engine materializes both, flattens them into one cache dict
        for `serve_step`, and owns the host-side bookkeeping (block
        tables for "paged", lane reset/save/restore for "arena")."""
        return {"paged": self.paged_cache_specs(n_pages, page_size,
                                                kv_dtype),
                "arena": self.arena_state_specs(max_batch)}
