"""Dense GQA decoders (Qwen2 / Phi-3 / Llama) in int4: a model family.

A family is every step of a run that depends on the architecture.  A
configuration names its family (`"family"` in its file) and
`cells.family` loads this module by that name.  Each family module
exposes:

    dims(cfg)            the sizes of a configuration file, as a dict with
                         at least `v` (the vocabulary traffic draws from)
                         and `L` (layers)
    all_leaves(cfg, root)
                         every weight leaf from the root key, in the type
                         it is served in; jittable with `root` traced, so
                         one compiled program serves every seed
    layer_leaves(cfg, root, layer), global_leaves(cfg, root)
                         the same values one layer, or the leaves outside
                         the layers, at a time: what the reference reads
    model_config(cfg)    the program's `ModelConfig`
    program_params(cfg, leaves, model)
                         the program's parameter tree over `all_leaves`
                         (`program.param_tree` checks it against the
                         model's own specs)
    decode_work(dims, contexts, steps, lanes, kv_bytes)
                         {"flops", "bytes"} of `steps` decode steps that
                         produced tokens with these live `contexts`, on
                         an engine of `lanes` lanes; what `decode_mfu_pct`
                         and `decode_roofline_pct` read
    REFERENCE            the name of a module under `bench/reference/`
                         that exposes `served_gap(cfg, seed, seqs)` and
                         `control_gap(cfg, seed, seqs)`

Here: per layer q, k, v, o and SwiGLU gate, up, down matrices, packed
int4 (`weights.packed`); bfloat16 norm gains and, where the config has
them, q/k/v biases; an int4 embedding grouped along d and, untied, an
int4 head.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

import program
import weights as W
from work import paged_flash_attention as attn

REFERENCE = "dense_gqa"

# leaf ids: part of the key derivation, so never renumber one
LAYER_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
IDS = {name: i for i, name in enumerate(
    ("embed", "head", "ln_final") + LAYER_MATRICES
    + ("bq", "bk", "bv", "ln_attn", "ln_ffn"))}


def dims(cfg: Dict) -> Dict[str, int]:
    """Sizes of a dense GQA config given in Hugging Face key names."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "g": cfg["num_key_value_heads"], "hd": hd,
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "tied": bool(cfg["tie_word_embeddings"]),
            "bias": bool(cfg.get("attention_bias", False))}


def matrix_shapes(s: Dict) -> Dict[str, tuple]:
    """(K, N) of each per-layer matrix of the sizes `s` (`dims`); K is
    the contraction axis."""
    d, h, g, hd, f = s["d"], s["h"], s["g"], s["hd"], s["f"]
    return {"wq": (d, h * hd), "wk": (d, g * hd), "wv": (d, g * hd),
            "wo": (h * hd, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


def _key(root: jax.Array, name: str, layer) -> jax.Array:
    return W.leaf_key(root, IDS, name, layer)


def _layer(cfg: Dict, root: jax.Array, layer) -> Dict:
    s = dims(cfg)
    out = {}
    for name, (k, n) in matrix_shapes(s).items():
        out[name] = W.packed(_key(root, name, layer), k, n,
                             1.0 / math.sqrt(k))
    for name in ("ln_attn", "ln_ffn"):
        out[name] = (1.0 + 0.1 * jax.random.normal(
            _key(root, name, layer), (s["d"],))).astype(jnp.bfloat16)
    if s["bias"]:
        for name, n in (("bq", s["h"] * s["hd"]), ("bk", s["g"] * s["hd"]),
                        ("bv", s["g"] * s["hd"])):
            out[name] = (0.5 * jax.random.normal(
                _key(root, name, layer), (n,))).astype(jnp.bfloat16)
    return out


def _globals(cfg: Dict, root: jax.Array) -> Dict:
    s = dims(cfg)
    d, v = s["d"], s["v"]
    # the embedding is grouped along d (its rows are gathered whole), and
    # its element std d**-0.5 gives unit-scale logits when it is tied
    codes, scales = W.packed(_key(root, "embed", 0), d, v, d ** -0.5)
    out = {"embed": (codes.T, scales.T),
           "ln_final": (1.0 + 0.1 * jax.random.normal(
               _key(root, "ln_final", 0), (d,))).astype(jnp.bfloat16)}
    if not s["tied"]:
        out["head"] = W.packed(_key(root, "head", 0), d, v, d ** -0.5)
    return out


def all_leaves(cfg: Dict, root: jax.Array) -> Dict:
    """Every leaf, layers stacked on a leading axis.  Jit it with `root`
    traced, so one compiled program serves every seed."""
    layers = jax.vmap(lambda l: _layer(cfg, root, l))(
        jnp.arange(dims(cfg)["L"]))
    return {"layers": layers, **_globals(cfg, root)}


def layer_leaves(cfg: Dict, root: jax.Array, layer) -> Dict:
    """The leaves of one layer, equal to `all_leaves(...)["layers"]`
    sliced at `layer`."""
    return _layer(cfg, root, layer)


def global_leaves(cfg: Dict, root: jax.Array) -> Dict:
    return _globals(cfg, root)


def model_config(cfg: Dict):
    """The program's ModelConfig for a configuration file: the registry
    entry named by `arch`, with every size set from the file."""
    from repro.configs import get_config
    s = dims(cfg)
    return get_config(cfg["arch"]).replace(
        n_layers=s["L"], d_model=s["d"], n_heads=s["h"], n_kv_heads=s["g"],
        head_dim=s["hd"], d_ff=s["f"], vocab=s["v"], qkv_bias=s["bias"],
        tie_embeddings=s["tied"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype="bfloat16", remat=False)


def program_params(cfg: Dict, leaves: Dict, model) -> Dict:
    """The program's parameter tree over the benchmark's leaves."""
    s = dims(cfg)
    L = s["L"]
    lay = leaves["layers"]
    shp = matrix_shapes(s)
    attn_p = {k: program.qtensor(*lay[k], (L, *shp[k]))
              for k in ("wq", "wk", "wv", "wo")}
    if s["bias"]:
        attn_p.update({k: lay[k] for k in ("bq", "bk", "bv")})
    ffn = {k: program.qtensor(*lay[k], (L, *shp[k]))
           for k in ("w_gate", "w_up", "w_down")}
    params = {
        "embed": program.qtensor(*leaves["embed"], (s["v"], s["d"]),
                                 axis=-1),
        "ln_final": {"scale": leaves["ln_final"]},
        "blocks": {"ln_attn": {"scale": lay["ln_attn"]}, "attn": attn_p,
                   "ln_ffn": {"scale": lay["ln_ffn"]}, "ffn": ffn},
    }
    if not s["tied"]:
        params["head"] = program.qtensor(*leaves["head"], (s["d"], s["v"]))
    return params


# Work of decode steps of the whole model.
#
# FLOPs: 2 per weight of every layer's projections and FFN and of the
# head for each decoded token, plus its attention over its live context.
# The engine computes every lane of `max_batch`, but only live tokens
# count.  Bytes, per step: every layer's packed weights and the head as
# stored (codes and f32 scales; the norm gains and biases in bfloat16),
# the f32 logits of every lane written out; per token: its live KV rows
# read (`paged_flash_attention`) and its new K and V rows written.  The
# embedding table is not streamed: a step gathers one row per lane.

def _packed_bytes(k: int, n: int) -> int:
    return k * n // 2 + 4 * (k // W.GROUP) * n


def streamed_bytes(dims: Dict) -> int:
    """Weights a decode step reads once, as stored."""
    d, L = dims["d"], dims["L"]
    mats = L * sum(_packed_bytes(k, n)
                   for k, n in matrix_shapes(dims).values())
    small = 2 * (2 * d * L + d)
    if dims["bias"]:
        small += 2 * (dims["h"] + 2 * dims["g"]) * dims["hd"] * L
    return mats + _packed_bytes(d, dims["v"]) + small


def matmul_weights(dims: Dict) -> int:
    return (sum(k * n for k, n in matrix_shapes(dims).values()) * dims["L"]
            + dims["d"] * dims["v"])


def decode_work(dims: Dict, contexts: Sequence[int], steps: int,
                lanes: int, kv_bytes: int) -> Dict:
    """`steps` decode steps that produced tokens with these `contexts`;
    `lanes` is the engine's `max_batch` (rows of logits written)."""
    a = attn.total(dims, contexts, kv_bytes)
    row = dims["hd"] * kv_bytes + (attn.SCALE_BYTES if kv_bytes == 1 else 0)
    new_rows = 2 * len(contexts) * dims["g"] * row * dims["L"]
    per_step = streamed_bytes(dims) + 4 * lanes * dims["v"]
    return {"flops": 2.0 * matmul_weights(dims) * len(contexts) + a["flops"],
            "bytes": float(steps * per_step + a["bytes"] + new_rows)}
