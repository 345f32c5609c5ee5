"""Seeded int4 weights of a dense GQA decoder, made on the device.

The benchmark makes the weights itself, in the type they are served in:
int4 codes packed two to a byte along the contraction axis (the even row
in the low nibble, the odd row in the high nibble, each stored as
code + 8) with one float32 scale per (group of 128 rows, column) holding
an f16-exact value.  Norm gains and attention biases are bfloat16.

Every leaf of every layer comes from its own key, `fold_in(fold_in(root,
leaf), layer)`, so the reference regenerates one layer at a time
(`layer_leaves`) and gets the same values the program was handed
(`all_leaves`), without taking anything from the program.

Codes are uniform over -7..7 with 0 twice as likely (nibble 0, code -8,
maps to code 0), so every weight column has mean zero; the scale spread
of +-25% per group gives the groups different ranges, as a quantized
checkpoint has.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

GROUP = 128
CODE_STD = math.sqrt(2 * sum(k * k for k in range(1, 8)) / 16)   # 4.18

# leaf ids: part of the key derivation, so never renumber one
LAYER_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_IDS = {name: i for i, name in enumerate(
    ("embed", "head", "ln_final") + LAYER_MATRICES
    + ("bq", "bk", "bv", "ln_attn", "ln_ffn"))}


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, the bits above 32 included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


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


def matrix_shapes(cfg: Dict) -> Dict[str, tuple]:
    """(K, N) of each per-layer matrix; K is the contraction axis."""
    s = dims(cfg)
    d, h, g, hd, f = s["d"], s["h"], s["g"], s["hd"], s["f"]
    return {"wq": (d, h * hd), "wk": (d, g * hd), "wv": (d, g * hd),
            "wo": (h * hd, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


def _packed(key, k: int, n: int, std: float):
    """(k/2, n) packed codes and (k/GROUP, n) scales for a (k, n) matrix
    of element std `std`, grouped along k."""
    kb, ks = jax.random.split(key)
    b = jax.random.bits(kb, (k // 2, n), jnp.uint8)
    lo, hi = b & 15, b >> 4
    lo = jnp.where(lo == 0, jnp.uint8(8), lo)
    hi = jnp.where(hi == 0, jnp.uint8(8), hi)
    codes = lo | (hi << 4)
    spread = jax.random.uniform(ks, (k // GROUP, n), jnp.float32, 0.75, 1.25)
    scales = (spread * (std / CODE_STD)).astype(jnp.float16)
    return codes, scales.astype(jnp.float32)


def _leaf_key(root: jax.Array, name: str, layer) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(root, _IDS[name]), layer)


def _layer(cfg: Dict, root: jax.Array, layer) -> Dict:
    s = dims(cfg)
    out = {}
    for name, (k, n) in matrix_shapes(cfg).items():
        out[name] = _packed(_leaf_key(root, name, layer), k, n,
                            1.0 / math.sqrt(k))
    for name in ("ln_attn", "ln_ffn"):
        out[name] = (1.0 + 0.1 * jax.random.normal(
            _leaf_key(root, name, layer), (s["d"],))).astype(jnp.bfloat16)
    if s["bias"]:
        for name, n in (("bq", s["h"] * s["hd"]), ("bk", s["g"] * s["hd"]),
                        ("bv", s["g"] * s["hd"])):
            out[name] = (0.5 * jax.random.normal(
                _leaf_key(root, name, layer), (n,))).astype(jnp.bfloat16)
    return out


def _globals(cfg: Dict, root: jax.Array) -> Dict:
    s = dims(cfg)
    d, v = s["d"], s["v"]
    # the embedding is grouped along d (its rows are gathered whole), and
    # its element std d**-0.5 gives unit-scale logits when it is tied
    codes, scales = _packed(_leaf_key(root, "embed", 0), d, v, d ** -0.5)
    out = {"embed": (codes.T, scales.T),
           "ln_final": (1.0 + 0.1 * jax.random.normal(
               _leaf_key(root, "ln_final", 0), (d,))).astype(jnp.bfloat16)}
    if not s["tied"]:
        out["head"] = _packed(_leaf_key(root, "head", 0), d, v, d ** -0.5)
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


def unpack(codes: jax.Array, scales: jax.Array) -> jax.Array:
    """(k/2, n) packed codes and (k/GROUP, n) scales -> (k, n) float32."""
    lo = (codes & 15).astype(jnp.float32) - 8.0
    hi = (codes >> 4).astype(jnp.float32) - 8.0
    q = jnp.stack([lo, hi], axis=1).reshape(2 * codes.shape[0],
                                            codes.shape[1])
    k, n = q.shape
    w = q.reshape(k // GROUP, GROUP, n) * scales[:, None, :]
    return w.reshape(k, n)
