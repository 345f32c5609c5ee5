"""GQA decoders whose FFN is routed experts, in int4: a model family that
lives only with the benchmark's own tests.

It shows that an architecture other than dense GQA enters the benchmark
through its own files (this module, its reference
`fixture_references/tiny_moe_ref.py` and a configuration that names
it) and no edit elsewhere.  The contract is that of
`bench/families/dense_gqa.py`.

Per layer: q, k, v, o matrices; a bfloat16 router (d, experts); per
expert gate, up and down matrices, stacked on an expert axis; bfloat16
norm gains.  Routing is top-k of the router's softmax, renormalized,
as the program computes it; the capacity factor experts / top-k makes
every expert's capacity hold every token, so no token is dropped and a
served token never depends on its batch-mates.

The router's logits are wide (std ROUTER_STD).  The program routes on
bfloat16 activations and the reference on float32 ones, so where two
experts nearly tie for the last place in the top k they can pick
different ones.  At std 1 that swapped a whole expert's share of the
output: a sound tiny run read a widest gap of 1.78, as high as its
control.  At std 8 the expert that can swap carries little weight after
renormalization: sound runs read 0-0.18 over 15 seeds, the control
0.58-3.63.  A served routed model meets the same ties at its own router
scale.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

import program
import weights as W
from work import paged_flash_attention as attn

REFERENCE = "tiny_moe_ref"
ROUTER_STD = 8.0        # of a router logit: see above

MATRICES = ("wq", "wk", "wv", "wo")
EXPERTS = ("we_gate", "we_up", "we_down")
IDS = {name: i for i, name in enumerate(
    ("embed", "head", "ln_final", "router") + MATRICES + EXPERTS
    + ("ln_attn", "ln_ffn"))}


def dims(cfg: Dict) -> Dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "g": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "e": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "fe": cfg["moe_intermediate_size"], "v": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "tied": bool(cfg["tie_word_embeddings"]), "bias": False}


def matrix_shapes(s: Dict) -> Dict[str, tuple]:
    """(K, N) of each attention matrix and of each expert's matrices."""
    d, h, g, hd, fe = s["d"], s["h"], s["g"], s["hd"], s["fe"]
    return {"wq": (d, h * hd), "wk": (d, g * hd), "wv": (d, g * hd),
            "wo": (h * hd, d), "we_gate": (d, fe), "we_up": (d, fe),
            "we_down": (fe, d)}


def _key(root, name, layer):
    return W.leaf_key(root, IDS, name, layer)


def _gain(key, d):
    return (1.0 + 0.1 * jax.random.normal(key, (d,))).astype(jnp.bfloat16)


def _layer(cfg: Dict, root, layer) -> Dict:
    s = dims(cfg)
    shp = matrix_shapes(s)
    out = {}
    for name in MATRICES:
        k, n = shp[name]
        out[name] = W.packed(_key(root, name, layer), k, n, 1 / math.sqrt(k))
    for name in EXPERTS:
        k, n = shp[name]
        keys = jax.random.split(_key(root, name, layer), s["e"])
        out[name] = jax.vmap(
            lambda key: W.packed(key, k, n, 1 / math.sqrt(k)))(keys)
    out["router"] = (jax.random.normal(_key(root, "router", layer),
                                       (s["d"], s["e"]))
                     * (ROUTER_STD / math.sqrt(s["d"]))).astype(jnp.bfloat16)
    for name in ("ln_attn", "ln_ffn"):
        out[name] = _gain(_key(root, name, layer), s["d"])
    return out


def global_leaves(cfg: Dict, root) -> Dict:
    s = dims(cfg)
    d, v = s["d"], s["v"]
    codes, scales = W.packed(_key(root, "embed", 0), d, v, d ** -0.5)
    out = {"embed": (codes.T, scales.T),
           "ln_final": _gain(_key(root, "ln_final", 0), d)}
    if not s["tied"]:
        out["head"] = W.packed(_key(root, "head", 0), d, v, d ** -0.5)
    return out


def all_leaves(cfg: Dict, root) -> Dict:
    layers = jax.vmap(lambda l: _layer(cfg, root, l))(
        jnp.arange(dims(cfg)["L"]))
    return {"layers": layers, **global_leaves(cfg, root)}


def layer_leaves(cfg: Dict, root, layer) -> Dict:
    return _layer(cfg, root, layer)


def model_config(cfg: Dict):
    from repro.configs import get_config
    from repro.models import MoEConfig
    s = dims(cfg)
    return get_config(cfg["arch"]).replace(
        family="moe", n_layers=s["L"], d_model=s["d"], n_heads=s["h"],
        n_kv_heads=s["g"], head_dim=s["hd"], d_ff=s["fe"], vocab=s["v"],
        qkv_bias=False, qk_norm=False, tie_embeddings=s["tied"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype="bfloat16", remat=False,
        moe=MoEConfig(n_experts=s["e"], top_k=s["k"], n_shared_experts=0,
                      d_ff_expert=s["fe"],
                      capacity_factor=s["e"] / s["k"], dispatch="gather"))


def program_params(cfg: Dict, leaves: Dict, model) -> Dict:
    s = dims(cfg)
    L, e = s["L"], s["e"]
    lay = leaves["layers"]
    shp = matrix_shapes(s)
    params = {
        "embed": program.qtensor(*leaves["embed"], (s["v"], s["d"]),
                                 axis=-1),
        "ln_final": {"scale": leaves["ln_final"]},
        "blocks": {
            "ln_attn": {"scale": lay["ln_attn"]},
            "attn": {k: program.qtensor(*lay[k], (L, *shp[k]))
                     for k in MATRICES},
            "ln_ffn": {"scale": lay["ln_ffn"]},
            "ffn": {"router": lay["router"],
                    **{k: program.qtensor(*lay[k], (L, e, *shp[k]))
                       for k in EXPERTS}}},
    }
    if not s["tied"]:
        params["head"] = program.qtensor(*leaves["head"], (s["d"], s["v"]))
    return params


def _packed_bytes(k: int, n: int) -> int:
    return k * n // 2 + 4 * (k // W.GROUP) * n


def decode_work(dims: Dict, contexts: Sequence[int], steps: int,
                lanes: int, kv_bytes: int) -> Dict:
    """FLOPs: per decoded token, 2 per weight of the attention matrices,
    the router, the top-k experts' matrices and the head, plus its
    attention.  Bytes: per step every weight as stored (every expert:
    a step's lanes route to most of them) and the logits of every lane;
    the live KV rows read and the new rows written."""
    s = dims
    shp = matrix_shapes(s)
    a = attn.total(s, contexts, kv_bytes)
    att = [shp[k] for k in MATRICES]
    exp = [shp[k] for k in EXPERTS]
    per_token = (sum(k * n for k, n in att) + s["d"] * s["e"]
                 + s["k"] * sum(k * n for k, n in exp)) * s["L"] \
        + s["d"] * s["v"]
    weights = (sum(_packed_bytes(k, n) for k, n in att)
               + s["e"] * sum(_packed_bytes(k, n) for k, n in exp)
               + 2 * s["d"] * s["e"] + 2 * 2 * s["d"]) * s["L"] \
        + _packed_bytes(s["d"], s["v"]) + 2 * s["d"]
    row = s["hd"] * kv_bytes + (attn.SCALE_BYTES if kv_bytes == 1 else 0)
    new_rows = 2 * len(contexts) * s["g"] * row * s["L"]
    return {"flops": 2.0 * per_token * len(contexts) + a["flops"],
            "bytes": float(steps * (weights + 4 * lanes * s["v"])
                           + a["bytes"] + new_rows)}
