"""Plain float32 reference of the `tiny_moe` test family: pre-norm GQA
attention (`reference/common.py`), then routed experts: the router's
softmax over the experts, its top k renormalized to sum to one, and
the weighted sum of those experts' SwiGLU outputs.  With `control=True`
the matmul inputs are rounded to float8 and K and V to int4, as in the
dense reference."""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

import cells
import weights as W
from reference import common as C


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _block(x, lw, cfg, control: bool):
    c = dict(cfg)
    fam = cells.family(c)
    s = fam.dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    rnd = C.fp8 if control else C.same
    mat = {k: W.unpack(*lw[k]) for k in fam.MATRICES}
    x = x + C.gqa_attention(x, lw, mat, s, eps, theta, control)
    h = rnd(C.rms(x, lw["ln_ffn"].astype(jnp.float32), eps))
    probs = jax.nn.softmax(h @ lw["router"].astype(jnp.float32), axis=-1)
    w, ids = jax.lax.top_k(probs, s["k"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    gate, up, down = (jax.vmap(W.unpack)(*lw[k]) for k in fam.EXPERTS)
    y = jax.nn.silu(jnp.einsum("td,edf->etf", h, gate)) \
        * jnp.einsum("td,edf->etf", h, up)
    y = jnp.einsum("etf,efd->etd", rnd(y), down)           # (E, T, d)
    picked = y[ids, jnp.arange(x.shape[0])[:, None]]       # (T, k, d)
    return x + jnp.einsum("tk,tkd->td", w, picked)


def served_gap(cfg: Dict, seed: int, seqs: Sequence[Dict]) -> Dict:
    return C.served_gap(cfg, seed, seqs, _block)


def control_gap(cfg: Dict, seed: int, seqs: Sequence[Dict]) -> Dict:
    return C.control_gap(cfg, seed, seqs, _block)
