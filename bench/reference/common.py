"""What every family's plain reference shares.

The int4 layout of the leaves outside the layers (an embedding grouped
along d, a final norm gain, a head that is the embedding's transpose
when tied), the teacher-forced pass over the served sequences one layer
at a time, the comparison that decides `correct`, and the control's
rounding one precision step down.  A family's reference supplies its
decoder layer, `block(x, lw, cfg, control)` over one padded sequence
`x (T, d)`, jitted with `cfg` (`frozen`) and `control` static.

It imports nothing of the program under test and takes nothing it made:
the weights are regenerated from the seed by the configuration's family
(`layer_leaves`, `global_leaves`), one layer at a time.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import cells
import weights as W

BUCKET = 256            # sequence lengths are padded to a multiple of it
_FP8_MAX = 448.0


def fp8(x: jax.Array) -> jax.Array:
    """Round each row to float8 e4m3 under a per-row scale."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = _FP8_MAX / jnp.maximum(amax, 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def int4(x: jax.Array) -> jax.Array:
    """Round each row (last axis) to symmetric int4 under its own scale."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 7.0
    s = jnp.maximum(s, 1e-30)
    return jnp.clip(jnp.round(x / s), -7.0, 7.0) * s


def same(x: jax.Array) -> jax.Array:
    return x


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (T, heads, hd); rotation of the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def frozen(cfg: Dict) -> tuple:
    """The scalar entries of a config, hashable for a static argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def gqa_attention(x, lw, mat, s: Dict, eps, theta, control: bool):
    """The residual update of a pre-norm attention sublayer with grouped
    KV heads over x (T, d): RMSNorm (`ln_attn`), q/k/v projections with
    optional biases (`bq`, `bk`, `bv` when `s["bias"]`), rotary
    embedding over split halves, causal softmax, the `wo` projection.
    Query head h reads KV head h // (h / g).  `mat` holds the unpacked
    float32 matrices; `s` has `h`, `g`, `hd` and `bias`."""
    rnd = fp8 if control else same
    kv = int4 if control else same
    T = x.shape[0]
    h = rnd(rms(x, lw["ln_attn"].astype(jnp.float32), eps))
    q, k, v = h @ mat["wq"], h @ mat["wk"], h @ mat["wv"]
    if s["bias"]:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    pos = jnp.arange(T)
    q = rope(q.reshape(T, s["h"], s["hd"]), pos, theta)
    k = kv(rope(k.reshape(T, s["g"], s["hd"]), pos, theta))
    v = kv(v.reshape(T, s["g"], s["hd"]))
    rep = s["h"] // s["g"]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(s["hd"]))
    causal = pos[:, None] >= pos[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -1e30), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(T, s["h"] * s["hd"])
    return rnd(o) @ mat["wo"]


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _head(h, gl, cfg, control: bool):
    """Logits (m, V) of final hidden states h (m, d)."""
    c = dict(cfg)
    rnd = fp8 if control else same
    h = rnd(rms(h, gl["ln_final"].astype(jnp.float32), c["rms_norm_eps"]))
    if cells.family(c).dims(c)["tied"]:
        codes, scales = gl["embed"]
        head = W.unpack(codes.T, scales.T)                  # (d, V)
    else:
        head = W.unpack(*gl["head"])
    return h @ head


@jax.jit
def _embed(gl, ids):
    codes, scales = gl["embed"]                 # (V, d/2), (V, d/GROUP)
    return jax.vmap(lambda c, s: W.unpack(c[:, None], s[:, None])[:, 0])(
        codes[ids], scales[ids])


def served_logits(cfg: Dict, seed: int, seqs: Sequence[Dict],
                  block: Callable, control: bool = False
                  ) -> List[np.ndarray]:
    """For each {"prompt": [...], "served": [...]}: the reference logits
    (n_served, V) at the positions that produced each served token,
    every layer being `block`."""
    fam = cells.family(cfg)
    key = frozen(cfg)
    root = W.root_key(seed)
    with jax.default_matmul_precision("highest"):
        gl = jax.jit(functools.partial(fam.global_leaves, cfg))(root)
        xs = []
        for q in seqs:
            toks = np.concatenate([np.asarray(q["prompt"], np.int32),
                                   np.asarray(q["served"][:-1], np.int32)])
            ids = np.zeros(-(-len(toks) // BUCKET) * BUCKET, np.int32)
            ids[:len(toks)] = toks
            xs.append(_embed(gl, jnp.asarray(ids)))
        layer_fn = jax.jit(functools.partial(fam.layer_leaves, cfg))
        for layer in range(fam.dims(cfg)["L"]):
            lw = layer_fn(root, jnp.int32(layer))
            xs = [block(x, lw, key, control) for x in xs]
            del lw
        out = []
        for x, q in zip(xs, seqs):
            p, n = len(q["prompt"]), len(q["served"])
            out.append(np.asarray(_head(x[p - 1:p - 1 + n], gl, key,
                                        control)))
        return out


def widest(ref: List[np.ndarray], picks: List[np.ndarray]) -> Dict:
    """How far each picked token's reference logit lies below the best
    at its position: the widest gap, the mean gap and the token count."""
    gaps = np.concatenate([lg.max(-1) - lg[np.arange(len(t)), t]
                           for lg, t in zip(ref, picks)])
    return {"widest_gap": float(gaps.max()), "tokens": int(gaps.size),
            "mean_gap": float(gaps.mean())}


def served_gap(cfg: Dict, seed: int, seqs: Sequence[Dict],
               block: Callable) -> Dict:
    """The reading of the program's served tokens; `widest_gap` is what
    decides `correct`."""
    ref = served_logits(cfg, seed, seqs, block)
    return widest(ref, [np.asarray(q["served"]) for q in seqs])


def control_gap(cfg: Dict, seed: int, seqs: Sequence[Dict],
                block: Callable) -> Dict:
    """The same reading for the control: at each served position, the
    token the lower-precision reference ranks first, judged by the
    float32 one.  Returns the program's reading too (`served`), from the
    same float32 logits."""
    ref = served_logits(cfg, seed, seqs, block)
    ctl = served_logits(cfg, seed, seqs, block, control=True)
    return {"served": widest(ref, [np.asarray(q["served"]) for q in seqs]),
            "control": widest(ref, [lc.argmax(-1) for lc in ctl])}
