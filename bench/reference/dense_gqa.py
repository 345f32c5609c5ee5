"""Plain float32 reference of a dense GQA decoder (Qwen2 / Phi-3 / Llama).

Pre-norm blocks: RMSNorm, attention with grouped KV heads (query head h
reads KV head h // (heads / kv_heads)), optional q/k/v biases, rotary
embedding over split halves, causal softmax; RMSNorm, SwiGLU
(silu(x W_gate) * (x W_up)) W_down; a final RMSNorm and the head (the
embedding's transpose when tied).  Weights are the int4 codes times their
scales from the `dense_gqa` family, in float32; every matmul runs at the
highest precision.  It imports nothing of the program under test and
takes nothing it made: the weights are regenerated here, one layer at a
time, from the seed (`reference/common.py`).

`common.served_logits` teacher-forces each prompt with its served tokens
through `_block` and returns the logits at the positions that produced
them.  With `control=True` it computes one precision step below each
precision the configuration states: every matmul input is rounded to float8 e4m3
under a per-row scale (the served activations are bfloat16), and K and
V to int4 under a per-row, per-head scale (the served KV is int8).
"""
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
    """One decoder layer over one padded sequence x (T, d)."""
    c = dict(cfg)
    fam = cells.family(c)
    s = fam.dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    rnd = C.fp8 if control else C.same
    mat = {k: W.unpack(*lw[k]) for k in fam.LAYER_MATRICES}
    x = x + C.gqa_attention(x, lw, mat, s, eps, theta, control)
    h = rnd(C.rms(x, lw["ln_ffn"].astype(jnp.float32), eps))
    g, u = h @ mat["w_gate"], h @ mat["w_up"]
    return x + rnd(jax.nn.silu(g) * u) @ mat["w_down"]


def served_gap(cfg: Dict, seed: int, seqs: Sequence[Dict]) -> Dict:
    """How far each served token's reference logit lies below the best
    at its position; `widest_gap` is what decides `correct`."""
    return C.served_gap(cfg, seed, seqs, _block)


def control_gap(cfg: Dict, seed: int, seqs: Sequence[Dict]) -> Dict:
    """The same reading for the control (float8 matmul inputs, int4 K
    and V), with the program's own reading beside it (`served`)."""
    return C.control_gap(cfg, seed, seqs, _block)
