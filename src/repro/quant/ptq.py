"""Post-training quantization of a parameter pytree.

Converts the DRAM-traffic-dominant 2D matmul weights (attention q/k/v/o,
FFN gate/up/down, MoE experts, embedding/LM head) to packed QTensors at
INT4 or INT8 — the serve-path image of EdgeCIM's precision axis.  Norm
scales, biases, gates and other small/1D tensors stay in bf16 (they are
latency-irrelevant: <0.5% of decode bytes, matching the paper's treatment
of auxiliary operators on dedicated units).
"""
from __future__ import annotations

import warnings
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .qarray import QTensor, quantize

# parameter names eligible for quantization (leaf key in the pytree path)
QUANT_KEYS = {
    "wq", "wk", "wv", "wo", "w_dkv", "w_uk", "w_uv",          # attention
    "w_gate", "w_up", "w_down",                               # dense ffn
    "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down",  # moe
    "embed", "head",                                          # vocab
    "in_proj", "out_proj", "up_proj", "down_proj", "w_o",     # ssm blocks
    "ffn_up", "ffn_down",                                     # slstm ffn
}


# one fused program per leaf: eagerly, a stacked (layers, K, N) leaf
# holds several f32 copies of itself at once, more than a 16 GB chip
# has room for beside a 3B model's float weights
_quantize_jit = jax.jit(quantize, static_argnames=("bits", "group", "axis"))


def _leaf_name(path) -> str:
    for p in reversed(path):
        if hasattr(p, "key"):
            return str(p.key)
    return ""


def _pick_group(K: int, group: int, shard_hint: int) -> int:
    """Largest group <= `group` dividing K, preferring group counts
    (K/group) divisible by the tensor-parallel mesh width: misaligned
    group counts force GSPMD to re-gather packed weights around the
    dequant reshape (SSPerf iteration c3, ~400MB/step on qwen2.5-3b).

    Returns 0 when no group >= 8 divides K (e.g. K prime or < 8); the
    caller must skip quantization for that leaf — 0 is a sentinel, not a
    usable group size."""
    best = 0
    for g in range(min(group, K), 7, -1):
        if K % g:
            continue
        if (K // g) % shard_hint == 0:
            return g
        best = best or g
    return best


def _skip_leaf(name: str, K: int) -> None:
    warnings.warn(
        f"ptq: no valid group size for leaf '{name}' (K={K}); "
        "leaving it unquantized", stacklevel=3)


def _quantize_leaf(name: str, x: Any, bits: int, group: int,
                   shard_hint: int = 16) -> Any:
    if not isinstance(x, jax.Array) or name not in QUANT_KEYS:
        return x
    if x.ndim < 2 or not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    # contraction axis: axis 0 for 2D (K, N); axis 1 for batched (E/L, K, N).
    # The embedding table groups along d (axis=1) so row lookups can gather
    # packed rows directly (qarray.dequant_rows).
    axis = 1 if name == "embed" else x.ndim - 2
    K = x.shape[axis]
    g = _pick_group(K, group, shard_hint)
    if not g or K % g != 0 or (bits == 4 and K % 2 != 0):
        # _pick_group returns the 0 sentinel when nothing >= 8 divides K;
        # quantize() would assert/divide by zero on it
        _skip_leaf(name, K)
        return x
    return _quantize_jit(x, bits=bits, group=g, axis=axis)


def quantize_params(params: Any, bits: int = 4, group: int = 128,
                    shard_hint: int = 16) -> Any:
    """Walk the pytree; replace eligible weights with QTensors."""
    def fn(path, x):
        return _quantize_leaf(_leaf_name(path), x, bits, group, shard_hint)
    return jax.tree_util.tree_map_with_path(fn, params)


def quantize_structs(spec_tree: Any, bits: int = 4, group: int = 128,
                     shard_hint: int = 16) -> Any:
    """ParamSpec pytree -> pytree of ShapeDtypeStructs where eligible
    weights become QTensor(structs) — the allocation-free image of
    quantize_params used by the multi-pod dry-run (a 235B model lowers
    quantized without materializing a byte)."""
    import jax as _jax
    from repro.models.common import ParamSpec, is_spec

    def fn(path, s: ParamSpec):
        name = _leaf_name(path)
        shape, dtype = tuple(s.shape), s.dtype
        if (name not in QUANT_KEYS or len(shape) < 2
                or not jnp.issubdtype(dtype, jnp.floating)):
            return s.struct()
        axis = 1 if name == "embed" else len(shape) - 2
        K = shape[axis]
        g = _pick_group(K, group, shard_hint)
        if not g or K % g != 0 or (bits == 4 and K % 2 != 0):
            _skip_leaf(name, K)
            return s.struct()
        dshape = list(shape)
        if bits == 4:
            dshape[axis] //= 2
        sshape = list(shape)
        sshape[axis] = K // g
        return QTensor(
            data=_jax.ShapeDtypeStruct(tuple(dshape),
                                       jnp.uint8 if bits == 4 else jnp.int8),
            scales=_jax.ShapeDtypeStruct(tuple(sshape), jnp.float32),
            bits=bits, group=g, axis=axis - len(shape),
            orig_shape=shape)

    return jax.tree_util.tree_map_with_path(
        fn, spec_tree, is_leaf=lambda x: hasattr(x, "axes")
        and hasattr(x, "materialize"))


def quantized_fraction(qparams: Any) -> float:
    """Fraction of parameter *bytes* now stored quantized."""
    qbytes = 0
    tbytes = 0
    for leaf in jax.tree_util.tree_leaves(
            qparams, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            n = int(np.prod(leaf.orig_shape))
            qbytes += n
            tbytes += n
        elif isinstance(leaf, jax.Array):
            tbytes += int(np.prod(leaf.shape))
    return qbytes / max(tbytes, 1)
