"""Packed INT4/INT8 weight tensors with per-group scales.

The TPU image of EdgeCIM's precision-reconfigurable DCIM storage: weights
live in DRAM/HBM packed at 4 or 8 bits with one scale per
(group_size x column) block; decode streams 1/4 (INT4) or 1/2 (INT8) of
the bf16 bytes — the same lever that gives the paper its ~2x INT4-over-
INT8 throughput (validated in EXPERIMENTS.md).

QTensor is a pytree node: it flows through jit/pjit/scan (packing is IN
PLACE along the contraction axis, so stacked-layer leading dims survive
for lax.scan), shards by the same logical axes as the dense weight it
replaces, and is consumed either by the pure-jnp dequant path
(kernels/ref.py — the lowering path on the CPU backend) or by the Pallas
`cim_gemv` kernel on TPU.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp

INT4_GROUP = 128

# Trace-time counters: python-side increments inside jitted functions run
# when the function is TRACED, not per step — so after tracing a decode
# step, `full_dequant == 0` proves the compiled graph contains no
# whole-weight float materialization (the serve-path residency guarantee
# asserted by `api_bench --precision int4`).  `fused_dequant` counts
# group-scale applications that never build the full float weight
# (fused refs, cim_gemv/swiglu_qgemv kernels, row gathers).
_COUNTERS = {"full_dequant": 0, "fused_dequant": 0}


def count_dequant(kind: str = "full_dequant") -> None:
    _COUNTERS[kind] += 1


def dequant_counters() -> dict:
    return dict(_COUNTERS)


def reset_dequant_counters() -> None:
    for k in _COUNTERS:
        _COUNTERS[k] = 0


@jax.tree_util.register_pytree_node_class
@dataclass
class QTensor:
    """Quantized weight; `axis` is the contraction/grouping axis.  INT4
    packs two consecutive `axis` entries per uint8 byte, in place:
    data.shape == orig_shape except axis dim halved (bits=4)."""
    data: jax.Array          # int8 (bits=8) or uint8 packed pairs (bits=4)
    scales: jax.Array        # orig_shape with axis dim = K/group, f32
    bits: int
    group: int
    axis: int                # NEGATIVE (from the end): slice-invariant under
                             # lax.scan slicing of leading stacked-layer dims
    orig_shape: Tuple[int, ...]

    def tree_flatten(self):
        return (self.data, self.scales), (self.bits, self.group, self.axis,
                                          self.orig_shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scales = children
        bits, group, axis, orig_shape = aux
        return cls(data, scales, bits, group, axis, orig_shape)

    @property
    def shape(self):
        return self.orig_shape

    @property
    def ndim(self):
        return len(self.orig_shape)

    def nbytes_packed(self) -> int:
        import numpy as np
        return (int(np.prod(self.data.shape))
                + self.scales.dtype.itemsize * int(np.prod(self.scales.shape)))

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        return dequantize(self, dtype)


def quantize(w: jax.Array, bits: int = 4, group: int = INT4_GROUP,
             axis: int = 0) -> QTensor:
    """Symmetric per-(group, col) quantization along `axis` (in place)."""
    assert bits in (4, 8)
    if axis >= 0:
        axis = axis - w.ndim                 # store relative to the end
    orig_shape = tuple(w.shape)
    wf = jnp.moveaxis(w.astype(jnp.float32), axis, 0)
    K = wf.shape[0]
    rest = wf.shape[1:]
    g = min(group, K)
    assert K % g == 0, (K, g)
    wg = wf.reshape(K // g, g, *rest)
    qmax = 7.0 if bits == 4 else 127.0
    absmax = jnp.max(jnp.abs(wg), axis=1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / qmax
    q = jnp.clip(jnp.round(wg / scale), -qmax - 1, qmax)
    q = q.reshape(K, *rest).astype(jnp.int8)
    # f16-rounded scales: bf16's 8-bit mantissa costs up to 0.5*scale of
    # extra INT8 error; f16 (10-bit) keeps it <6%.  Stored as f32, the
    # dtype the TPU kernels can load (the values stay f16-exact)
    scales = jnp.moveaxis(scale[:, 0].astype(jnp.float16)
                          .astype(jnp.float32), 0, axis)
    if bits == 4:
        assert K % 2 == 0
        lo = (q[0::2].astype(jnp.int32) + 8)
        hi = (q[1::2].astype(jnp.int32) + 8)
        data = jnp.moveaxis((lo | (hi << 4)).astype(jnp.uint8), 0, axis)
    else:
        data = jnp.moveaxis(q, 0, axis)
    return QTensor(data=data, scales=scales, bits=bits, group=g, axis=axis,
                   orig_shape=orig_shape)


def unpack_int4(packed: jax.Array, axis: int = 0) -> jax.Array:
    """(..., K/2, ...) uint8 -> (..., K, ...) int8 in [-8, 7] along axis."""
    p = jnp.moveaxis(packed, axis, 0)
    lo = (p & 0xF).astype(jnp.int8) - 8
    hi = (p >> 4).astype(jnp.int8) - 8
    out = jnp.stack([lo, hi], axis=1).reshape(2 * p.shape[0], *p.shape[1:])
    return jnp.moveaxis(out, 0, axis)


def dequantize(qt: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    count_dequant("full_dequant")
    q = unpack_int4(qt.data, qt.axis) if qt.bits == 4 else qt.data
    qm = jnp.moveaxis(q, qt.axis, 0)
    K = qm.shape[0]
    g = qt.group
    rest = qm.shape[1:]
    sm = jnp.moveaxis(qt.scales, qt.axis, 0)
    qg = qm.reshape(K // g, g, *rest).astype(jnp.float32)
    w = (qg * sm[:, None].astype(jnp.float32)).reshape(K, *rest)
    return jnp.moveaxis(w, 0, qt.axis).astype(dtype)


def maybe_dequantize(w: Any, dtype=jnp.bfloat16) -> jax.Array:
    return dequantize(w, dtype) if isinstance(w, QTensor) else w


def dequant_rows(qt: QTensor, ids: jax.Array, dtype=jnp.bfloat16
                 ) -> jax.Array:
    """Gather + dequantize rows of an axis=1-quantized (vocab, d) table.

    The embedding-lookup path: only the gathered rows are unpacked, so a
    quantized tied embedding costs `len(ids) * d/2` bytes, not the full
    table.  ids: (...,) int32 -> (..., d)."""
    assert qt.axis == -1 and len(qt.orig_shape) == 2
    count_dequant("fused_dequant")
    d = qt.orig_shape[1]
    data = qt.data[ids]                              # (..., d/2 or d)
    scales = qt.scales[ids]                          # (..., d/group)
    if qt.bits == 4:
        lo = (data & 0xF).astype(jnp.int8) - 8
        hi = (data >> 4).astype(jnp.int8) - 8
        q = jnp.stack([lo, hi], axis=-1).reshape(*data.shape[:-1], d)
    else:
        q = data
    qg = q.reshape(*q.shape[:-1], d // qt.group, qt.group).astype(jnp.float32)
    w = qg * scales[..., None].astype(jnp.float32)
    return w.reshape(*q.shape[:-1], d).astype(dtype)
