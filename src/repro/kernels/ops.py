"""jit'd public wrappers for the Pallas kernels.

Off the TPU the kernels execute in interpret mode (and the serve path
takes the pure-jnp references); the same call sites compile to real
Mosaic kernels on the TPU.  Each route is chosen from what the code can
observe — the backend and the shapes the kernels accept — so model code
can call these unconditionally.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.quant.qarray import QTensor, count_dequant, maybe_dequantize

from .cim_gemv import cim_gemv, tile_ok
from .flash_decode import flash_decode
from .paged_flash_decode import paged_flash_decode, paged_flash_verify
from .ref import (ref_flash_decode, ref_paged_decode, ref_paged_verify,
                  ref_qmatmul, ref_qmatmul_fused, ref_swiglu_qgemv)
from .swiglu_gemv import swiglu_qgemv


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tile_ok(qt: QTensor, rows: int, col_shards: int = 1) -> bool:
    """A packed weight the Pallas GEMV kernels accept (`cim_gemv.tile_ok`):
    2-D as traced (a scanned layer's slice counts), grouped along axis -2,
    with each of `col_shards` column shards kernel-sized.  Sizes come
    from the DATA array: under lax.scan the static orig_shape keeps the
    stacked-layer dim."""
    if qt.data.ndim != 2 or qt.axis != -2:
        return False
    K = qt.data.shape[0] * (2 if qt.bits == 4 else 1)
    N = qt.data.shape[1]
    return N % col_shards == 0 and tile_ok(K, N // col_shards, qt.group,
                                           rows)


def _mesh_rules():
    """(mesh, rules) of an active multi-device serve mesh, else None."""
    from repro.dist.shard import current_mesh_rules
    cur = current_mesh_rules()
    return cur if cur is not None and cur[0].size > 1 else None


def _tp_shards() -> int:
    """Devices the logical "tp" axis splits over in the active mesh."""
    cur = _mesh_rules()
    if cur is None:
        return 1
    from repro.dist.axes import _axis_size
    return _axis_size(cur[0], cur[1].get("tp"))


def _per_shard(fn, args, axes, out_axes):
    """Call a Pallas kernel once per device of the active serve mesh.

    A Mosaic kernel cannot be partitioned by XLA, so under a multi-device
    `use_mesh_rules` context it runs in shard_map over the logical axes
    tensor parallelism already split (kv heads, FFN columns): each device
    computes its own heads or columns, exactly the work GSPMD assigns the
    surrounding graph.  Without such a mesh the kernel is called as is."""
    cur = _mesh_rules()
    if cur is None:
        return fn(*args)
    from repro.dist import sanitize_pspec
    mesh, rules = cur
    specs = [sanitize_pspec(rules.pspec(a), x.shape, mesh)
             for a, x in zip(axes, args)]
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(specs),
                         out_specs=rules.pspec(out_axes),
                         check_vma=False)(*args)


def qmatmul(x: jax.Array, w: Any) -> jax.Array:
    """x @ W for dense or QTensor weights, kernel-accelerated when aligned."""
    if not isinstance(w, QTensor):
        return x @ w
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if _tile_ok(w, x2.shape[0]):
        count_dequant("fused_dequant")
        out = cim_gemv(x2, w.data, w.scales, bits=w.bits, group=w.group,
                       interpret=_interpret())
    else:
        out = ref_qmatmul(x2, w)
    return out.reshape(*lead, w.orig_shape[-1])


def qmatmul_xla(x: jax.Array, w: Any) -> jax.Array:
    """Fused grouped contraction on the XLA path (used for pjit lowering:
    keeps HLO free of pallas custom-calls while preserving the quantized
    bytes).  The weight stays integer end-to-end — scales multiply group
    partial sums, so no float copy of W is ever materialized (the
    serve-path residency invariant tracked by `qarray.dequant_counters`)."""
    if not isinstance(w, QTensor):
        return x @ w
    return ref_qmatmul_fused(x, w)


def projection(name: str, x: jax.Array, w: Any) -> jax.Array:
    """`qmatmul_xla` under the named scope `name` (`q_proj`, `down_proj`,
    ...), so each of its device ops says which projection it serves."""
    with jax.named_scope(name):
        return qmatmul_xla(x, w)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, window: int = 0, attn_cap: float = 0.0,
                     use_kernel: bool = True) -> jax.Array:
    """q: (b,g,qpk,hd); k/v: (b,S,g,hd) -> (b,g,qpk,hd)."""
    b, g, qpk, hd = q.shape
    S = k.shape[1]
    if not use_kernel or S % 512 != 0:
        return ref_flash_decode(q, k, v, pos, window, attn_cap)
    qf = q.reshape(b * g, qpk, hd)
    kf = k.swapaxes(1, 2).reshape(b * g, S, hd)
    vf = v.swapaxes(1, 2).reshape(b * g, S, hd)
    out = flash_decode(qf, kf, vf, pos, window=window, attn_cap=attn_cap,
                       interpret=_interpret())
    return out.reshape(b, g, qpk, hd)


_POOL = (None, "tp", None, None)          # (n_pages, g, ps, hd)
_SCALES = (None, "tp", None)              # (n_pages, g, ps)
_TABLES, _LENGTHS = (None, None), (None,)


def _paged_attention(kernel, q, q_axes, k_pages, v_pages, tables, lengths,
                     window, attn_cap, k_scales, v_scales):
    args = [q, k_pages, v_pages, tables, lengths]
    axes = [q_axes, _POOL, _POOL, _TABLES, _LENGTHS]
    if k_scales is not None:
        args += [k_scales, v_scales]
        axes += [_SCALES, _SCALES]

    def fn(q, k, v, tab, ln, ks=None, vs=None):
        return kernel(q, k, v, tab, ln, window=window, attn_cap=attn_cap,
                      interpret=_interpret(), k_scales=ks, v_scales=vs)

    return _per_shard(fn, args, axes, q_axes)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, tables: jax.Array,
                           lengths: jax.Array, window: int = 0,
                           attn_cap: float = 0.0,
                           use_kernel: bool = None,
                           k_scales: jax.Array = None,
                           v_scales: jax.Array = None) -> jax.Array:
    """Paged decode attention: q (b,g,qpk,hd), head-major pools
    (n_pages,g,ps,hd), tables (b,max_pages), lengths (b,) -> (b,g,qpk,hd).

    Routes to the Pallas block-table kernel on TPU (the gather never
    materializes); the pure-jnp gather reference is the lowering path
    everywhere else (and the oracle the kernel is tested against).
    With k_scales/v_scales the pools are per-token INT8 and dequantized
    in-kernel (or post-gather on the reference path).
    """
    if use_kernel is None:
        use_kernel = not _interpret()
    if not use_kernel:
        return ref_paged_decode(q, k_pages, v_pages, tables, lengths,
                                window, attn_cap, k_scales, v_scales)
    return _paged_attention(paged_flash_decode, q, (None, "tp", None, None),
                            k_pages, v_pages, tables, lengths, window,
                            attn_cap, k_scales, v_scales)


def paged_verify_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, tables: jax.Array,
                           lengths: jax.Array, window: int = 0,
                           attn_cap: float = 0.0,
                           use_kernel: bool = None,
                           k_scales: jax.Array = None,
                           v_scales: jax.Array = None) -> jax.Array:
    """Multi-query paged attention for speculative verify windows.

    q: (b, s, g, qpk, hd) — s draft positions per lane, query j at
    absolute position lengths[i] + j; lengths EXCLUDE the window.
    Pallas multi-query kernel on TPU (one pass over the sequence's
    pages verifies the whole window), jnp gather oracle elsewhere.
    k_scales/v_scales mark the pools as per-token INT8.
    Returns (b, s, g, qpk, hd).
    """
    if use_kernel is None:
        use_kernel = not _interpret()
    if not use_kernel:
        return ref_paged_verify(q, k_pages, v_pages, tables, lengths,
                                window, attn_cap, k_scales, v_scales)
    return _paged_attention(paged_flash_verify, q,
                            (None, None, "tp", None, None), k_pages,
                            v_pages, tables, lengths, window, attn_cap,
                            k_scales, v_scales)


def swiglu(x: jax.Array, w_gate: Any, w_up: Any,
           use_kernel: bool = None) -> jax.Array:
    """Fused quantized SwiGLU: the `swiglu_qgemv` Pallas kernel on TPU
    when both packed weights are kernel-sized, the fused grouped-einsum
    reference otherwise — packed weights stay integer on every route."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    quant = isinstance(w_gate, QTensor) and isinstance(w_up, QTensor)
    if use_kernel is None:
        shards = _tp_shards()
        use_kernel = (quant and not _interpret()
                      and _tile_ok(w_gate, x2.shape[0], shards)
                      and _tile_ok(w_up, x2.shape[0], shards))
    if use_kernel:
        count_dequant("fused_dequant")
        cols = (None, "tp")                 # FFN columns split by TP

        def fn(x, gd, gs, ud, us):
            return swiglu_qgemv(x, gd, gs, ud, us, bits=w_gate.bits,
                                group=w_gate.group, interpret=_interpret())

        out = _per_shard(fn, [x2, w_gate.data, w_gate.scales, w_up.data,
                              w_up.scales],
                         [(None, None), cols, cols, cols, cols], cols)
        return out.reshape(*lead, out.shape[-1])
    g = qmatmul_xla(x, w_gate).astype(jnp.float32)
    u = qmatmul_xla(x, w_up).astype(jnp.float32)
    return (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
