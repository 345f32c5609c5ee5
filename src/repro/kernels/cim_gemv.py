"""`cim_gemv` — quantized weight-stationary GEMV/GEMM Pallas TPU kernel.

The EdgeCIM DCIM macro, rethought for the TPU memory hierarchy
(DESIGN.md SS2): instead of bit-serial SRAM arrays, packed INT4/INT8
weight blocks stream HBM -> VMEM through the Pallas grid pipeline (the
hardware double-buffering plays the paper's "active tiles prefetch while
compute proceeds" role), and hit the MXU one quantization group at a
time: each group's integer weights contract against the activations and
the (m, block_n) partial sum is scaled by that group's per-column scale —
the float weight never exists, as in `ref.ref_qmatmul_fused`.  The
K-grid dimension is the paper's partition stream; accumulation lives in
a VMEM fp32 scratch.

Layout rules the TPU block shapes must meet (last two block dims
divisible by 8 and 128, or equal to the array's):
  * the scale block spans all K/group rows of its column block, so its
    row count never constrains block_k;
  * INT4 bytes are unpacked in int32 (no arithmetic on int8 vectors);
    a group's low nibbles (even K rows) and high nibbles (odd K rows)
    stack into one (group, block_n) tile, and the wrapper permutes the
    activations' K axis to that order, so the packed layout of
    `quant.qarray` is unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_BLOCK_K = 2048
MAX_ROWS = 256          # activation rows one call holds in VMEM


def default_blocks(K: int, N: int, group: int):
    """(block_k, block_n): the widest lane-aligned column block, and the
    largest K block <= MAX_BLOCK_K made of whole groups."""
    block_n = 256 if N % 256 == 0 else 128
    block_k = group
    for bk in range(group, min(K, MAX_BLOCK_K) + 1, group):
        if K % bk == 0 and bk % 128 == 0:
            block_k = bk
    return block_k, block_n


def tile_ok(K: int, N: int, group: int, rows: int) -> bool:
    """Shapes the kernels compile for on the TPU: lane-aligned columns
    and groups, whole groups in K, and a decode-sized row count."""
    return (N % 128 == 0 and group % 128 == 0 and K % group == 0
            and rows <= MAX_ROWS)


def group_order(x: jax.Array, bits: int, group: int) -> jax.Array:
    """Permute x's K axis to the kernels' INT4 row order: within each
    group, the even rows (low nibbles) then the odd rows (high)."""
    if bits != 4:
        return x
    m, K = x.shape
    return x.reshape(m, K // group, group // 2, 2).swapaxes(-1, -2
                                                          ).reshape(m, K)


def _group_weight(w_ref, j: int, bits: int, group: int) -> jax.Array:
    """Group j of the weight block as an f32 (group, block_n) tile of
    integer values (INT4: low nibbles, then high nibbles)."""
    if bits == 8:
        return w_ref[j * group:(j + 1) * group, :].astype(jnp.float32)
    half = group // 2
    p = w_ref[j * half:(j + 1) * half, :].astype(jnp.int32)
    q = jnp.concatenate([(p & 0xF) - 8, ((p >> 4) & 0xF) - 8], axis=0)
    return q.astype(jnp.float32)


def block_dot(x: jax.Array, w_ref, s_ref, k_idx, *, bits: int, group: int,
              n_groups: int) -> jax.Array:
    """sum_j (x_j @ q_j) * s_j over the block's groups -> (m, block_n)."""
    acc = None
    for j in range(n_groups):
        part = jnp.dot(x[:, j * group:(j + 1) * group],
                       _group_weight(w_ref, j, bits, group),
                       preferred_element_type=jnp.float32)
        part = part * s_ref[pl.ds(k_idx * n_groups + j, 1), :]
        acc = part if acc is None else acc + part
    return acc


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, bits: int, group: int,
            n_k: int, n_groups: int):
    k_idx = pl.program_id(1)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += block_dot(x_ref[...].astype(jnp.float32), w_ref, s_ref,
                              k_idx, bits=bits, group=group,
                              n_groups=n_groups)

    @pl.when(k_idx == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def operand_specs(m: int, K: int, bits: int, group: int, block_k: int,
                  block_n: int):
    """BlockSpecs of (x, packed weight, scales) on a (N blocks, K blocks)
    grid.  The scale block holds every group row of its columns: its
    index never moves along K, so it is fetched once per column block."""
    assert K % block_k == 0 and block_k % group == 0, (K, block_k, group)
    w_rows = block_k // 2 if bits == 4 else block_k
    return (pl.BlockSpec((m, block_k), lambda n, k: (0, k)),
            pl.BlockSpec((w_rows, block_n), lambda n, k: (k, n)),
            pl.BlockSpec((K // group, block_n), lambda n, k: (0, n)))


def as_kernel_weight(packed: jax.Array, scales: jax.Array):
    """INT4 bytes as int8 (a free bitcast: the kernel unpacks in int32)
    and scales as f32."""
    if packed.dtype == jnp.uint8:
        packed = jax.lax.bitcast_convert_type(packed, jnp.int8)
    return packed, scales.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "group", "block_n",
                                             "block_k", "interpret"))
def cim_gemv(x: jax.Array, packed: jax.Array, scales: jax.Array,
             bits: int = 4, group: int = 128,
             block_n: int = None, block_k: int = None,
             interpret: bool = False) -> jax.Array:
    """x: (M, K) bf16/f32; packed: (K/2, N) uint8 [int4] or (K, N) int8;
    scales: (K/group, N).  Returns (M, N) in x.dtype.

    Grid = (N blocks "parallel", K blocks "arbitrary"): K innermost so the
    fp32 accumulator carries across the weight-partition stream, exactly
    the EdgeCIM accumulate-across-partitions schedule (Sec. III-C1).
    """
    m, K = x.shape
    N = packed.shape[-1]
    dk, dn = default_blocks(K, N, group)
    block_k, block_n = block_k or dk, block_n or dn
    assert N % block_n == 0, (N, block_n)
    n_k = K // block_k
    packed, scales = as_kernel_weight(packed, scales)

    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, group=group, n_k=n_k,
                          n_groups=block_k // group),
        grid=(N // block_n, n_k),
        in_specs=list(operand_specs(m, K, bits, group, block_k, block_n)),
        out_specs=pl.BlockSpec((m, block_n), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((m, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((m, block_n), jnp.float32)],
        interpret=interpret,
        name="cim_gemv",
    )(group_order(x, bits, group), packed, scales)
