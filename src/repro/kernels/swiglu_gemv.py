"""`swiglu_gemv` — fused gate/up quantized GEMV + SiLU*mul epilogue.

EdgeCIM's FFN stage maps the up and gate matrices onto the PEs *in
parallel* and fuses activation + elementwise-multiply on dedicated units
(Sec. III-C4).  TPU image: both quantized weight blocks ride the same
K-stream; the SiLU*mul epilogue runs on the VPU at the last K step, so the
intermediate gate/up activations never round-trip to HBM.  Blocks,
scales and the INT4 unpack are `cim_gemv`'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cim_gemv import (as_kernel_weight, block_dot, default_blocks,
                       group_order, operand_specs)


def _kernel(x_ref, wg_ref, sg_ref, wu_ref, su_ref, o_ref, accg_ref,
            accu_ref, *, bits: int, group: int, n_k: int, n_groups: int):
    k_idx = pl.program_id(1)

    @pl.when(k_idx == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x = x_ref[...].astype(jnp.float32)
    dot = functools.partial(block_dot, x, k_idx=k_idx, bits=bits,
                            group=group, n_groups=n_groups)
    accg_ref[...] += dot(wg_ref, sg_ref)
    accu_ref[...] += dot(wu_ref, su_ref)

    @pl.when(k_idx == n_k - 1)
    def _done():
        g = accg_ref[...]
        o_ref[...] = (g * jax.nn.sigmoid(g) * accu_ref[...]
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "group", "block_n",
                                             "block_k", "interpret"))
def swiglu_qgemv(x: jax.Array, wg_packed: jax.Array, wg_scales: jax.Array,
                 wu_packed: jax.Array, wu_scales: jax.Array, bits: int = 4,
                 group: int = 128, block_n: int = None, block_k: int = None,
                 interpret: bool = False) -> jax.Array:
    """x: (M, K); gate/up packed like cim_gemv. Returns (M, F)."""
    m, K = x.shape
    F = wg_packed.shape[-1]
    dk, dn = default_blocks(K, F, group)
    block_k, block_n = block_k or dk, block_n or dn
    assert F % block_n == 0, (F, block_n)
    n_k = K // block_k
    wg_packed, wg_scales = as_kernel_weight(wg_packed, wg_scales)
    wu_packed, wu_scales = as_kernel_weight(wu_packed, wu_scales)

    xspec, wspec, sspec = operand_specs(m, K, bits, group, block_k, block_n)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, group=group, n_k=n_k,
                          n_groups=block_k // group),
        grid=(F // block_n, n_k),
        in_specs=[xspec, wspec, sspec, wspec, sspec],
        out_specs=pl.BlockSpec((m, block_n), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((m, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((m, block_n), jnp.float32),
                        pltpu.VMEM((m, block_n), jnp.float32)],
        interpret=interpret,
        name="swiglu_qgemv",
    )(group_order(x, bits, group), wg_packed, wg_scales, wu_packed,
      wu_scales)
