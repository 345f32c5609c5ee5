"""The int4 format and key derivation that every family's weights share.

The benchmark makes the weights itself, in the type they are served in:
int4 codes packed two to a byte along the contraction axis (the even row
in the low nibble, the odd row in the high nibble, each stored as
code + 8) with one float32 scale per (group of 128 rows, column) holding
an f16-exact value.  A family (`bench/families/<family>.py`) says which
leaves a model has and makes each of them with `packed` and `leaf_key`.

Every leaf of every layer comes from its own key, `fold_in(fold_in(root,
id), layer)`, the id taken from the family's own table, so the reference
regenerates one layer at a time and gets the same values the program
was handed, without taking anything from the program.

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


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, the bits above 32 included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def packed(key, k: int, n: int, std: float):
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


def leaf_key(root: jax.Array, ids: Dict[str, int], name: str,
             layer) -> jax.Array:
    """The key of leaf `name` of `layer`; `ids` is the family's table of
    leaf ids, part of the key derivation, so never renumber one."""
    return jax.random.fold_in(jax.random.fold_in(root, ids[name]), layer)


def unpack(codes: jax.Array, scales: jax.Array) -> jax.Array:
    """(k/2, n) packed codes and (k/GROUP, n) scales -> (k, n) float32."""
    lo = (codes & 15).astype(jnp.float32) - 8.0
    hi = (codes >> 4).astype(jnp.float32) - 8.0
    q = jnp.stack([lo, hi], axis=1).reshape(2 * codes.shape[0],
                                            codes.shape[1])
    k, n = q.shape
    w = q.reshape(k // GROUP, GROUP, n) * scales[:, None, :]
    return w.reshape(k, n)
