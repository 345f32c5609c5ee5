"""`paged_flash_decode` — block-table paged decode attention Pallas kernel.

The paged-KV serving runtime keeps K/V in a shared pool of fixed-size
pages; each sequence owns a list of page ids (its block table).  This
kernel is `flash_decode` with the KV stream INDIRECTED through the block
table (EdgeCIM's KV-block streaming, Sec. III-C2, with paging on top),
and it walks only the KV a lane holds.

Grid: one step per lane.  A lane whose window ends at `lengths[b] + s`
rows holds `n_live = ceil((lengths[b] + s) / page_size)` live pages; its
step loops over `ceil(n_live / P)` blocks of P pages, a trip count read
from the scalar-prefetched lengths, so an idle lane costs one empty grid
step.  The pools stay in HBM: each live page is copied by a manual
async copy into one of two VMEM slots, and while a block is scored the
next one — the lane's next block, or the first block of the next lane
holding any page — is already in flight.  Online-softmax state (m, l,
acc) rides the block loop.

Pools are stored head-major, (n_pages, g, page_size, hd), so page p is
one contiguous (g, page_size, hd) slab: one copy brings every local kv
head, and each head scores its block's P * page_size rows with one dot
(int8 K/V cast to the query's dtype, which is exact; probabilities stay
f32).  INT8 pools carry f32 per-(token, kv-head) scales (n_pages, g,
page_size) holding f16-rounded values (the TPU kernel cannot load f16).
Mosaic cannot copy a slice of an array whose minor dim is under one
128-lane tile, so the wrapper gathers each lane's scales into one
lane-major (g, max_pages * page_size) row block, zero past its live
pages, which the grid streams lane by lane; they multiply scores (K)
and probabilities (V).

P comes from the shapes (`pages_per_block`): about 512 KV rows, in whole
128-lane tiles, fewer when two slots of K and V blocks would pass their
VMEM budget, and never more pages than a table holds.

Padding rule: the kernel never reads block-table entries past a lane's
live pages — no copy, no score — and the scale gather zeroes what it
fetches for them, so they may hold any valid page id (the engine pads
with 0).  Rows of the last live page past the lane's length are copied
and masked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
BLOCK_ROWS = 512                   # KV rows a block aims at
BLOCK_VMEM_BYTES = 8 * 1024 * 1024  # two slots of the K and V blocks


def pages_per_block(page_size: int, max_pages: int, g: int, hd: int,
                    itemsize: int) -> int:
    """P: pages per block — about BLOCK_ROWS rows, a whole number of
    128-lane tiles of the scale rows, halved while two slots of K and V
    blocks pass BLOCK_VMEM_BYTES; a table of at most P pages is one
    block."""
    step = 128 // math.gcd(page_size, 128)       # P * page_size % 128 == 0
    p = max(step, BLOCK_ROWS // page_size // step * step)
    while (p % (2 * step) == 0
           and 2 * 2 * p * g * page_size * hd * itemsize > BLOCK_VMEM_BYTES):
        p //= 2
    return min(p, max_pages)


def _lane_scales(scales: jax.Array, tables: jax.Array, live: jax.Array,
                 width: int) -> jax.Array:
    """(b, g, width) f32: each lane's per-token scales, page after page
    along the last axis, zero past its live pages (so a masked row's
    probability, 0, is never multiplied by a padded page's value)."""
    b, mp = tables.shape
    _, g, ps = scales.shape
    held = jnp.arange(mp)[None, :] < live[:, None]               # (b, mp)
    rows = jnp.where(held[..., None, None], scales[tables], 0.0)
    rows = rows.transpose(0, 2, 1, 3).reshape(b, g, mp * ps)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, width - mp * ps)))


def _kernel(tab_ref, len_ref, live_ref, nxt_ref, q_ref, k_hbm, v_hbm,
            *rest, page_size: int, max_pages: int, n_lanes: int, P: int,
            qpk: int, scale: float, window: int, attn_cap: float,
            quant: bool, score_dtype):
    """The q block carries all g kv heads' s query positions (rows
    j*qpk..j*qpk+qpk-1 of a head are position lengths[b]+j), each with
    its own causal horizon — one pass over the lane's live blocks scores
    a whole verify window (decode is the s == 1 case)."""
    if quant:
        ks_ref, vs_ref, o_ref, kbuf, vbuf, sems, slot_ref = rest
    else:
        o_ref, kbuf, vbuf, sems, slot_ref = rest
        ks_ref = vs_ref = None
    b_idx = pl.program_id(0)
    _, g, sq, hd = q_ref.shape
    rows = P * page_size

    def for_pages(lane, blk, slot, op):
        """op(copy) for the K and V copy of each live page of block
        `blk` of `lane` into `slot` — a loop as long as the block holds
        live pages, so no padded entry is ever copied."""
        first = blk * P

        def page(i, carry):
            at = tab_ref[lane * max_pages + first + i]
            for pool, buf, sem in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                op(pltpu.make_async_copy(pool.at[at], buf.at[slot, i],
                                         sems.at[sem, slot]))
            return carry

        n = jnp.minimum(live_ref[lane] - first, P)
        jax.lax.fori_loop(0, n, page, 0)

    def start(lane, blk, slot):
        for_pages(lane, blk, slot, lambda cp: cp.start())

    def wait(lane, blk, slot):
        for_pages(lane, blk, slot, lambda cp: cp.wait())

    @pl.when(b_idx == 0)
    def _first():
        # pages a block does not copy keep a slot's older rows: make
        # them finite once, so masked rows weigh exactly 0
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        lane0 = nxt_ref[0]

        @pl.when(lane0 < n_lanes)
        def _():
            start(lane0, 0, 0)

    length = len_ref[b_idx]                      # tokens BEFORE window
    n_blk = pl.cdiv(live_ref[b_idx], P)
    next_lane = nxt_ref[b_idx + 1]
    slot0 = slot_ref[0]
    q = q_ref[0].astype(score_dtype)             # (g, sq, hd)
    q_pos = length + jax.lax.broadcasted_iota(
        jnp.int32, (sq, rows), 0) // qpk         # intra-window causal
    col = jax.lax.broadcasted_iota(jnp.int32, (sq, rows), 1)

    def body(j, carry):
        slot = (slot0 + j) % 2

        @pl.when(j + 1 < n_blk)
        def _():
            start(b_idx, j + 1, 1 - slot)

        @pl.when((j + 1 == n_blk) & (next_lane < n_lanes))
        def _():
            start(next_lane, 0, 1 - slot)

        wait(b_idx, j, slot)
        k_pos = j * rows + col
        valid = k_pos <= q_pos
        if window:
            valid = valid & (q_pos - k_pos < window)
        blk = pl.ds(pl.multiple_of(j * rows, rows), rows)
        out = []
        for gi in range(g):
            m_prev, l_prev, acc = carry[3 * gi: 3 * gi + 3]
            k = kbuf[slot, :, gi].astype(score_dtype).reshape(rows, hd)
            s = jax.lax.dot_general(q[gi], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if quant:               # per-token K scale multiplies its score
                s = s * ks_ref[0, pl.ds(gi, 1), blk]
            s = s * scale
            if attn_cap:
                s = attn_cap * jnp.tanh(s / attn_cap)
            s = jnp.where(valid, s, NEG_INF)             # (sq, rows)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quant:               # per-token V scale weights its prob
                p = p * vs_ref[0, pl.ds(gi, 1), blk]
            v = vbuf[slot, :, gi].astype(jnp.float32).reshape(rows, hd)
            acc = acc * alpha + jnp.dot(p, v,
                                        preferred_element_type=jnp.float32)
            out += [m_new, l_new, acc]
        return tuple(out)

    init = []
    for _ in range(g):
        init += [jnp.full((sq, 1), NEG_INF, jnp.float32),
                 jnp.zeros((sq, 1), jnp.float32),
                 jnp.zeros((sq, hd), jnp.float32)]
    carry = jax.lax.fori_loop(0, n_blk, body, tuple(init))
    slot_ref[0] = (slot0 + n_blk) % 2
    for gi in range(g):
        _, l_fin, acc = carry[3 * gi: 3 * gi + 3]
        o_ref[0, gi] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "attn_cap",
                                             "interpret"))
def paged_flash_verify(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       tables: jax.Array, lengths: jax.Array,
                       window: int = 0, attn_cap: float = 0.0,
                       interpret: bool = False,
                       k_scales: jax.Array = None,
                       v_scales: jax.Array = None) -> jax.Array:
    """Speculative-verify attention over the paged pool.

    q: (b, s, g, qpk, hd) — s draft-window query positions per lane;
    k_pages/v_pages: (n_pages, g, page_size, hd); tables: (b, max_pages)
    int32.  Query j of lane i sits at absolute position lengths[i] + j
    and attends k_pos <= lengths[i] + j (its own K row is already
    scattered into the pool).  lengths counts tokens cached BEFORE this
    window (exclusive — unlike `paged_flash_decode`, whose lengths
    include the current token).  With k_scales/v_scales ((n_pages, g,
    page_size) f32) the pools are per-token INT8, streamed packed and
    dequantized in-register.  Returns (b, s, g, qpk, hd).
    """
    b, s, g, qpk, hd = q.shape
    page_size = k_pages.shape[2]
    max_pages = tables.shape[1]
    sq = s * qpk
    qf = q.transpose(0, 2, 1, 3, 4).reshape(b, g, sq, hd)
    quant = k_scales is not None
    P = pages_per_block(page_size, max_pages, g, hd, k_pages.dtype.itemsize)
    score_dtype = (q.dtype if quant
                   else jnp.promote_types(q.dtype, k_pages.dtype))

    lengths = lengths.astype(jnp.int32)
    live = jnp.clip(pl.cdiv(lengths + s, page_size), 0, max_pages)
    # nxt[i]: first lane >= i holding a live page (b when none) — the
    # lane whose first block lane i-1's last block prefetches
    lane_ids = jnp.where(live > 0, jnp.arange(b, dtype=jnp.int32), b)
    nxt = jax.lax.cummin(jnp.append(lane_ids, jnp.int32(b)), reverse=True)

    qspec = pl.BlockSpec((1, g, sq, hd), lambda bi, *_: (bi, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    operands = [qf, k_pages, v_pages]
    in_specs = [qspec, pool_spec, pool_spec]
    if quant:
        width = pl.cdiv(max_pages, P) * P * page_size
        operands += [_lane_scales(k_scales, tables, live, width),
                     _lane_scales(v_scales, tables, live, width)]
        in_specs += [pl.BlockSpec((1, g, width),
                                  lambda bi, *_: (bi, 0, 0))] * 2
    scratch = [pltpu.VMEM((2, P, g, page_size, hd), k_pages.dtype),
               pltpu.VMEM((2, P, g, page_size, hd), v_pages.dtype),
               pltpu.SemaphoreType.DMA((2, 2)), pltpu.SMEM((1,), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=in_specs,
        out_specs=qspec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, max_pages=max_pages,
                          n_lanes=b, P=P, qpk=qpk, scale=1.0 / (hd ** 0.5),
                          window=window, attn_cap=attn_cap, quant=quant,
                          score_dtype=score_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, sq, hd), q.dtype),
        # blocks are prefetched across lanes: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_flash_attention",
    )(tables.reshape(-1).astype(jnp.int32), lengths, live, nxt, *operands)
    return out.reshape(b, g, s, qpk, hd).transpose(0, 2, 1, 3, 4)


@functools.partial(jax.jit, static_argnames=("window", "attn_cap",
                                             "interpret"))
def paged_flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       tables: jax.Array, lengths: jax.Array,
                       window: int = 0, attn_cap: float = 0.0,
                       interpret: bool = False,
                       k_scales: jax.Array = None,
                       v_scales: jax.Array = None) -> jax.Array:
    """q: (b, g, qpk, hd); pools as `paged_flash_verify`; lengths: (b,)
    int32 valid tokens per sequence (inclusive of the current token; 0
    for an idle lane, whose output is 0).  A decode step is a
    one-position verify window whose query sits at lengths - 1.
    Returns (b, g, qpk, hd)."""
    return paged_flash_verify(q[:, None], k_pages, v_pages, tables,
                              lengths - 1, window=window, attn_cap=attn_cap,
                              interpret=interpret, k_scales=k_scales,
                              v_scales=v_scales)[:, 0]
