"""`paged_flash_decode` — block-table paged decode attention Pallas kernel.

The paged-KV serving runtime keeps K/V in a shared pool of fixed-size
pages; each sequence owns a list of page ids (its block table).  This
kernel is `flash_decode` with the KV stream INDIRECTED through the block
table: the table and the per-sequence lengths ride in as scalar-prefetch
operands, so the grid's page dimension DMAs exactly the pages the
sequence owns (EdgeCIM's KV-block streaming, Sec. III-C2, with paging on
top).  Online-softmax state (m, l, acc) lives in VMEM scratch across the
page dimension.

Pools are stored head-major, (n_pages, g, page_size, hd), so one grid
step streams one kv head's page as a dense (page_size, hd) tile — the
TPU block rule (last two block dims divisible by 8 and 128, or equal to
the array's) holds for any kv-head count.  INT8 pools carry f32
per-(token, kv-head) scales (n_pages, g, page_size) holding f16-rounded
values (the TPU kernel cannot load f16); a step reads the page's whole
(g, page_size) scale block and picks its head's row.

Grid: (batch, kv_head, seq_page).  Padded table entries must hold a
valid page id (the engine pads with 0); their scores are masked by the
length operand, so the gathered garbage never contributes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _head_row(ref, gi) -> jax.Array:
    """Row `gi` of a (1, g, page_size) scale block as (1, page_size) f32."""
    rows = ref[0].astype(jnp.float32)                   # (g, page_size)
    sel = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == gi
    return jnp.sum(jnp.where(sel, rows, 0.0), axis=0, keepdims=True)


def _kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, *rest, page_size: int,
            n_i: int, qpk: int, scale: float, window: int, attn_cap: float,
            quant: bool):
    """The q block carries s query positions (rows j*qpk..j*qpk+qpk-1 are
    position lengths[b]+j), each with its own causal horizon — one pass
    over the sequence's pages scores a whole verify window (decode is the
    s == 1 case)."""
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b_idx = pl.program_id(0)
    g_idx = pl.program_id(1)
    i_idx = pl.program_id(2)

    @pl.when(i_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b_idx]                             # tokens BEFORE window
    q = q_ref[0, 0].astype(jnp.float32)                 # (s*qpk, hd)
    k = k_ref[0, 0].astype(jnp.float32)                 # (page_size, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    sq = q.shape[0]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    if quant:                       # per-token K scale multiplies its score
        s = s * _head_row(ks_ref, g_idx)
    s = s * scale
    if attn_cap:
        s = attn_cap * jnp.tanh(s / attn_cap)
    k_pos = i_idx * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (sq, page_size), 1)
    q_pos = length + jax.lax.broadcasted_iota(
        jnp.int32, (sq, page_size), 0) // qpk           # intra-window causal
    valid = k_pos <= q_pos
    if window:
        valid = valid & (q_pos - k_pos < window)
    s = jnp.where(valid, s, NEG_INF)                    # (s*qpk, page_size)

    m_prev = m_ref[...]                                 # (s*qpk, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if quant:                       # per-token V scale weights its prob
        p = p * _head_row(vs_ref, g_idx)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(i_idx == n_i - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


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

    # page i of lane bi streams pool page tab[bi, i] for kv-head gi
    kv = pl.BlockSpec((1, 1, page_size, hd), lambda bi, gi, i, tab, ln:
                      (tab[bi, i], gi, 0, 0))
    page_specs = [kv, kv]
    operands = (qf, k_pages, v_pages)
    if quant:
        sc = pl.BlockSpec((1, g, page_size), lambda bi, gi, i, tab, ln:
                          (tab[bi, i], 0, 0))
        page_specs += [sc, sc]
        operands += (k_scales, v_scales)
    qspec = pl.BlockSpec((1, 1, sq, hd), lambda bi, gi, i, tab, ln:
                         (bi, gi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, g, max_pages),
        in_specs=[qspec, *page_specs],
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((sq, 1), jnp.float32),
            pltpu.VMEM((sq, 1), jnp.float32),
            pltpu.VMEM((sq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, n_i=max_pages,
                          qpk=qpk, scale=1.0 / (hd ** 0.5), window=window,
                          attn_cap=attn_cap, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, sq, hd), q.dtype),
        interpret=interpret,
        name="paged_flash_attention",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
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
    int32 valid tokens per sequence (inclusive of the current token).
    A decode step is a one-position verify window whose query sits at
    lengths - 1.  Returns (b, g, qpk, hd)."""
    return paged_flash_verify(q[:, None], k_pages, v_pages, tables,
                              lengths - 1, window=window, attn_cap=attn_cap,
                              interpret=interpret, k_scales=k_scales,
                              v_scales=v_scales)[:, 0]
