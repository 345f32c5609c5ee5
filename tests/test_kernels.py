"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cim_gemv import cim_gemv
from repro.kernels.flash_decode import flash_decode
from repro.kernels.paged_flash_decode import (pages_per_block,
                                              paged_flash_decode,
                                              paged_flash_verify)
from repro.kernels.ref import (ref_flash_decode, ref_paged_decode,
                               ref_paged_verify, ref_qmatmul,
                               ref_swiglu_qgemv)
from repro.kernels.swiglu_gemv import swiglu_qgemv
from repro.kernels import ops
from repro.quant.qarray import quantize


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("m,k,n,bk,bn,group", [
    (1, 256, 128, 256, 128, 128),     # pure GEMV
    (4, 512, 256, 256, 128, 128),
    (8, 1024, 512, 512, 256, 128),    # default-ish blocks
    (2, 512, 384, 256, 128, 64),      # non-default group
    (1, 256, 128, 128, 128, 32),      # small group
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cim_gemv_sweep(bits, m, k, n, bk, bn, group, dtype):
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n), jnp.float32) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32
                          ).astype(dtype)
    qt = quantize(w, bits=bits, group=group)
    ref = ref_qmatmul(x.astype(jnp.float32), qt, out_dtype=jnp.float32)
    out = cim_gemv(x, qt.data, qt.scales, bits=bits, group=group,
                   block_n=bn, block_k=bk, interpret=True)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))
                / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < tol, rel


@pytest.mark.parametrize("S,block_s,window,cap", [
    (512, 256, 0, 0.0),
    (1024, 512, 0, 0.0),
    (1024, 256, 200, 0.0),
    (1024, 256, 0, 50.0),
    (512, 512, 64, 30.0),
])
@pytest.mark.parametrize("pos_frac", [0.1, 0.7, 1.0])
def test_flash_decode_sweep(S, block_s, window, cap, pos_frac):
    b, g, qpk, hd = 2, 2, 4, 64
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, g, qpk, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, S, g, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, S, g, hd), jnp.float32)
    pos = jnp.int32(int(pos_frac * (S - 1)))
    ref = ref_flash_decode(q, k, v, pos, window, cap)
    qf = q.reshape(b * g, qpk, hd)
    kf = k.swapaxes(1, 2).reshape(b * g, S, hd)
    vf = v.swapaxes(1, 2).reshape(b * g, S, hd)
    out = flash_decode(qf, kf, vf, pos, block_s=block_s, window=window,
                       attn_cap=cap, interpret=True).reshape(b, g, qpk, hd)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def _edge_pool(rows, s, g, ps, mp, kv, rng, qpk=4, hd=64):
    """q, pools and tables for lanes holding `rows` KV rows each, with
    s query positions: tables as the engine builds them (shuffled live
    pages, then page 0); the kernel's pools and scales hold NaN in page
    0, which no lane owns, so reading a padded entry poisons the
    output.  Returns (q, kernel pools, oracle pools, tables); pools are
    (k, v, k_scales, v_scales), scales None for float pools."""
    b = len(rows)
    n_pages = b * mp + 1
    q = jnp.asarray(rng.standard_normal((b, s, g, qpk, hd)), jnp.float32)
    shape = (n_pages, g, ps, hd)
    if kv == "int8":
        k, v = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                for _ in range(2))
        ks, vs = (jnp.asarray(rng.uniform(0.5, 1.5, shape[:3]) / 127,
                              jnp.float32) for _ in range(2))
        oracle = (k, v, ks, vs)
        kernel = (k, v, ks.at[0].set(jnp.nan), vs.at[0].set(jnp.nan))
    else:
        k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                for _ in range(2))
        oracle = (k, v, None, None)
        kernel = (k.at[0].set(jnp.nan), v.at[0].set(jnp.nan), None, None)
    tables = np.zeros((b, mp), np.int32)
    ids = rng.permutation(np.arange(1, n_pages))
    for i, n in enumerate(rows):
        live = -(-n // ps)
        tables[i, :live] = ids[i * mp:i * mp + live]
    return q, kernel, oracle, jnp.asarray(tables)


def _edge_rows(ps, mp, g, kv):
    """KV rows at 1, ps - 1, ps, ps + 1, one block of the kernel - 1,
    + 0, + 1, and the whole table."""
    blk = ps * pages_per_block(ps, mp, g, 64, 1 if kv == "int8" else 4)
    assert blk < mp * ps, "the table must span more than one block"
    return (1, ps - 1, ps, ps + 1, blk - 1, blk, blk + 1, mp * ps)


_DECODE_CASES = [
    (16, 8, 0, 0.0, None),
    (32, 4, 0, 0.0, None),
    (16, 8, 40, 0.0, None),
    (16, 8, 0, 30.0, None),
    (8, 16, 24, 50.0, None),
    (16, 40, 0, 0.0, (1, "f32")),
    (16, 40, 0, 0.0, (2, "int8")),
    (16, 40, 40, 30.0, (2, "int8")),
    (16, 40, 0, 0.0, (10, "int8")),
]


def _case_id(case):
    ps, mp, window, cap, edge = case
    tag = f"-g{edge[0]}-{edge[1]}-edges" if edge else ""
    return f"{ps}-{mp}-{window}-{cap}{tag}"


@pytest.mark.parametrize("page_size,max_pages,window,cap,edge",
                         _DECODE_CASES, ids=map(_case_id, _DECODE_CASES))
def test_paged_flash_decode_sweep(page_size, max_pages, window, cap, edge):
    """Block-table kernel vs the gather oracle, shuffled page layouts and
    ragged per-sequence lengths.  Edge cases: lengths at page and block
    boundaries, an idle lane (finite zero output), g in {1, 2, 10},
    int8 pools with f32 scales, and padded table entries never read."""
    rng = np.random.default_rng(0)
    if edge is not None:              # ... and an idle lane (length 0)
        g, kv = edge
        lens = _edge_rows(page_size, max_pages, g, kv) + (0,)
        q, kern, orc, tables = _edge_pool(lens, 1, g, page_size, max_pages,
                                          kv, rng)
        q, lengths = q[:, 0], jnp.asarray(lens, jnp.int32)
        ref = ref_paged_decode(q, *orc[:2], tables, lengths, window, cap,
                               *orc[2:])
        out = paged_flash_decode(q, *kern[:2], tables, lengths,
                                 window=window, attn_cap=cap,
                                 interpret=True, k_scales=kern[2],
                                 v_scales=kern[3])
        live = np.asarray(lens) > 0
        assert float(jnp.max(jnp.abs(out - ref)[live])) < 1e-5
        assert float(jnp.max(jnp.abs(out[~live]))) == 0.0
        return
    b, g, qpk, hd = 3, 2, 4, 64
    n_pages = b * max_pages
    q = jnp.asarray(rng.standard_normal((b, g, qpk, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, g, page_size, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, g, page_size, hd)),
                     jnp.float32)
    tables = jnp.asarray(
        rng.permutation(n_pages).reshape(b, max_pages), jnp.int32)
    S = max_pages * page_size
    lengths = jnp.asarray(rng.integers(1, S + 1, size=b), jnp.int32)
    ref = ref_paged_decode(q, kp, vp, tables, lengths, window, cap)
    out = paged_flash_decode(q, kp, vp, tables, lengths, window=window,
                             attn_cap=cap, interpret=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


_VERIFY_CASES = [
    (4, 16, 8, 0, 0.0, None),
    (5, 8, 16, 0, 0.0, None),
    (3, 16, 8, 24, 0.0, None),
    (4, 16, 8, 0, 30.0, None),
    (2, 8, 16, 12, 50.0, None),
    (4, 16, 40, 0, 0.0, (2, "f32")),
    (4, 16, 40, 0, 0.0, (1, "int8")),
    (4, 16, 40, 0, 0.0, (10, "int8")),
]


@pytest.mark.parametrize("s,page_size,max_pages,window,cap,edge",
                         _VERIFY_CASES,
                         ids=[f"{c[0]}-" + _case_id(c[1:])
                              for c in _VERIFY_CASES])
def test_paged_flash_verify_sweep(s, page_size, max_pages, window, cap,
                                  edge):
    """Multi-query verify kernel vs the gather oracle: shuffled page
    layouts, ragged base lengths, every intra-window causal horizon;
    edge cases as `test_paged_flash_decode_sweep`'s."""
    rng = np.random.default_rng(0)
    if edge is not None:       # windows that end at `_edge_rows`, and
        g, kv = edge           # one that starts at 0
        rows = _edge_rows(page_size, max_pages, g, kv)[1:] + (s,)
        q, kern, orc, tables = _edge_pool(rows, s, g, page_size, max_pages,
                                          kv, rng)
        lengths = jnp.asarray(rows, jnp.int32) - s
        ref = ref_paged_verify(q, *orc[:2], tables, lengths, window, cap,
                               *orc[2:])
        out = paged_flash_verify(q, *kern[:2], tables, lengths,
                                 window=window, attn_cap=cap,
                                 interpret=True, k_scales=kern[2],
                                 v_scales=kern[3])
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
        return
    b, g, qpk, hd = 3, 2, 4, 64
    n_pages = b * max_pages
    q = jnp.asarray(rng.standard_normal((b, s, g, qpk, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, g, page_size, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, g, page_size, hd)),
                     jnp.float32)
    tables = jnp.asarray(
        rng.permutation(n_pages).reshape(b, max_pages), jnp.int32)
    S = max_pages * page_size
    lengths = jnp.asarray(rng.integers(0, S - s + 1, size=b), jnp.int32)
    ref = ref_paged_verify(q, kp, vp, tables, lengths, window, cap)
    out = paged_flash_verify(q, kp, vp, tables, lengths, window=window,
                             attn_cap=cap, interpret=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_paged_verify_s1_matches_paged_decode():
    """A 1-wide verify window IS a decode step (lengths exclusive vs
    inclusive is the only difference in convention)."""
    b, g, qpk, hd, ps, mp = 2, 2, 4, 64, 16, 8
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((b, 1, g, qpk, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((b * mp, g, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((b * mp, g, ps, hd)), jnp.float32)
    tables = jnp.arange(b * mp, dtype=jnp.int32).reshape(b, mp)
    lengths = jnp.asarray([17, 90], jnp.int32)
    dec = ref_paged_decode(q[:, 0], kp, vp, tables, lengths + 1)
    ver = ref_paged_verify(q, kp, vp, tables, lengths)[:, 0]
    assert float(jnp.max(jnp.abs(dec - ver))) < 1e-6
    krn = paged_flash_verify(q, kp, vp, tables, lengths,
                             interpret=True)[:, 0]
    assert float(jnp.max(jnp.abs(dec - krn))) < 1e-5


def test_paged_decode_matches_dense_flash_decode():
    """Identity block table + full lengths == the dense decode kernel."""
    b, g, qpk, hd, ps, n_pg = 2, 2, 2, 32, 16, 16
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, g, qpk, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((b * n_pg // 2, g, ps, hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((b * n_pg // 2, g, ps, hd)),
                     jnp.float32)
    tables = jnp.arange(b * n_pg // 2, dtype=jnp.int32).reshape(b, -1)
    S = (n_pg // 2) * ps
    kd = kp.reshape(b, n_pg // 2, g, ps, hd).swapaxes(2, 3).reshape(
        b, S, g, hd)
    vd = vp.reshape(b, n_pg // 2, g, ps, hd).swapaxes(2, 3).reshape(
        b, S, g, hd)
    pos = jnp.int32(100)
    dense = ref_flash_decode(q, kd, vd, pos)
    paged = ops.paged_decode_attention(
        q, kp, vp, tables, jnp.full((b,), 101, jnp.int32),
        use_kernel=False)
    assert float(jnp.max(jnp.abs(dense - paged))) < 1e-6


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("k,f", [(256, 128), (512, 256)])
def test_swiglu_fused_sweep(bits, k, f):
    wg = jax.random.normal(jax.random.PRNGKey(0), (k, f), jnp.float32) * 0.1
    wu = jax.random.normal(jax.random.PRNGKey(1), (k, f), jnp.float32) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(2), (2, k), jnp.float32)
    qg = quantize(wg, bits, 128)
    qu = quantize(wu, bits, 128)
    ref = ref_swiglu_qgemv(x, qg, qu)
    out = swiglu_qgemv(x, qg.data, qg.scales, qu.data, qu.scales, bits=bits,
                       group=128, block_n=128, block_k=256, interpret=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4


def test_ops_qmatmul_dispatches_and_matches():
    w = jax.random.normal(jax.random.PRNGKey(0), (512, 256), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 512), jnp.float32)
    qt = quantize(w, 4, 128)
    out_kernel = ops.qmatmul(x, qt)          # aligned -> pallas interpret
    out_ref = ops.qmatmul_xla(x, qt)         # dequants to bf16 (serving path)
    rel = float(jnp.max(jnp.abs(out_kernel - out_ref))
                / jnp.max(jnp.abs(out_ref)))
    assert rel < 5e-3


def test_decode_attention_wrapper():
    b, g, qpk, hd, S = 2, 2, 2, 32, 1024
    q = jax.random.normal(jax.random.PRNGKey(0), (b, g, qpk, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, S, g, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, S, g, hd))
    pos = jnp.int32(900)
    out_k = ops.decode_attention(q, k, v, pos, use_kernel=True)
    out_r = ops.decode_attention(q, k, v, pos, use_kernel=False)
    assert float(jnp.max(jnp.abs(out_k - out_r))) < 1e-5
