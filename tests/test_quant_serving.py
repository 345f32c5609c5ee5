"""INT4/INT8 serving hot path (PR 8): fused-dequant kernel refs,
quantized paged KV, and the unified ServeConfig precision API.

Covers the residency guarantee (no full-weight float materialization
traced into quantized decode graphs), numerical agreement of the fused
grouped contraction with the dequant oracle, quantized-KV kernels vs
their refs, page conservation under fork/COW/trim/preempt with scale
pages riding along, the legacy-kwarg deprecation shim, and the
quality/capacity acceptance bars (greedy divergence, logit MSE, lane
capacity vs f32 pools)."""
import asyncio
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ref import (ref_paged_decode, ref_paged_verify,
                               ref_qmatmul, ref_qmatmul_fused)
from repro.models import DecoderLM, ModelConfig, init_params
from repro.quant.ptq import quantize_params
from repro.quant.qarray import (QTensor, dequant_counters, quantize,
                                reset_dequant_counters)
from repro.serve import (PagedServeEngine, SamplingParams, ServeConfig,
                         ServeRequest)


# ----------------------------------------------------------------------------
# fused grouped contraction vs the dequant oracle
# ----------------------------------------------------------------------------
@pytest.mark.parametrize("bits", [4, 8])
def test_fused_qmatmul_matches_dequant_oracle_2d(bits):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    qt = quantize(w, bits=bits, group=16, axis=0)
    ref = ref_qmatmul(x, qt, out_dtype=jnp.float32)
    out = ref_qmatmul_fused(x, qt, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_fused_qmatmul_matches_oracle_expert_stack_and_table():
    rng = np.random.default_rng(1)
    # (E, K, N) expert stack, x: (E, C, K)
    xe = jnp.asarray(rng.normal(size=(4, 5, 32)), jnp.float32)
    we = jnp.asarray(rng.normal(size=(4, 32, 24)), jnp.float32)
    qe = quantize(we, bits=4, group=16, axis=1)
    ref = jnp.einsum("ecd,edf->ecf", xe, qe.dequantize(jnp.float32))
    out = ref_qmatmul_fused(xe, qe, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)
    # (V, K) axis=-1 embedding table contracted over K (tied logits)
    h = jnp.asarray(rng.normal(size=(2, 32)), jnp.float32)
    tab = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    qt = quantize(tab, bits=4, group=16, axis=1)
    ref = h @ qt.dequantize(jnp.float32).T
    out = ref_qmatmul_fused(h, qt, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


def test_fused_qmatmul_ignores_stale_orig_shape_from_scan_slicing():
    """Under lax.scan a stacked QTensor's leaves are sliced per layer
    while the static orig_shape aux keeps the layer dim; the fused path
    must size itself from the data, not the aux (regression: reshape
    error inside the scanned serve step)."""
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(2, 32, 24)), jnp.float32)  # (L, K, N)
    qt = quantize(w, bits=4, group=16, axis=1)
    sliced = QTensor(data=qt.data[0], scales=qt.scales[0], bits=4,
                     group=16, axis=qt.axis, orig_shape=qt.orig_shape)
    x = jnp.asarray(rng.normal(size=(3, 32)), jnp.float32)
    ref = x @ qt.dequantize(jnp.float32)[0]
    out = ref_qmatmul_fused(x, sliced, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


def test_dequant_counters_classify_paths():
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    qt = quantize(w, bits=4, group=16, axis=0)
    x = jnp.asarray(rng.normal(size=(1, 32)), jnp.float32)
    reset_dequant_counters()
    ref_qmatmul_fused(x, qt)
    assert dequant_counters() == {"full_dequant": 0, "fused_dequant": 1}
    qt.dequantize()
    assert dequant_counters()["full_dequant"] == 1


# ----------------------------------------------------------------------------
# quantized paged KV: kernels vs refs (interpret mode)
# ----------------------------------------------------------------------------
def _quant_pools(rng, n_pages, ps, g, hd):
    k = rng.normal(size=(n_pages, g, ps, hd)).astype(np.float32)
    v = rng.normal(size=(n_pages, g, ps, hd)).astype(np.float32)

    def q(x):
        scale = (np.maximum(np.abs(x).max(-1), 1e-8) / 127.0
                 ).astype(np.float16)       # the STORED scale is f16-exact
        qi = np.clip(np.round(x / scale[..., None].astype(np.float32)),
                     -127, 127)
        return (jnp.asarray(qi, jnp.int8),
                jnp.asarray(scale, jnp.float32),
                jnp.asarray(qi * scale[..., None].astype(np.float32),
                            jnp.float32))

    kq, ks, kf = q(k)
    vq, vs, vf = q(v)
    return kq, ks, kf, vq, vs, vf


def test_paged_decode_kernel_quantized_kv_matches_ref():
    from repro.kernels.paged_flash_decode import paged_flash_decode
    rng = np.random.default_rng(4)
    b, g, qpk, hd, ps, n_pages = 2, 2, 2, 16, 4, 8
    tables = jnp.asarray(rng.integers(0, n_pages, (b, 4)), jnp.int32)
    lengths = jnp.asarray([9, 14], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, g, qpk, hd)), jnp.float32)
    kq, ks, kf, vq, vs, vf = _quant_pools(rng, n_pages, ps, g, hd)
    ref = ref_paged_decode(q, kq, vq, tables, lengths,
                           k_scales=ks, v_scales=vs)
    ref_float = ref_paged_decode(q, kf, vf, tables, lengths)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ref_float),
                               atol=1e-5)
    out = paged_flash_decode(q, kq, vq, tables, lengths,
                             k_scales=ks, v_scales=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_paged_verify_kernel_quantized_kv_matches_ref():
    from repro.kernels.paged_flash_decode import paged_flash_verify
    rng = np.random.default_rng(5)
    b, s, g, qpk, hd, ps, n_pages = 2, 3, 2, 2, 16, 4, 8
    tables = jnp.asarray(rng.integers(0, n_pages, (b, 4)), jnp.int32)
    lengths = jnp.asarray([5, 8], jnp.int32)       # EXCLUSIVE of window
    q = jnp.asarray(rng.normal(size=(b, s, g, qpk, hd)), jnp.float32)
    kq, ks, _, vq, vs, _ = _quant_pools(rng, n_pages, ps, g, hd)
    ref = ref_paged_verify(q, kq, vq, tables, lengths,
                           k_scales=ks, v_scales=vs)
    out = paged_flash_verify(q, kq, vq, tables, lengths,
                             k_scales=ks, v_scales=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------------------
# ptq: _pick_group 0 sentinel falls back to unquantized, with a warning
# ----------------------------------------------------------------------------
def test_ptq_unquantizable_leaf_warns_and_stays_float():
    from repro.quant.ptq import _pick_group
    assert _pick_group(7, 128, 16) == 0          # prime K < 8: sentinel
    assert _pick_group(4, 128, 16) == 0          # K < smallest group
    assert _pick_group(13, 128, 16) == 13        # 13 >= 8 divides itself,
    # but odd K still skips int4 below (packing needs K % 2 == 0)
    params = {"blocks": {"wq": jnp.ones((2, 13, 8), jnp.float32)}}
    with pytest.warns(UserWarning, match="no valid group size"):
        out = quantize_params(params, bits=4, group=128)
    w = out["blocks"]["wq"]
    assert not isinstance(w, QTensor), "K=13 leaf must stay float"
    assert w.dtype == jnp.float32
    # eligible leaves still quantize in the same tree
    params["blocks"]["wk"] = jnp.ones((2, 16, 8), jnp.float32)
    with pytest.warns(UserWarning, match="wq"):
        out = quantize_params(params, bits=4, group=128)
    assert isinstance(out["blocks"]["wk"], QTensor)


# ----------------------------------------------------------------------------
# ServeConfig API + deprecation shim
# ----------------------------------------------------------------------------
def _model(vocab=64, d=32):
    cfg = ModelConfig(name="s", family="dense", n_layers=2, d_model=d,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=vocab,
                      head_dim=16, dtype="float32", remat=False)
    model = DecoderLM(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0),
                         dtype_override=jnp.float32)
    return model, params


MODEL, PARAMS = _model()


def test_serve_config_validation_and_resolution():
    with pytest.raises(ValueError, match="precision"):
        ServeConfig(precision="fp8")
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeConfig(kv_dtype="f64")
    assert ServeConfig(precision="fp").resolved_kv_dtype() == jnp.bfloat16
    assert ServeConfig(precision="int4").resolved_kv_dtype() == jnp.int8
    assert ServeConfig(precision="int4",
                       kv_dtype="bf16").resolved_kv_dtype() == jnp.bfloat16
    d = ServeConfig(precision="int8").as_dict()
    assert d["kv_dtype_resolved"] == "int8" and d["weight_bits"] == 8
    assert ServeConfig(precision="fp").weight_bits() == 16


def test_legacy_kwargs_shim_warns_once_and_maps():
    import repro.serve.engine as engine_mod
    engine_mod._legacy_warned = False
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng = PagedServeEngine(MODEL, PARAMS, max_batch=2, max_seq=64,
                               page_size=4, kv_dtype=jnp.float32)
        eng2 = PagedServeEngine(MODEL, PARAMS, max_batch=2, max_seq=64,
                                page_size=4)
    deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(deps) == 1, "legacy kwargs warn once per process"
    assert eng.config.precision == "fp"
    assert eng.config.kv_dtype == "f32"
    assert eng.config.max_batch == 2 and eng.config.page_size == 4
    assert eng2.config.kv_dtype == "bf16"


def test_config_and_legacy_kwargs_together_is_an_error():
    with pytest.raises(ValueError, match="not both"):
        PagedServeEngine(MODEL, PARAMS, ServeConfig(), max_batch=2)


def test_engine_quantizes_float_params_when_config_says_so():
    eng = PagedServeEngine(MODEL, PARAMS,
                           ServeConfig(precision="int4", quant_group=16,
                                       max_batch=2, max_seq=64,
                                       page_size=4))
    leaves = jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, QTensor))
    assert any(isinstance(l, QTensor) for l in leaves)
    assert eng.energy.w_bits == 4 and eng.energy.a_bits == 8
    # already-packed params are adopted as-is (replica sharing)
    eng2 = PagedServeEngine(MODEL, eng.params,
                            ServeConfig(precision="int4", quant_group=16,
                                        max_batch=2, max_seq=64,
                                        page_size=4))
    assert eng2.params is eng.params


# ----------------------------------------------------------------------------
# e2e: quantized serving quality + residency + quantized-KV conservation
# ----------------------------------------------------------------------------
def _run_greedy(model, params, cfg, prompt, tokens=12):
    eng = PagedServeEngine(model, params, cfg)
    req = ServeRequest(prompt=prompt, max_new_tokens=tokens, rid=0,
                       sampling=SamplingParams(temperature=0.0))
    eng.run([req])
    return eng, req


def test_quantized_precisions_serve_with_zero_full_dequants():
    prompt = np.arange(1, 9, dtype=np.int32)
    base = ServeConfig(max_batch=2, max_seq=64, page_size=4,
                       quant_group=16)
    _, fp = _run_greedy(MODEL, PARAMS,
                        dataclasses.replace(base, precision="fp"), prompt)
    for precision in ("int8", "int4"):
        cfg = dataclasses.replace(base, precision=precision)
        reset_dequant_counters()
        eng, req = _run_greedy(MODEL, PARAMS, cfg, prompt)
        dq = dequant_counters()
        assert dq["full_dequant"] == 0, \
            f"{precision} traced a full-weight float materialization"
        assert dq["fused_dequant"] > 0
        assert len(req.out_tokens) == len(fp.out_tokens)
        # greedy divergence: int8 must track fp for half the window.
        # The d=32 random-init test model has near-uniform logits, so
        # int4's ~8e-2 logit MSE flips the argmax immediately — its
        # divergence floor is enforced at bench scale by check_bench
        # (--quant-match-min on api_bench_quant), not here.
        if precision == "int8":
            match = 0
            for a, b in zip(fp.out_tokens, req.out_tokens):
                if a != b:
                    break
                match += 1
            assert match >= 6, (fp.out_tokens, req.out_tokens)
        s = eng.summary()
        assert s["weight_full_dequants"] == 0.0
        assert s["weight_fused_dequants"] > 0.0
        assert s["sim_w_bits"] == (8.0 if precision == "int8" else 4.0)


def test_quantized_logit_mse_bounded():
    x = {"tokens": jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])}
    lf = MODEL.forward(PARAMS, x).astype(jnp.float32)
    for bits, ceiling in ((8, 1e-2), (4, 0.5)):
        qp = quantize_params(PARAMS, bits=bits, group=16)
        lq = MODEL.forward(qp, x).astype(jnp.float32)
        mse = float(jnp.mean((lf - lq) ** 2))
        assert mse < ceiling, (bits, mse)


def test_int8_kv_pools_halve_bytes_and_admit_2x_f32_lanes():
    def bytes_per_token(cfg):
        eng = PagedServeEngine(MODEL, PARAMS, cfg)
        total = sum(v.nbytes for v in
                    jax.tree_util.tree_leaves(eng.cache.pools))
        return total / (eng.cache.allocator.n_pages
                        * eng.cache.page_size)

    base = dict(max_batch=2, max_seq=64, page_size=4, quant_group=16)
    f32 = bytes_per_token(ServeConfig(precision="fp", kv_dtype="f32",
                                      **base))
    q = bytes_per_token(ServeConfig(precision="int4", **base))
    assert f32 / q >= 2.0, (f32, q)


def test_quantized_kv_logprobs_track_exact_model():
    """int8 KV pools only quantize the cache: with FLOAT weights, the
    logprob the serving path assigns each sampled token must track the
    exact (non-paged, f32) model's log-softmax for the same stream.
    This bounds the end-to-end int8-KV error without depending on
    argmax stability — the random-init test model's top-1 logit gap
    (~2e-3) is far below even bf16 noise, so greedy-stream equality is
    not a meaningful check at this scale."""
    prompt = np.arange(1, 9, dtype=np.int32)
    cfg = ServeConfig(precision="fp", kv_dtype="int8", max_batch=2,
                      max_seq=64, page_size=4)
    eng = PagedServeEngine(MODEL, PARAMS, cfg)
    req = ServeRequest(prompt=prompt, max_new_tokens=10, rid=0,
                       logprobs=True,
                       sampling=SamplingParams(temperature=1.0))
    eng.run([req])
    assert len(req.out_tokens) == 10
    toks = jnp.asarray(np.concatenate([prompt, req.out_tokens])[None])
    logits = MODEL.forward(PARAMS, {"tokens": toks}).astype(jnp.float32)
    lsm = jax.nn.log_softmax(logits[0], axis=-1)
    errs = [abs(lp - float(lsm[len(prompt) - 1 + i, t]))
            for i, (t, (lp, _)) in
            enumerate(zip(req.out_tokens, req.out_logprobs))]
    assert max(errs) < 0.05, errs


def test_quantized_kv_fork_cow_trim_preempt_conserve_pages():
    """test_cancel's conservation property, on int8 KV pools: any
    interleaving of submits/aborts with fork children and preemptions
    ends with every page free and the scale pages consistent (a fork
    child's greedy stream matches unshared serving, proving COW copied
    the scale pages alongside the int8 rows)."""
    rng = np.random.default_rng(11)
    for trial in range(3):
        cfg = ServeConfig(precision="int4", quant_group=16, max_batch=2,
                          max_seq=32, page_size=4,
                          n_pages=int(rng.integers(10, 16)),
                          prefill_chunk=4,
                          prefix_cache=bool(trial % 2), seed=trial)
        eng = PagedServeEngine(MODEL, PARAMS, cfg)
        n_pages = eng.cache.allocator.n_pages
        reqs, pending = [], []
        for i in range(int(rng.integers(5, 8))):
            prompt = rng.integers(0, 64, int(rng.integers(2, 12))
                                  ).astype(np.int32)
            r = ServeRequest(prompt=prompt, rid=i,
                             max_new_tokens=int(rng.integers(2, 8)),
                             sampling=SamplingParams(
                                 temperature=float(rng.choice([0., 1.]))))
            if reqs and rng.random() < 0.3:
                r.prompt = reqs[-1].prompt.copy()
                r.fork_from = reqs[-1]
            reqs.append(r)
            pending.append(r)
        for _ in range(300):
            if pending and (rng.random() < 0.4 or not eng.busy):
                eng.submit(pending.pop(0))
            elif eng.busy:
                eng.step()
            live = [r for r in reqs if r.eid >= 0 and not r.done]
            if live and rng.random() < 0.2:
                eng.cancel(live[int(rng.integers(0, len(live)))].eid)
            alloc = eng.cache.allocator
            held = {p for pages in alloc._held.values() for p in pages}
            assert alloc.n_free + len(held) == n_pages, \
                (trial, "pages leaked mid-flight")
            if not pending and not eng.busy:
                break
        while eng.busy:
            eng.step()
        assert (eng.cache.n_free_or_cached() == n_pages
                and all(r is None for r in eng.lanes)), trial

    # fork-COW correctness: greedy child == unshared greedy run.
    # Prompt length 10 on page_size 4 shares a PARTIAL tail page
    # (prefix 9 = 2 full pages + 1 token), so the parent's next write
    # must copy-on-write — scale pages ride along with the int8 rows.
    prompt = np.arange(1, 11, dtype=np.int32)
    cfg = ServeConfig(precision="int4", quant_group=16, max_batch=2,
                      max_seq=64, page_size=4)
    _, solo = _run_greedy(MODEL, PARAMS, cfg, prompt, tokens=6)
    eng = PagedServeEngine(MODEL, PARAMS, cfg)
    parent = ServeRequest(prompt=prompt.copy(), max_new_tokens=6, rid=0,
                          sampling=SamplingParams(temperature=0.0))
    child = ServeRequest(prompt=prompt.copy(), max_new_tokens=6, rid=1,
                         fork_from=parent,
                         sampling=SamplingParams(temperature=0.0))
    eng.run([parent, child])
    assert eng.cache.cow_copies > 0, "fork tail page must copy-on-write"
    assert child.out_tokens == solo.out_tokens
    assert parent.out_tokens == solo.out_tokens


def test_mla_rejects_int8_kv():
    from repro.models.config import MLAConfig
    cfg = ModelConfig(name="mla", family="dense", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                      head_dim=16, dtype="float32", remat=False,
                      attn_kind="mla",
                      mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=8,
                                    qk_rope_head_dim=8, v_head_dim=16))
    model = DecoderLM(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0),
                         dtype_override=jnp.float32)
    with pytest.raises(ValueError, match="MLA"):
        PagedServeEngine(model, params,
                         ServeConfig(precision="fp", kv_dtype="int8",
                                     max_batch=2, max_seq=64,
                                     page_size=4))
    # auto means "best supported": quantized weights on MLA degrade the
    # KV pools to bf16 instead of crashing, and the engine's config
    # reports the pinned resolution
    eng = PagedServeEngine(model, params,
                           ServeConfig(precision="int4", quant_group=16,
                                       max_batch=2, max_seq=64,
                                       page_size=4))
    assert eng.config.kv_dtype == "bf16"
    assert eng.config.as_dict()["kv_dtype_resolved"] == "bfloat16"


# ----------------------------------------------------------------------------
# /metrics reports the resolved config
# ----------------------------------------------------------------------------
def test_fleet_metrics_reports_resolved_config():
    from repro.fleet import FleetRouter
    cfg = ServeConfig(precision="int4", quant_group=16, max_batch=2,
                      max_seq=64, page_size=4, max_pending=5)
    eng = PagedServeEngine(MODEL, PARAMS, cfg)
    router = FleetRouter([eng]).start()
    try:
        payload = asyncio.run(router.fleet_metrics())
    finally:
        router.stop()
    c = payload["config"]
    assert c["precision"] == "int4"
    assert c["kv_dtype_resolved"] == "int8"
    assert c["weight_bits"] == 4
    assert router.replicas[0].max_pending == 5, \
        "router must adopt the config's per-replica cap"
