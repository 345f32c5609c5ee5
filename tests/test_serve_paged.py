"""Paged continuous-batching runtime: equivalence vs the dense decode
path, the prefill-clobbering regression, sampling, and telemetry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import DecoderLM, ModelConfig, init_params
from repro.models.config import MLAConfig
from repro.models.common import spec_structs
from repro.serve import (PagedServeEngine, SamplingParams, ServeRequest,
                         sample_tokens)


def _model(**kw):
    cfg = ModelConfig(name="s", family="dense", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                      head_dim=16, dtype="float32", remat=False, **kw)
    model = DecoderLM(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0),
                         dtype_override=jnp.float32)
    return model, params


def _zeros(tree):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  spec_structs(tree))


def _paged_vs_dense(model, params, toks, chunk=4, atol=1e-4):
    """Decode `toks` through decode_step and paged_step; compare logits."""
    cache = _zeros(model.cache_specs(1, 32, jnp.float32))
    dense = []
    for t, tok in enumerate(toks):
        lg, cache = model.decode_step(params, cache,
                                      {"tokens": jnp.asarray([[tok]])},
                                      jnp.int32(t))
        dense.append(np.asarray(lg[0, 0]))

    ps, n_pages = 4, 10
    pool = _zeros(model.paged_cache_specs(n_pages, ps, jnp.float32))
    tables = jnp.asarray([[3, 7, 1, 5, 0, 0, 0, 0]], jnp.int32)
    lg, pool = model.paged_step(
        params, pool, {"tokens": jnp.asarray(toks[None, :chunk])}, tables,
        jnp.asarray([0], jnp.int32), jnp.asarray([chunk], jnp.int32))
    paged = [np.asarray(lg[0, i]) for i in range(chunk)]
    L = chunk
    for tok in toks[chunk:]:
        lg, pool = model.paged_step(
            params, pool, {"tokens": jnp.asarray([[tok]])}, tables,
            jnp.asarray([L], jnp.int32), jnp.asarray([1], jnp.int32))
        paged.append(np.asarray(lg[0, 0]))
        L += 1
    for i, (d, p) in enumerate(zip(dense, paged)):
        np.testing.assert_allclose(p, d, atol=atol,
                                   err_msg=f"position {i}")


def test_paged_matches_dense_gqa():
    model, params = _model()
    toks = np.array([5, 9, 3, 17, 2, 41, 8], np.int32)
    _paged_vs_dense(model, params, toks)


def test_paged_matches_dense_local_window():
    model, params = _model(local_window=3, local_pattern=2,
                           rope_theta_local=10000.0)
    toks = np.array([5, 9, 3, 17, 2, 41, 8, 30], np.int32)
    _paged_vs_dense(model, params, toks)


def test_paged_matches_dense_mla():
    cfg = ModelConfig(name="m", family="dense", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                      head_dim=16, dtype="float32", remat=False,
                      attn_kind="mla",
                      mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=16,
                                    qk_rope_head_dim=8, v_head_dim=16))
    model = DecoderLM(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(1),
                         dtype_override=jnp.float32)
    toks = np.array([5, 9, 3, 17, 2, 41], np.int32)
    _paged_vs_dense(model, params, toks)


# ----------------------------------------------------------------------------
# the seed `_prefill_slot` regression: prefilling one request must not
# clobber cache rows of requests already decoding
# ----------------------------------------------------------------------------
def test_prefill_does_not_clobber_active_requests():
    model, params = _model()
    prompt_a = np.array([1, 2, 3], np.int32)
    prompt_b = np.arange(10, 34, dtype=np.int32) % 64   # long: multi-chunk

    def run(requests):
        eng = PagedServeEngine(model, params, max_batch=2, max_seq=64,
                               page_size=8, prefill_chunk=4)
        eng.run(requests)
        return requests

    solo = run([ServeRequest(prompt=prompt_a, max_new_tokens=12, rid=0)])
    a, b = run([ServeRequest(prompt=prompt_a, max_new_tokens=12, rid=0),
                ServeRequest(prompt=prompt_b, max_new_tokens=4, rid=1)])
    # b's chunked prefill interleaves with a's first decode steps; a's
    # greedy continuation must be identical to running alone
    assert a.out_tokens == solo[0].out_tokens
    assert len(b.out_tokens) == 4


def test_engine_mixed_lengths_more_requests_than_lanes():
    model, params = _model()
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(prompt=rng.integers(0, 64, int(n)
                                             ).astype(np.int32),
                         max_new_tokens=5, rid=i)
            for i, n in enumerate([3, 11, 7, 20, 5])]
    eng = PagedServeEngine(model, params, max_batch=2, max_seq=64,
                           page_size=8, n_pages=12, prefill_chunk=8)
    eng.run(reqs)
    assert all(r.done and len(r.out_tokens) == 5 for r in reqs)
    # drained: every page is free or retained only by the prefix trie
    # (reclaimable on demand) — nothing is leaked to dead sequences
    assert eng.cache.n_free_or_cached() == 12, "pages leaked after drain"
    m = eng.summary()
    assert m["tokens"] == 25
    assert m["kv_occupancy_peak"] <= 1.0
    assert np.isfinite(m["ttft_p50_s"]) and np.isfinite(m["tpot_p50_s"])
    assert m["ttft_p99_s"] >= m["ttft_p50_s"]


def test_paged_pool_smaller_than_dense_on_mixed_workload():
    """The acceptance bar: a workload-sized pool serves a mixed-length
    request set in less KV memory than the dense (n_slots, max_seq)
    cache the seed engine would allocate."""
    model, params = _model()
    rng = np.random.default_rng(1)
    lens = [4, 28, 9, 17]
    max_batch, max_seq, page_size, new = 4, 64, 8, 6
    peak_tokens = sum(n + new for n in lens)
    n_pages = -(-peak_tokens // page_size) + max_batch
    eng = PagedServeEngine(model, params, max_batch=max_batch,
                           max_seq=max_seq, page_size=page_size,
                           n_pages=n_pages, kv_dtype=jnp.bfloat16)
    reqs = [ServeRequest(prompt=rng.integers(0, 64, n).astype(np.int32),
                         max_new_tokens=new, rid=i)
            for i, n in enumerate(lens)]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    row_bytes = eng.cache.kv_bytes() // (n_pages * page_size)
    dense_bytes = max_batch * max_seq * row_bytes
    assert eng.cache.kv_bytes() < dense_bytes


def test_overlong_prompt_rejected_not_crashed():
    model, params = _model()
    eng = PagedServeEngine(model, params, max_batch=2, max_seq=32,
                           page_size=8)
    reqs = [ServeRequest(prompt=np.arange(50, dtype=np.int32) % 64,
                         max_new_tokens=4, rid=0),
            ServeRequest(prompt=np.arange(5, dtype=np.int32),
                         max_new_tokens=4, rid=1)]
    eng.run(reqs)
    assert reqs[0].rejected and reqs[0].out_tokens == []
    assert reqs[1].done and len(reqs[1].out_tokens) == 4


def test_pool_too_small_for_generation_terminates():
    """A request whose generation can never fit the pool must end
    rejected (with partial output), not livelock run() forever."""
    model, params = _model()
    eng = PagedServeEngine(model, params, max_batch=1, max_seq=64,
                           page_size=4, n_pages=3)
    r = ServeRequest(prompt=np.arange(8, dtype=np.int32),
                     max_new_tokens=10, rid=0)
    eng.run([r])           # must return, not spin
    assert r.done and r.truncated and not r.rejected
    assert len(r.out_tokens) >= 1, "partial progress is preserved"


def test_duplicate_default_rids_do_not_collide():
    """rid is a caller label; the engine keys its cache on its own ids,
    so two requests with the default rid=0 must both serve cleanly."""
    model, params = _model()
    eng = PagedServeEngine(model, params, max_batch=2, max_seq=32,
                           page_size=8)
    reqs = [ServeRequest(prompt=np.array([1, 2, 3], np.int32),
                         max_new_tokens=4),
            ServeRequest(prompt=np.array([4, 5, 6], np.int32),
                         max_new_tokens=4)]
    eng.run(reqs)
    assert all(r.done and len(r.out_tokens) == 4 for r in reqs)


def test_empty_prompt_rejected_not_hung():
    model, params = _model()
    eng = PagedServeEngine(model, params, max_batch=1, max_seq=32,
                           page_size=8)
    r = ServeRequest(prompt=np.array([], np.int32), max_new_tokens=4,
                     rid=0)
    eng.run([r])
    assert r.rejected and r.out_tokens == []


def test_shim_accepts_any_max_seq():
    """The seed API took arbitrary max_seq; the shim must keep that."""
    from repro.serve import Request, ServeEngine
    model, params = _model()
    eng = ServeEngine(model, params, n_slots=1, max_seq=100)
    out = eng.run([Request(prompt=np.array([1, 2, 3], np.int32),
                           max_new_tokens=4)])
    assert len(out[0].out_tokens) == 4


def test_engine_preempts_and_recovers_when_pool_exhausts():
    model, params = _model()
    # pool fits both prompts but not both full generations
    eng = PagedServeEngine(model, params, max_batch=2, max_seq=64,
                           page_size=4, n_pages=8, prefill_chunk=8)
    reqs = [ServeRequest(prompt=np.arange(1, 9, dtype=np.int32),
                         max_new_tokens=10, rid=i) for i in range(2)]
    eng.run(reqs)
    assert all(r.done and len(r.out_tokens) >= 10 for r in reqs)
    assert eng.cache.n_free_or_cached() == 8


# ----------------------------------------------------------------------------
# sampling (the seed's softmax-then-argmax bug)
# ----------------------------------------------------------------------------
def test_sample_tokens_greedy_is_argmax():
    logits = jnp.asarray(np.random.default_rng(0)
                         .standard_normal((3, 64)).astype(np.float32))
    out = sample_tokens(jax.random.PRNGKey(0), logits,
                        jnp.zeros(3), jnp.zeros(3, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_sample_tokens_temperature_varies_with_key():
    logits = jnp.zeros((1, 64))          # uniform: sampling must explore
    temp = jnp.ones(1)
    topk = jnp.zeros(1, jnp.int32)
    draws = {int(sample_tokens(jax.random.PRNGKey(k), logits, temp,
                               topk)[0]) for k in range(20)}
    assert len(draws) > 3, "temperature sampling is not degenerate argmax"
    # deterministic per key
    a = sample_tokens(jax.random.PRNGKey(7), logits, temp, topk)
    b = sample_tokens(jax.random.PRNGKey(7), logits, temp, topk)
    assert int(a[0]) == int(b[0])


def test_sample_tokens_top_k_restricts_support():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((1, 64)).astype(np.float32))
    top5 = set(np.asarray(jnp.argsort(logits[0])[::-1][:5]))
    for k in range(30):
        tok = int(sample_tokens(jax.random.PRNGKey(k), logits,
                                jnp.ones(1) * 2.0,
                                jnp.asarray([5], jnp.int32))[0])
        assert tok in top5


def test_sample_tokens_mixed_lanes():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((2, 64)).astype(np.float32))
    out = sample_tokens(jax.random.PRNGKey(0), logits,
                        jnp.asarray([0.0, 1.0]),
                        jnp.asarray([0, 0], jnp.int32))
    assert int(out[0]) == int(jnp.argmax(logits[0]))


def test_sample_tokens_top_p_restricts_support():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((1, 64)).astype(np.float32))
    probs = np.asarray(jax.nn.softmax(logits[0]))
    order = np.argsort(probs)[::-1]
    nucleus = set(order[:np.searchsorted(np.cumsum(probs[order]), 0.5) + 1])
    for k in range(40):
        tok = int(sample_tokens(jax.random.PRNGKey(k), logits,
                                jnp.ones(1), jnp.zeros(1, jnp.int32),
                                jnp.asarray([0.5], jnp.float32))[0])
        assert tok in nucleus, (tok, nucleus)


def test_sample_tokens_top_p_one_keeps_full_support():
    """top_p=1.0 must not truncate: uniform logits stay explorable."""
    logits = jnp.zeros((1, 64))
    draws = {int(sample_tokens(jax.random.PRNGKey(k), logits, jnp.ones(1),
                               jnp.zeros(1, jnp.int32),
                               jnp.ones(1, jnp.float32))[0])
             for k in range(30)}
    assert len(draws) > 5


def test_sample_tokens_top_p_composes_with_top_k():
    """With both active the tighter truncation wins per lane."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((2, 64)).astype(np.float32))
    top3_row1 = set(np.asarray(jnp.argsort(logits[1])[::-1][:3]))
    for k in range(30):
        out = sample_tokens(jax.random.PRNGKey(k), logits,
                            jnp.asarray([0.0, 2.0]),
                            jnp.asarray([0, 3], jnp.int32),
                            jnp.asarray([0.9, 0.99], jnp.float32))
        # lane 0 greedy regardless of truncation params
        assert int(out[0]) == int(jnp.argmax(logits[0]))
        assert int(out[1]) in top3_row1


def test_sample_tokens_vocab_wide_top_k_lane_does_not_untruncate_others():
    """One lane asking for top_k >= vocab must not disable another
    lane's truncation (the batch-max k is clamped, not zeroed)."""
    rng = np.random.default_rng(4)
    v = 16
    logits = jnp.asarray(rng.standard_normal((2, v)).astype(np.float32))
    top3 = set(np.asarray(jnp.argsort(logits[0])[::-1][:3]))
    for k in range(30):
        out = sample_tokens(jax.random.PRNGKey(k), logits,
                            jnp.asarray([2.0, 2.0]),
                            jnp.asarray([3, v], jnp.int32))
        assert int(out[0]) in top3


def test_sample_tokens_and_processed_probs_top_p_zero_is_argmax():
    """top_p <= 0 floors to greedy on BOTH the device path and the host
    mirror (no crash, no empty support)."""
    from repro.serve.sampling import processed_probs
    rng = np.random.default_rng(5)
    logits = rng.standard_normal(32).astype(np.float32)
    best = int(np.argmax(logits))
    p = processed_probs(logits, 1.0, 0, 0.0)
    assert int(np.argmax(p)) == best and p[best] == pytest.approx(1.0)
    for k in range(10):
        tok = int(sample_tokens(jax.random.PRNGKey(k),
                                jnp.asarray(logits[None, :]), jnp.ones(1),
                                jnp.zeros(1, jnp.int32),
                                jnp.zeros(1, jnp.float32))[0])
        assert tok == best


def test_sample_tokens_top_p_always_keeps_argmax():
    """Even a tiny nucleus keeps the most likely token (the exclusive-
    cumsum rule), so sampling never degenerates to an empty support."""
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((1, 32)).astype(np.float32))
    tok = int(sample_tokens(jax.random.PRNGKey(0), logits, jnp.ones(1),
                            jnp.zeros(1, jnp.int32),
                            jnp.asarray([1e-6], jnp.float32))[0])
    assert tok == int(jnp.argmax(logits[0]))


def test_processed_probs_matches_device_truncation():
    """The host-side mirror (speculative acceptance) must keep exactly
    the support the device sampler keeps."""
    from repro.serve.sampling import processed_probs
    rng = np.random.default_rng(3)
    logits = rng.standard_normal(64).astype(np.float32)
    for temp, top_k, top_p in [(1.0, 0, 1.0), (0.7, 5, 1.0),
                               (1.3, 0, 0.6), (0.9, 12, 0.8),
                               (0.0, 0, 1.0)]:
        p = processed_probs(logits, temp, top_k, top_p)
        assert p.shape == (64,) and abs(p.sum() - 1.0) < 1e-9
        support = set(np.nonzero(p > 0)[0])
        if temp <= 0:
            assert support == {int(np.argmax(logits))}
            continue
        draws = set()
        for k in range(200):
            tok = int(sample_tokens(
                jax.random.PRNGKey(k), jnp.asarray(logits[None, :]),
                jnp.asarray([temp]), jnp.asarray([top_k], jnp.int32),
                jnp.asarray([top_p], jnp.float32))[0])
            draws.add(tok)
            assert tok in support, (temp, top_k, top_p)
        # all mass the device explores lives inside the mirror's support
        assert draws <= support


def test_engine_temperature_sampling_end_to_end():
    model, params = _model()
    prompt = np.array([1, 2, 3], np.int32)

    def gen(seed):
        eng = PagedServeEngine(model, params, max_batch=1, max_seq=32,
                               page_size=8, seed=seed)
        r = ServeRequest(prompt=prompt, max_new_tokens=12, rid=0,
                         sampling=SamplingParams(temperature=1.5,
                                                 top_k=40))
        eng.run([r])
        return tuple(r.out_tokens)

    assert gen(0) == gen(0), "same engine seed -> same stream"
    outs = {gen(s) for s in range(4)}
    assert len(outs) > 1, "different seeds explore"


def test_deadline_rejection_and_streaming_callback():
    model, params = _model()
    t = [0.0]

    def clock():
        t[0] += 0.5
        return t[0]

    eng = PagedServeEngine(model, params, max_batch=1, max_seq=32,
                           page_size=8, clock=clock)
    got = []
    ok = ServeRequest(prompt=np.array([1, 2], np.int32), max_new_tokens=3,
                      rid=0, on_token=lambda rid, tok: got.append(tok))
    late = ServeRequest(prompt=np.array([3, 4], np.int32),
                        max_new_tokens=3, rid=1, deadline_s=1e-3,
                        priority=1)
    eng.run([ok, late])
    assert ok.done and got == ok.out_tokens, "streaming callback fires"
    assert late.rejected and late.out_tokens == []


def test_attn_live_page_share_counts_the_pages_each_decode_holds():
    """`attn_live_page_share` is the mean over decode dispatches of
    sum ceil(total / page_size) / (max_batch x max_pages), from the
    lengths each dispatch hands the kernel; absent before any decode."""
    from repro.obs import prometheus_text
    from repro.serve import ServeConfig
    model, params = _model()
    eng = PagedServeEngine(model, params, ServeConfig(
        max_batch=3, max_seq=64, page_size=8, prefill_chunk=8))
    assert "attn_live_page_share" not in eng.summary()
    shares = []
    dispatch = eng._dispatch

    def spy(fn, name, args, tokens, tables, lengths, n_new):
        if name == "decode_step":
            total = lengths + n_new
            shares.append(sum(-(-int(t) // 8) for t in total) / (3 * 8))
        return dispatch(fn, name, args, tokens, tables, lengths, n_new)

    eng._dispatch = spy
    rng = np.random.default_rng(3)
    reqs = [ServeRequest(prompt=rng.integers(0, 64, n).astype(np.int32),
                         max_new_tokens=m, rid=i)
            for i, (n, m) in enumerate([(15, 4), (3, 12), (9, 9)])]
    eng.submit(reqs[0])
    eng.step()               # the first of two prefill chunks, no decode
    assert shares == [] and "attn_live_page_share" not in eng.summary()
    for r in reqs[1:]:
        eng.submit(r)
    while eng.busy:
        eng.step()
    assert len(shares) > 5 and min(shares) < max(shares) < 1
    got = eng.summary()["attn_live_page_share"]
    assert got == pytest.approx(float(np.mean(shares)), rel=1e-12)
    assert "repro_engine_attn_live_page_share" in prometheus_text(
        {"engine": eng.summary()})
