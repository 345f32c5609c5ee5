"""Tensor-parallel serving (PR 9): the serve hot path on a ("model",)
mesh.

The invariant everything here leans on: tp is an execution detail, not
a semantics knob.  A tp=2 engine on the forced 2-device host mesh
(conftest sets --xla_force_host_platform_device_count=2 before jax
initializes) must produce byte-identical greedy streams to tp=1 — for
float AND int4 weights, through speculative decoding, and for
recurrent-state (arena) families — while page/lane bookkeeping stays
exact under random abort/fork/preempt interleavings on the sharded
pools.  Config validation must fail loudly (non-dividing dims, too few
devices), never silently degrade.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import SERVE_RULES, serve_mesh
from repro.models import DecoderLM, ModelConfig, init_params
from repro.models.config import SSMConfig
from repro.quant.qarray import dequant_counters, reset_dequant_counters
from repro.serve import (PagedServeEngine, SamplingParams, ServeConfig,
                         ServeRequest)


def _dense(seed=0, **kw):
    base = dict(name="s", family="dense", n_layers=2, d_model=32,
                n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                head_dim=16, dtype="float32", remat=False)
    cfg = ModelConfig(**{**base, **kw})
    model = DecoderLM(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(seed),
                         dtype_override=jnp.float32)
    return model, params


def _xlstm():
    cfg = ModelConfig(name="x", family="xlstm", n_layers=4, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                      head_dim=16, dtype="float32", remat=False,
                      ssm=SSMConfig(mlstm_heads=2, slstm_every=2))
    model = DecoderLM(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0),
                         dtype_override=jnp.float32)
    return model, params


def _prompts(vocab=64, n=6):
    rng = np.random.default_rng(0)
    return [rng.integers(0, vocab, int(k)).astype(np.int32)
            for k in rng.integers(4, 17, size=n)]


def _run(model, params, cfg, prompts, new=12, spec=None):
    eng = PagedServeEngine(model, params, cfg, spec=spec)
    reqs = [ServeRequest(prompt=p.copy(), max_new_tokens=new, rid=i)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    return [r.out_tokens for r in reqs], eng


# ----------------------------------------------------------------------------
# SERVE_RULES pspec units
# ----------------------------------------------------------------------------
def test_serve_rules_pspec_units():
    # only tensor-parallel-marked dims shard; batch/page/seq axes stay
    # replicated so block tables and lane bookkeeping remain host-side
    # per-shard-identical
    assert SERVE_RULES.pspec(("tp",)) == P("model")
    assert SERVE_RULES.pspec(("expert",)) == P("model")
    assert SERVE_RULES.pspec(("batch", None, "tp")) == \
        P(None, None, "model")
    assert SERVE_RULES.pspec(("batch", "kv_seq", "tp", None)) == \
        P(None, None, "model", None)
    assert SERVE_RULES.pspec(("layers", "fsdp", "seq")) == P(None, None,
                                                            None)


# ----------------------------------------------------------------------------
# config / mesh validation: fail loudly, never silently degrade
# ----------------------------------------------------------------------------
def test_serveconfig_tp_validation():
    with pytest.raises(ValueError, match="tp must be >= 1"):
        ServeConfig(tp=0)
    with pytest.raises(ValueError, match="tp must be >= 1"):
        serve_mesh(0)
    # more shards than devices: the error names the count AND the
    # host-mesh escape hatch instead of an opaque mesh failure
    n = len(jax.devices())
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        serve_mesh(n + 1)


def test_engine_rejects_non_dividing_tp_dims():
    # 3 heads / d_ff=96 on tp=2: the engine must refuse with the dims
    # named rather than building a mesh that unevenly shards the pools
    model, params = _dense(n_heads=3, n_kv_heads=3, d_model=48, d_ff=96)
    with pytest.raises(ValueError, match="does not divide"):
        PagedServeEngine(model, params,
                         ServeConfig(max_batch=2, max_seq=32, page_size=8,
                                     tp=2))
    with pytest.raises(ValueError, match="n_heads"):
        model.validate_tp(2)
    # tp=3 divides 3 heads/96 ffn but exceeds the 2-device host mesh
    with pytest.raises(ValueError, match="devices"):
        PagedServeEngine(model, params,
                         ServeConfig(max_batch=2, max_seq=32, page_size=8,
                                     tp=3))


# ----------------------------------------------------------------------------
# the acceptance bar: tp=2 greedy == tp=1 greedy, byte for byte
# ----------------------------------------------------------------------------
@pytest.mark.parametrize("precision", ["fp", "int4"])
def test_tp2_greedy_byte_identical(precision):
    model, params = _dense()
    prompts = _prompts()

    def cfg(tp):
        return ServeConfig(precision=precision, quant_group=16,
                           max_batch=4, max_seq=64, page_size=8, tp=tp)

    base, eng1 = _run(model, params, cfg(1), prompts)
    reset_dequant_counters()
    out, eng2 = _run(model, params, cfg(2), prompts)
    assert out == base, f"tp=2 diverged from tp=1 at precision={precision}"

    # weights are actually distributed, not silently replicated
    leaves = jax.tree_util.tree_leaves(eng2.params)
    assert any(len(l.sharding.device_set) == 2 for l in leaves), \
        "tp=2 engine left every param leaf on one device"
    # ... and so are the KV pools
    pool_leaves = jax.tree_util.tree_leaves(eng2.cache.pools)
    assert any(len(l.sharding.device_set) == 2 for l in pool_leaves), \
        "tp=2 engine left every KV pool leaf on one device"

    if precision == "int4":
        # residency guarantee survives sharding: no whole-weight float
        # materialization traced into the tp=2 graphs
        assert dequant_counters()["full_dequant"] == 0, \
            "tp=2 quantized hot path traced a full-weight dequant"

    # energy accounting: same token stream => same aggregate joules;
    # tp models aggregate bandwidth, so simulated wall time halves and
    # per-device keys carry each shard's slice
    s1, s2 = eng1.summary(), eng2.summary()
    assert s2["sim_tp"] == 2.0 and "sim_tp" not in s1
    np.testing.assert_allclose(s2["sim_energy_j"], s1["sim_energy_j"],
                               rtol=1e-9)
    np.testing.assert_allclose(s2["sim_time_s"], s1["sim_time_s"] / 2,
                               rtol=1e-9)
    np.testing.assert_allclose(s2["sim_energy_j_per_device"],
                               s2["sim_energy_j"] / 2, rtol=1e-9)


def test_tp2_spec_ngram_byte_identical():
    """Speculative decoding rides the sharded verify step: tp=2 with an
    n-gram drafter must still match plain tp=1 decode byte-for-byte
    (the engine rewraps `paged_verify_step` with the mesh-aware jit)."""
    from repro.spec import SpecConfig
    model, params = _dense()
    prompts = [np.array([1, 2, 3, 1, 2, 3, 1, 2], np.int32),
               np.array([7, 9, 11], np.int32),
               np.arange(10, 30, dtype=np.int32) % 64]

    def cfg(tp):
        return ServeConfig(max_batch=2, max_seq=64, page_size=8,
                           prefill_chunk=8, tp=tp)

    base, _ = _run(model, params, cfg(1), prompts)
    out, eng = _run(model, params, cfg(2), prompts,
                    spec=SpecConfig(k=4, drafter="ngram"))
    assert out == base
    assert eng.summary()["spec_drafted"] > 0
    assert eng.cache.n_free_or_cached() == eng.cache.allocator.n_pages


def test_tp2_recurrent_arena_byte_identical():
    """StateArena lanes (xlstm mLSTM/sLSTM state) shard their TP cell
    dims; save/restore/reset are eager gather/scatters on the sharded
    leaves and must not perturb the stream."""
    model, params = _xlstm()
    prompts = _prompts(n=4)

    def cfg(tp):
        return ServeConfig(max_batch=2, max_seq=32, page_size=8, tp=tp)

    base, _ = _run(model, params, cfg(1), prompts, new=8)
    out, eng = _run(model, params, cfg(2), prompts, new=8)
    assert out == base, "tp=2 recurrent stream diverged from tp=1"
    assert eng.arena is not None
    arena_leaves = jax.tree_util.tree_leaves(eng.arena.state)
    assert any(len(l.sharding.device_set) == 2 for l in arena_leaves), \
        "tp=2 engine left every arena leaf on one device"


# ----------------------------------------------------------------------------
# page/lane conservation on sharded pools under abort/fork/preempt
# ----------------------------------------------------------------------------
def test_tp2_page_conservation_random_interleavings():
    """test_cancel's conservation property, on tp=2 sharded int4 pools:
    any interleaving of submits/aborts with fork children and
    preemptions ends with every page free and every lane empty.  Block
    tables and refcounts are host-side and per-shard-identical, so the
    invariant must hold exactly as at tp=1."""
    model, params = _dense()
    rng = np.random.default_rng(11)
    for trial in range(2):
        cfg = ServeConfig(precision="int4", quant_group=16, max_batch=2,
                          max_seq=32, page_size=4,
                          n_pages=int(rng.integers(10, 16)),
                          prefill_chunk=4, seed=trial, tp=2)
        eng = PagedServeEngine(model, params, cfg)
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
                (trial, "pages leaked mid-flight on sharded pools")
            if not pending and not eng.busy:
                break
        while eng.busy:
            eng.step()
        assert (eng.cache.n_free_or_cached() == n_pages
                and all(r is None for r in eng.lanes)), trial
        # the sharded pools survived the churn with their canonical
        # shardings intact (out_shardings pins them step over step)
        pool_leaves = jax.tree_util.tree_leaves(eng.cache.pools)
        assert any(len(l.sharding.device_set) == 2 for l in pool_leaves)


# ----------------------------------------------------------------------------
# Pallas kernels under the serve mesh: one call per device (shard_map)
# ----------------------------------------------------------------------------
@pytest.mark.parametrize("quant_kv", [False, True])
def test_tp2_paged_kernel_runs_per_shard(quant_kv):
    """XLA cannot partition a Mosaic kernel, so under a tp=2 mesh the
    paged kernel runs once per device on that device's kv heads; the
    result must equal the unsharded gather reference."""
    from repro.dist import use_mesh_rules
    from repro.kernels import ops
    from repro.kernels.ref import ref_paged_decode
    mesh = serve_mesh(2)
    rng = np.random.default_rng(3)
    b, g, qpk, hd, ps, mp = 2, 2, 4, 64, 8, 4
    q = jnp.asarray(rng.normal(size=(b, g, qpk, hd)), jnp.float32)
    shape = (b * mp, g, ps, hd)
    if quant_kv:
        k = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.5, 1.5, shape[:3]) / 127,
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.5, 1.5, shape[:3]) / 127,
                         jnp.float32)
    else:
        k = jnp.asarray(rng.normal(size=shape), jnp.float32)
        v = jnp.asarray(rng.normal(size=shape), jnp.float32)
        ks = vs = None
    tables = jnp.asarray(rng.permutation(b * mp).reshape(b, mp), jnp.int32)
    lengths = jnp.asarray([5, 29], jnp.int32)

    @jax.jit
    def sharded(q, k, v, tables, lengths, ks, vs):
        with use_mesh_rules(mesh, SERVE_RULES):
            return ops.paged_decode_attention(q, k, v, tables, lengths,
                                              use_kernel=True, k_scales=ks,
                                              v_scales=vs)

    out = sharded(q, k, v, tables, lengths, ks, vs)
    ref = ref_paged_decode(q, k, v, tables, lengths, k_scales=ks,
                           v_scales=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_tp2_swiglu_kernel_runs_per_shard():
    """The fused SwiGLU kernel under tp=2 computes each device's FFN
    columns and leaves the output column-sharded for w_down."""
    from repro.dist import use_mesh_rules
    from repro.kernels import ops
    from repro.kernels.ref import ref_swiglu_qgemv
    from repro.quant.qarray import quantize
    mesh = serve_mesh(2)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    wg = quantize(jax.random.normal(keys[0], (256, 512)) * 0.1, 4, 128)
    wu = quantize(jax.random.normal(keys[1], (256, 512)) * 0.1, 4, 128)
    x = jax.random.normal(keys[2], (3, 256))

    @jax.jit
    def sharded(x, wg, wu):
        with use_mesh_rules(mesh, SERVE_RULES):
            return ops.swiglu(x, wg, wu, use_kernel=True)

    out = sharded(x, wg, wu)
    assert out.sharding.spec == P(None, "model")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref_swiglu_qgemv(x, wg, wu)),
                               atol=1e-4)
