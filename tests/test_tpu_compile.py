"""The served Pallas kernels and serve steps compile for a TPU v5e.

Compiles against a described `v5e:2x2` topology (no chip attached) at
qwen2.5-3b widths: g=2 KV heads, hd=128, page 16, d_model 2048,
d_ff 11008, vocab 151936, group 128 — and the paged decode kernel at the
benchmark cells' shapes too.  Interpret-mode tests cannot see the
TPU's block-shape and Mosaic lowering rules; these compiles can.  Every
test asserts the kernel is in the compiled program (`tpu_custom_call`).

The topology is described inside a module fixture — never at import —
so every pytest worker collects the same tests and only the worker
running this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

G, QPK, HD, PS = 2, 8, 128, 16
B, MAX_SEQ = 8, 640
N_PAGES = B * MAX_SEQ // PS
D, F, GROUP = 2048, 11008, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _kv(sharding, kv: str):
    """(k, v, k_scales, v_scales) pool structs; scales None for bf16."""
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    pool = jax.ShapeDtypeStruct((N_PAGES, G, PS, HD), dt, sharding=sharding)
    sc = (jax.ShapeDtypeStruct((N_PAGES, G, PS), jnp.float32,
                               sharding=sharding) if kv == "int8" else None)
    return pool, pool, sc, sc


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


# (b, max_seq, g, qpk): the smoke shape at qwen2.5-3b widths, then the
# benchmark cells' decode attention — 32 lanes of 2,048 rows at qwen's
# and phi3-medium's head counts, and the chat cell's 16 lanes of 1,280
_DECODE_SHAPES = {"": (B, MAX_SEQ, G, QPK), "-qwen-batch": (32, 2048, 2, 8),
                  "-phi3-batch": (32, 2048, 10, 4),
                  "-qwen-chat": (16, 1280, 2, 8)}


_DECODE_CASES = [(kv, tag) for tag in _DECODE_SHAPES for kv in ("bf16", "int8")]


@pytest.mark.parametrize("kv,shape", _DECODE_CASES,
                         ids=[kv + tag for kv, tag in _DECODE_CASES])
def test_paged_flash_decode_compiles(one_chip, kv, shape):
    """The block size and VMEM budget the kernel derives from each shape
    pass Mosaic's rules."""
    from repro.kernels.paged_flash_decode import paged_flash_decode
    b, max_seq, g, qpk = _DECODE_SHAPES[shape]
    mp = max_seq // PS
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    pool = jax.ShapeDtypeStruct((b * mp, g, PS, HD), dt, sharding=one_chip)
    sc = (jax.ShapeDtypeStruct((b * mp, g, PS), jnp.float32,
                               sharding=one_chip) if kv == "int8" else None)
    q = jax.ShapeDtypeStruct((b, g, qpk, HD), jnp.bfloat16, sharding=one_chip)
    text = _compile(lambda q, k, v, t, n, ks, vs: paged_flash_decode(
        q, k, v, t, n, k_scales=ks, v_scales=vs),
        q, pool, pool, _i32((b, mp), one_chip), _i32((b,), one_chip),
        sc, sc)
    assert "paged_flash_attention" in text


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_flash_verify_compiles(one_chip, kv):
    from repro.kernels.paged_flash_decode import paged_flash_verify
    k, v, ks, vs = _kv(one_chip, kv)
    q = jax.ShapeDtypeStruct((B, 5, G, QPK, HD), jnp.bfloat16,
                             sharding=one_chip)
    _compile(lambda q, k, v, t, n, ks, vs: paged_flash_verify(
        q, k, v, t, n, k_scales=ks, v_scales=vs),
        q, k, v, _i32((B, MAX_SEQ // PS), one_chip), _i32((B,), one_chip),
        ks, vs)


def _qweight(K, N, bits, sharding):
    data = jax.ShapeDtypeStruct((K // 2, N) if bits == 4 else (K, N),
                                jnp.uint8 if bits == 4 else jnp.int8,
                                sharding=sharding)
    scales = jax.ShapeDtypeStruct((K // GROUP, N), jnp.float32,
                                  sharding=sharding)
    return data, scales


@pytest.mark.parametrize("bits", [8, 4])
def test_swiglu_qgemv_compiles(one_chip, bits):
    from repro.kernels.swiglu_gemv import swiglu_qgemv
    x = jax.ShapeDtypeStruct((B, D), jnp.bfloat16, sharding=one_chip)
    _compile(lambda x, gd, gs, ud, us: swiglu_qgemv(
        x, gd, gs, ud, us, bits=bits, group=GROUP),
        x, *_qweight(D, F, bits, one_chip), *_qweight(D, F, bits, one_chip))


@pytest.mark.parametrize("K,N", [(2048, 2048), (11008, 2048)])
@pytest.mark.parametrize("bits", [8, 4])
def test_cim_gemv_compiles(one_chip, K, N, bits):
    from repro.kernels.cim_gemv import cim_gemv
    x = jax.ShapeDtypeStruct((B, K), jnp.bfloat16, sharding=one_chip)
    _compile(lambda x, d, s: cim_gemv(x, d, s, bits=bits, group=GROUP),
             x, *_qweight(K, N, bits, one_chip))


# ----------------------------------------------------------------------------
# the whole serve step: one chip, and tp=2 on two chips of the host
# ----------------------------------------------------------------------------
@pytest.mark.parametrize("precision,tp", [("fp", 1), ("int4", 1),
                                          ("int4", 2)])
def test_qwen_decode_step_compiles(topo, monkeypatch, precision, tp):
    """qwen2.5-3b's decode step at full width reaches the Pallas kernels
    and compiles — at tp=2 with every kernel in a per-device shard_map."""
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.dist import (SERVE_RULES, qtree_shardings, tree_shardings,
                            use_mesh_rules)
    from repro.kernels import ops
    from repro.models import DecoderLM
    from repro.models.common import spec_structs
    from repro.quant.ptq import quantize_structs

    # the code asks the (CPU) backend which route to take: steer it to
    # the TPU route the described chip compiles
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = DecoderLM(get_config("qwen2.5-3b").replace(remat=False))
    pspecs = model.param_specs()
    params = (quantize_structs(pspecs, bits=4, group=GROUP)
              if precision == "int4" else spec_structs(pspecs))
    kv = jnp.int8 if precision == "int4" else jnp.bfloat16
    sspecs = model.decode_state_specs(B, N_PAGES, PS, kv)["paged"]
    state = spec_structs(sspecs)
    if tp == 1:
        one = SingleDeviceSharding(topo.devices[0])
        psh = jax.tree.map(lambda _: one, params)
        ssh = jax.tree.map(lambda _: one, state)
        rep = one
        step = model.serve_step
    else:
        mesh = Mesh(np.asarray(topo.devices[:tp]), ("model",))
        psh = qtree_shardings(pspecs, params, mesh, SERVE_RULES)
        ssh = tree_shardings(sspecs, mesh, SERVE_RULES)
        rep = NamedSharding(mesh, P())

        def step(*args):
            with use_mesh_rules(mesh, SERVE_RULES):
                return model.serve_step(*args)

    def put(tree, shardings):
        return jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sh), tree, shardings)

    text = _compile(step, put(params, psh), put(state, ssh),
                    {"tokens": _i32((B, 1), rep)},
                    _i32((B, MAX_SEQ // PS), rep), _i32((B,), rep),
                    _i32((B,), rep))
    assert "paged_flash_attention" in text
    if precision == "int4":
        assert "swiglu_qgemv" in text
