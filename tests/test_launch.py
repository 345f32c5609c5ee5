"""Launcher entry points shared with `chip_smoke.py`: model loading,
engine construction with per-replica devices, the compile-cache
location, and the smoke script's refusal to run without a TPU."""
import importlib.util
import os

import jax
import numpy as np
import pytest

from repro.launch import compile_cache
from repro.launch.serve import build_engines, load_model
from repro.serve import ServeConfig, ServeRequest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    return smoke


def test_chip_smoke_refuses_cpu(monkeypatch, capsys):
    smoke = _chip_smoke()
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main() != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out + out.err
    assert "needs a TPU" in out.err


@pytest.mark.parametrize("precision", ["fp", "int4"])
def test_chip_smoke_reads_the_engine_programs(precision):
    """The smoke's handles on an engine's programs: the compiled decode
    program it counts kernels in, and the prefill it compares logits of
    across tp.  A renamed engine handle fails here, not on the chip."""
    smoke = _chip_smoke()
    model, params = load_model("qwen2.5-3b", smoke=True)
    cfg = ServeConfig(precision=precision, quant_group=16, max_batch=2,
                      max_seq=32, page_size=8, prefill_chunk=8)
    eng = build_engines(model, params, cfg)[0]
    hlo = smoke.decode_hlo(eng)
    assert hlo.startswith("HloModule jit_serve_decode")
    assert smoke.custom_calls(hlo, "paged_flash_attention") == 0  # CPU
    prompts = [np.arange(1 + i, 9 + i, dtype=np.int32) for i in range(2)]
    logits = smoke.first_step_logits(eng, prompts)
    assert logits.shape == (2, model.cfg.vocab)
    assert np.isfinite(logits).all()


def test_load_model_dtypes():
    model, params = load_model("qwen2.5-3b", smoke=True)
    assert model.cfg.dtype == "float32"
    leaves = jax.tree_util.tree_leaves(params)
    assert all(l.dtype == np.float32 for l in leaves)
    # the full config keeps its own dtype (traced abstractly: no weights
    # are built here)
    full = jax.eval_shape(lambda: load_model("qwen2.5-3b", smoke=False)[1])
    assert {str(l.dtype) for l in jax.tree_util.tree_leaves(full)} == {
        "bfloat16"}


@pytest.mark.parametrize("precision", ["fp", "int4"])
def test_build_engines_serves_smoke_config(precision):
    model, params = load_model("qwen2.5-3b", smoke=True)
    cfg = ServeConfig(precision=precision, quant_group=16, max_batch=2,
                      max_seq=32, page_size=8, replicas=2)
    engines = build_engines(model, params, cfg)
    assert len(engines) == 2
    # the suite's host exposes 2 devices: one replica each
    on = [{d for l in jax.tree_util.tree_leaves(e.params)
           for d in l.sharding.device_set} for e in engines]
    assert on[0] == {jax.devices()[0]} and on[1] == {jax.devices()[1]}
    outs = []
    for eng in engines:
        reqs = [ServeRequest(prompt=np.arange(1, 6 + i, dtype=np.int32),
                             max_new_tokens=4, rid=i) for i in range(3)]
        eng.run(reqs)
        assert all(len(r.out_tokens) == 4 for r in reqs)
        outs.append([r.out_tokens for r in reqs])
    assert outs[0] == outs[1], "replicas on different devices diverged"


def test_build_engines_shares_devices_when_short():
    model, params = load_model("qwen2.5-3b", smoke=True)
    cfg = ServeConfig(max_batch=2, max_seq=32, page_size=8, replicas=3)
    engines = build_engines(model, params, cfg)
    for eng in engines:
        devs = {d for l in jax.tree_util.tree_leaves(eng.params)
                for d in l.sharding.device_set}
        assert devs == {jax.devices()[0]}


def test_compile_cache_dir(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.compile_cache_dir() == "/elsewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.compile_cache_dir()
    assert path == os.path.join(os.path.realpath(ROOT), ".jax_cache")
    was = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == path
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
