"""The model-family seam.

The dense GQA configurations get from their family exactly the numbers
that every reading of their cells rests on (`dense_gqa_golden.json`:
dimensions, decode work on a fixed list of contexts, the shapes of the
seeded leaves at the published widths, the tiny model's leaves, the
reference's gaps on fixed sequences, the tiny run's widest gap).  A
configuration names a family or is refused.  And an architecture that
exists only as files under the tests (`fixture_families/tiny_moe.py`,
its reference `fixture_references/tiny_moe_ref.py` and
`tiny_moe_config.json`) runs through the harness, its per-layer readers
take its own work count, and no other file knows it.
"""
import functools
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells
import tiny
import weights as W

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = tiny.load("dense_gqa_golden.json")
CONFIGS = {"qwen2.5-3b-int4": cells.config("qwen2.5-3b-int4"),
           "phi3-medium-14b-int4": cells.config("phi3-medium-14b-int4"),
           "tiny": tiny.load("tiny_config.json")}
# the fixture family's sound tiny runs read a widest gap of 0-0.18 over
# 15 seeds and its control 0.58-3.63 (tiny_moe.py: routing ties)
MOE_LIMITS = {"sample_requests": 4, "widest_gap": {"limit": 0.4}}


def _fixture_dirs(monkeypatch):
    monkeypatch.setattr(cells, "FAMILIES",
                        os.path.join(HERE, "fixture_families"))
    monkeypatch.setattr(cells, "REFERENCES",
                        os.path.join(HERE, "fixture_references"))


def _gap_sequences():
    rng = np.random.default_rng(GOLDEN["gap_sequences_rng"])
    return [{"prompt": rng.integers(0, 512, n).tolist(),
             "served": rng.integers(0, 512, m).tolist()}
            for n, m in ((5, 3), (40, 20), (17, 9), (29, 14))]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_dense_family_gives_the_numbers_the_readings_rest_on(name):
    cfg, want = CONFIGS[name], GOLDEN["configs"][name]
    fam = cells.family(cfg)
    d = fam.dims(cfg)
    assert d == want["dims"]
    ctxs = GOLDEN["contexts"]
    assert fam.decode_work(d, ctxs, 7, 32, 1) == want["work"]["int8"]
    assert fam.decode_work(d, ctxs, 3, 16, 2) == want["work"]["bf16"]
    shapes = jax.eval_shape(functools.partial(fam.all_leaves, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    got = {jax.tree_util.keystr(p): [list(s.shape), str(s.dtype)]
           for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert got == want["leaves"]


def test_tiny_leaves_are_those_of_the_seed():
    cfg = CONFIGS["tiny"]
    fam = cells.family(cfg)
    leaves = jax.jit(functools.partial(fam.all_leaves, cfg))(
        W.root_key(GOLDEN["seed"]))
    h = hashlib.sha256()
    for p, x in jax.tree_util.tree_flatten_with_path(leaves)[0]:
        h.update(jax.tree_util.keystr(p).encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == GOLDEN["tiny_leaves_sha256"]
    # what the reference regenerates one layer at a time is what the
    # program was handed
    one = fam.layer_leaves(cfg, W.root_key(GOLDEN["seed"]), 1)
    sliced = jax.tree.map(lambda a: a[1], leaves["layers"])
    assert all(jax.tree.leaves(jax.tree.map(np.array_equal, one, sliced)))


def test_the_reference_reads_the_same_gaps_on_fixed_sequences():
    cfg = CONFIGS["tiny"]
    ref = cells.reference(cells.family(cfg))
    seqs = _gap_sequences()
    assert ref.served_gap(cfg, GOLDEN["seed"], seqs) == pytest.approx(
        GOLDEN["served_gap"], rel=1e-6)
    both = ref.control_gap(cfg, GOLDEN["seed"], seqs)
    for k in ("served", "control"):
        assert both[k] == pytest.approx(GOLDEN["control_gap"][k], rel=1e-6)


def test_tiny_run_reads_the_same_widest_gap():
    res = tiny.run()
    assert res["checks"]["widest_gap"]["value"] == \
        GOLDEN["tiny_run"]["widest_gap"]
    assert res["_extra"]["mean_gap"] == GOLDEN["tiny_run"]["mean_gap"]


@pytest.mark.parametrize("cfg", [{}, {"family": ""}, {"family": "ghost"}])
def test_a_configuration_must_name_a_family_that_exists(cfg):
    with pytest.raises(KeyError):
        cells.family(cfg)


def test_model_step_readers_take_the_work_of_the_family(monkeypatch):
    _fixture_dirs(monkeypatch)
    cfg = tiny.load("tiny_moe_config.json")
    fam = cells.family(cfg)
    d = fam.dims(cfg)
    ctxs = [5, 40, 300]
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e10}
    ctx = {"family": fam, "dims": d, "decode_contexts": ctxs,
           "kv_bytes": 1, "engine": {"max_batch": 4}, "peaks": peaks,
           "trace": {"window_s": 2.0, "programs": {"jit_serve_decode": {
               "runs": 3, "seconds": 0.5,
               "ops": {"paged_flash_attention": {
                   "count": 6, "seconds": 0.1, "meta": ""}}}}}}
    w = fam.decode_work(d, ctxs, 0, 0, 1)
    assert cells.metric_reader("decode_mfu_pct").read(ctx) == \
        pytest.approx(100.0 * w["flops"] / (2.0 * 1e12))
    w = fam.decode_work(d, ctxs, 3, 4, 1)
    least = max(w["flops"] / 1e12, w["bytes"] / 1e10)
    assert cells.metric_reader("decode_roofline_pct").read(ctx) == \
        pytest.approx(100.0 * least / 0.5)


def test_a_new_family_runs_from_its_own_files_alone(monkeypatch):
    _fixture_dirs(monkeypatch)
    fam = cells.family(tiny.load("tiny_moe_config.json"))
    assert fam.__file__.startswith(HERE)
    assert cells.reference(fam).__file__.startswith(HERE)
    res = tiny.run(config="tiny_moe_config.json", limits=MOE_LIMITS)
    assert res["correct"] is True
    assert res["_extra"]["compared_tokens"] > 0
    ctl = tiny.run(config="tiny_moe_config.json", limits=MOE_LIMITS,
                   control=True)
    assert ctl["correct"] is False
