"""The system under test, built from a configuration file.

This module and the families' `model_config` and `program_params`
(`bench/families/<family>.py`) are the only code of a run that imports
the program (`repro`).  The configuration's family maps its sizes onto
the program's model config and lays the benchmark's packed int4 weights
out as the program's parameter tree of `QTensor`s; this module checks
that tree against the model's own specs and builds the engines the way
the launcher does (`repro.launch.serve.build_engines`): a `FleetRouter`
over `PagedServeEngine`s, served by an in-process `Gateway`.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax

import cells
import weights as W


def qtensor(codes, scales, shape, axis: int = -2):
    """The program's `QTensor` over packed int4 codes and their scales
    (`weights.packed`), grouped along `axis`."""
    from repro.quant.qarray import QTensor
    return QTensor(data=codes, scales=scales, bits=4, group=W.GROUP,
                   axis=axis, orig_shape=shape)


def make_leaves(cfg: Dict, seed: int) -> Dict:
    """Every weight from the seed, in one device program."""
    fn = jax.jit(functools.partial(cells.family(cfg).all_leaves, cfg))
    return jax.block_until_ready(fn(W.root_key(seed)))


def param_tree(cfg: Dict, leaves: Dict, model) -> Dict:
    """The family's parameter tree over the benchmark's leaves, checked
    against the model's own parameter specs."""
    tree = cells.family(cfg).program_params(cfg, leaves, model)
    _check_against_specs(tree, model.param_specs())
    return tree


def _check_against_specs(params, specs) -> None:
    from repro.models.common import is_spec
    from repro.quant.qarray import QTensor
    got = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, QTensor))[0]
    want = dict(jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=is_spec)[0])
    if len(got) != len(want):
        raise ValueError(f"weights have {len(got)} leaves, the model "
                         f"{len(want)}")
    for path, leaf in got:
        spec = want[path]
        shape = leaf.orig_shape if isinstance(leaf, QTensor) else leaf.shape
        if tuple(shape) != tuple(spec.shape):
            raise ValueError(f"{jax.tree_util.keystr(path)}: weights "
                             f"{tuple(shape)}, model {tuple(spec.shape)}")


def serve_config(cfg: Dict, engine: Dict):
    from repro.serve import ServeConfig
    return ServeConfig(precision=cfg["precision"], kv_dtype=cfg["kv_dtype"],
                       quant_group=W.GROUP, max_batch=engine["max_batch"],
                       max_seq=engine["max_seq"],
                       page_size=engine["page_size"],
                       prefill_chunk=engine["prefill_chunk"],
                       max_pending=engine["max_pending"])


def build(cfg: Dict, engine: Dict, seed: int):
    """(model, engines) serving the seed's weights."""
    from repro.launch.serve import build_engines
    from repro.models import DecoderLM
    model = DecoderLM(cells.family(cfg).model_config(cfg))
    leaves = make_leaves(cfg, seed)
    params = param_tree(cfg, leaves, model)
    del leaves
    return model, build_engines(model, params, serve_config(cfg, engine))


def warm_up(engines, engine: Dict, vocab: int) -> None:
    """Compile every shape the window uses: a chunked prefill that ends a
    prompt and samples, and decode steps."""
    import numpy as np
    from repro.serve import ServeRequest
    rng = np.random.default_rng(0)
    n = engine["prefill_chunk"] + 1
    for eng in engines:
        eng.run([ServeRequest(prompt=rng.integers(0, vocab, n).astype(
            np.int32), max_new_tokens=3, rid=i) for i in range(2)])


def router_and_gateway(engines):
    from repro.api import Gateway
    from repro.fleet import FleetRouter
    router = FleetRouter(engines)
    return router, Gateway(router)


def telemetry_snapshot(eng) -> Dict:
    """Counters of the engine's `Telemetry` (run on the engine's thread)."""
    import time
    t = eng.telemetry
    return {"t": time.monotonic(), "decode_s": t.decode_s,
            "prefill_s": t.prefill_s, "steps": t.steps,
            "decode_steps": t.decode_steps,
            "batch_samples": list(t.batch_samples),
            "queue": [(tr.t_enqueue, tr.t_admit)
                      for tr in t.traces.values() if tr.t_admit is not None]}


def tracer():
    from repro.obs.trace import get_tracer
    return get_tracer()


def kv_dtype_name(engines) -> str:
    return engines[0].config.as_dict()["kv_dtype_resolved"]


def free(engines) -> None:
    """Drop the engines' device state: weights and KV pools."""
    for eng in engines:
        eng.params = None
        eng.cache.pools = {}
