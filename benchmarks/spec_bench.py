"""Speculative-decoding benchmark: drafter x k x workload sweep.

Runs the paged engine with and without speculation on workloads at both
ends of the draftability spectrum and reports, per cell:

  * acceptance rate and mean tokens emitted per verify step
  * decode-graph tokens/s vs the non-speculative baseline
  * the analytical SpecKnob speedup the measured acceptance rate
    implies for the paper's accelerator (ties the runtime measurement
    back to the DSE cost model)

Workloads:
  repetitive   prompts with strong n-gram structure (extractive /
               templated traffic — where prompt-lookup shines)
  random       uniform random prompts (worst case: model drafter only)

  PYTHONPATH=src python benchmarks/spec_bench.py [--scale 8] [--tokens 24]
"""
import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from common import save_json  # noqa: E402
from serve_bench import warm_engine  # noqa: E402

from repro.core import EdgeCIMSimulator, SpecKnob  # noqa: E402
from repro.core.hw import HWConfig  # noqa: E402
from repro.core.workload import make_dense_spec  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import DecoderLM, ModelConfig, init_params  # noqa: E402
from repro.serve import PagedServeEngine, ServeRequest  # noqa: E402
from repro.spec import SpecConfig  # noqa: E402

VOCAB = 512


def build_model(scale: int, n_layers: int, seed: int = 0):
    cfg = ModelConfig(name="bench", family="dense", n_layers=n_layers,
                      d_model=2048 // scale, n_heads=max(32 // scale, 1),
                      n_kv_heads=8 // min(scale, 8) or 1,
                      d_ff=8192 // scale, vocab=VOCAB, head_dim=64,
                      dtype="float32", remat=False)
    model = DecoderLM(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(seed),
                         dtype_override=jnp.float32)
    return model, params


def make_requests(workload: str, n_requests: int, tokens: int):
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(n_requests):
        if workload == "repetitive":
            motif = rng.integers(0, VOCAB, 4).astype(np.int32)
            reps = int(rng.integers(3, 6))
            prompt = np.tile(motif, reps)
        else:
            prompt = rng.integers(0, VOCAB,
                                  int(rng.integers(8, 24))).astype(np.int32)
        reqs.append(ServeRequest(prompt=prompt, max_new_tokens=tokens,
                                 rid=i))
    return reqs


def run_one(model, params, spec_cfg, *, workload: str, n_requests: int,
            tokens: int, batch: int, max_seq: int):
    reqs = make_requests(workload, n_requests, tokens)
    eng = PagedServeEngine(model, params, max_batch=batch, max_seq=max_seq,
                           page_size=8, prefill_chunk=16, spec=spec_cfg)
    warm_engine(eng, vocab=VOCAB)
    t0 = time.monotonic()
    eng.run(reqs)
    wall = time.monotonic() - t0
    assert all(r.done for r in reqs)
    m = eng.summary()
    return {
        "wall_s": wall,
        "tokens": m["tokens"],
        "decode_steps": m["decode_steps"],
        "tokens_per_s_decode": eng.throughput(),
        "tokens_per_step": m["tokens_per_decode_step"],
        "acceptance_rate": m["spec_acceptance_rate"],
        "drafted": m["spec_drafted"],
        "accepted": m["spec_accepted"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=8)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--ks", type=int, nargs="+", default=[2, 4])
    # "self" drafts with the TARGET model: random-weight draft models
    # can't agree with a random-weight target, so this cell calibrates
    # the acceptance upper bound (~1.0) the verify pipeline supports at
    # the worst-case draft cost (ratio 1.0)
    ap.add_argument("--drafters", nargs="+",
                    default=["ngram", "model", "self"])
    args = ap.parse_args()
    enable_compile_cache()

    model, params = build_model(args.scale, args.layers)
    # draft model: same family, 1 layer and half width (~8x fewer params)
    draft_model, draft_params = build_model(args.scale * 2, 1, seed=7)
    print(f"target: {model.n_params()/1e6:.1f}M params, draft: "
          f"{draft_model.n_params()/1e6:.1f}M, "
          f"backend={jax.default_backend()}")
    draft_ratio = draft_model.n_params() / model.n_params()

    sim = EdgeCIMSimulator()
    slm = make_dense_spec("bench", 24, 2048, 16, 8, 5632, 32000)
    hw = HWConfig()
    base_lat = sim.generate(slm, hw, 128, 128).latency_s

    rows = []
    print("workload,drafter,k,acc_rate,tok/step,tok/s,baseline_tok/s,"
          "speedup,sim_speedup")
    for workload in ("repetitive", "random"):
        base = run_one(model, params, None, workload=workload,
                       n_requests=args.requests, tokens=args.tokens,
                       batch=args.batch, max_seq=args.max_seq)
        for drafter in args.drafters:
            for k in args.ks:
                if drafter == "model":
                    sc = SpecConfig(k=k, drafter="model",
                                    draft_model=draft_model,
                                    draft_params=draft_params,
                                    draft_page_size=8)
                elif drafter == "self":
                    sc = SpecConfig(k=k, drafter="model",
                                    draft_model=model,
                                    draft_params=params,
                                    draft_page_size=8)
                else:
                    sc = SpecConfig(k=k, drafter="ngram")
                r = run_one(model, params, sc, workload=workload,
                            n_requests=args.requests, tokens=args.tokens,
                            batch=args.batch, max_seq=args.max_seq)
                acc = r["acceptance_rate"]
                knob = SpecKnob(
                    k=k, accept_rate=0.0 if np.isnan(acc) else acc,
                    draft_cost_ratio={"model": draft_ratio,
                                      "self": 1.0}.get(drafter, 0.0))
                sim_speedup = base_lat / sim.generate(
                    slm, hw, 128, 128, spec_decode=knob).latency_s
                row = {"workload": workload, "drafter": drafter, "k": k,
                       "baseline_tokens_per_s": base["tokens_per_s_decode"],
                       "sim_speedup": sim_speedup, **r}
                rows.append(row)
                print(f"{workload},{drafter},{k},{acc:.2f},"
                      f"{r['tokens_per_step']:.2f},"
                      f"{r['tokens_per_s_decode']:.1f},"
                      f"{base['tokens_per_s_decode']:.1f},"
                      f"{r['tokens_per_s_decode']/base['tokens_per_s_decode']:.2f},"
                      f"{sim_speedup:.2f}")
    save_json("spec_bench", rows)


if __name__ == "__main__":
    main()
