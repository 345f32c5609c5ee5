"""Serving benchmark: batch-size x prompt-mix sweep on the paged engine.

Measures what the paper simulates — decode throughput and latency of a
batched SLM under a mixed-length request stream — on the real runtime:

  * tokens/s (decode-graph time and wall clock)
  * TTFT / TPOT p50 and p99
  * peak KV pages vs the dense (n_slots, max_seq) cache the seed engine
    allocated for the same workload

`--shared-prefix` adds an A/B run of a chat-template-style workload
(every prompt shares a long common prefix) with the radix-trie prefix
cache off vs on: it checks greedy outputs are byte-identical, that
prefill tokens were actually skipped, and reports the TTFT reduction —
the paper's time-to-first-token axis on edge traffic.

`--family {mamba2,xlstm,zamba}` benches the unified decode-state
runtime on a recurrent or hybrid model instead: a mixed-length workload
under continuous admission (per-lane StateArena slots, no equal-length
lockstep grouping), gated on byte-identical greedy output vs serving
each request alone.  Results land in `serve_bench_<family>.json` so CI
gates every family row independently.

  PYTHONPATH=src python benchmarks/serve_bench.py [--scale 8] [--tokens 16]
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

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import DecoderLM, ModelConfig, init_params  # noqa: E402
from repro.serve import PagedServeEngine, ServeRequest  # noqa: E402
from repro.serve.telemetry import Telemetry  # noqa: E402


def warm_engine(eng, vocab=2048):
    """Compile the engine's prefill/decode graphs on a throwaway
    request, then reset telemetry: each engine jit-compiles its own
    graphs, and that one-off second of compile time would otherwise
    dominate every gated TTFT/wall number at smoke scale.  The prompt
    is a repeated motif so an n-gram drafter proposes and the spec
    verify graph compiles too."""
    motif = np.random.default_rng(99).integers(0, vocab, 4)
    warm = np.tile(motif, 5).astype(np.int32)[:17]
    eng.run([ServeRequest(prompt=warm, max_new_tokens=2, rid=-1)])
    eng.telemetry = Telemetry()
    eng.energy.reset()      # tokens/J covers only the measured window

PROMPT_MIXES = {
    "short": (4, 12),        # uniform prompt-length range
    "mixed": (4, 48),
}


def build_model(scale: int, family: str = "dense"):
    from repro.models.config import SSMConfig, ZambaConfig
    d = 2048 // scale
    if family == "dense":
        cfg = ModelConfig(name="bench", family="dense", n_layers=4,
                          d_model=d, n_heads=32 // scale,
                          n_kv_heads=8 // min(scale, 8) or 1,
                          d_ff=8192 // scale, vocab=2048, head_dim=64,
                          dtype="float32", remat=False)
    elif family == "xlstm":
        cfg = ModelConfig(name="bench-xlstm", family="xlstm", n_layers=4,
                          d_model=d, n_heads=4, n_kv_heads=4,
                          d_ff=4 * d, vocab=2048, head_dim=d // 4,
                          dtype="float32", remat=False,
                          ssm=SSMConfig(mlstm_heads=4, slstm_every=2))
    elif family == "mamba2":
        # pure-mamba shape: zamba config whose shared-attention period
        # exceeds n_layers (zero attention groups -> StateArena only)
        cfg = ModelConfig(name="bench-mamba2", family="zamba", n_layers=4,
                          d_model=d, n_heads=4, n_kv_heads=2,
                          d_ff=4 * d, vocab=2048, head_dim=d // 4,
                          dtype="float32", remat=False,
                          ssm=SSMConfig(d_state=32, head_dim=d // 2,
                                        expand=2),
                          zamba=ZambaConfig(shared_every=8, lora_rank=16,
                                            shared_d_ff=4 * d))
    elif family == "zamba":
        cfg = ModelConfig(name="bench-zamba", family="zamba", n_layers=4,
                          d_model=d, n_heads=4, n_kv_heads=2,
                          d_ff=4 * d, vocab=2048, head_dim=d // 4,
                          dtype="float32", remat=False,
                          ssm=SSMConfig(d_state=32, head_dim=d // 2,
                                        expand=2),
                          zamba=ZambaConfig(shared_every=2, lora_rank=16,
                                            shared_d_ff=4 * d))
    else:
        raise ValueError(family)
    model = DecoderLM(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0),
                         dtype_override=jnp.float32)
    return model, params


def run_one(model, params, *, batch: int, mix: str, n_requests: int,
            tokens: int, max_seq: int, page_size: int):
    lo, hi = PROMPT_MIXES[mix]
    rng = np.random.default_rng(0)
    lens = rng.integers(lo, hi + 1, size=n_requests)
    reqs = [ServeRequest(prompt=rng.integers(0, 2048, int(n)
                                             ).astype(np.int32),
                         max_new_tokens=tokens, rid=i)
            for i, n in enumerate(lens)]
    # pool sized to the workload: peak tokens in flight across `batch`
    # concurrent lanes, not worst-case batch * max_seq
    peak_tokens = sum(sorted(int(n) + tokens for n in lens)[-batch:])
    n_pages = -(-peak_tokens // page_size) + batch
    eng = PagedServeEngine(model, params, max_batch=batch, max_seq=max_seq,
                           page_size=page_size, n_pages=n_pages,
                           prefill_chunk=16)
    warm_engine(eng)
    t0 = time.monotonic()
    eng.run(reqs)
    wall = time.monotonic() - t0
    m = eng.summary()

    row_bytes = eng.cache.kv_bytes() // (n_pages * page_size)
    paged_bytes = eng.cache.kv_bytes()
    dense_bytes = batch * max_seq * row_bytes
    return {
        "batch": batch, "mix": mix, "n_requests": n_requests,
        "wall_s": wall,
        "tokens_per_s_wall": m["tokens"] / wall,
        "tokens_per_s_decode": eng.throughput(),
        "ttft_p50_s": m["ttft_p50_s"], "ttft_p99_s": m["ttft_p99_s"],
        "tpot_p50_s": m["tpot_p50_s"], "tpot_p99_s": m["tpot_p99_s"],
        "queue_p50_s": m["queue_p50_s"],
        "kv_occupancy_peak": m["kv_occupancy_peak"],
        "kv_pages": n_pages,
        "kv_bytes_paged": paged_bytes,
        "kv_bytes_dense_equiv": dense_bytes,
        "kv_savings": 1.0 - paged_bytes / dense_bytes,
    }


def run_shared_prefix(model, params, *, batch: int, n_requests: int,
                      tokens: int, max_seq: int, page_size: int,
                      prefix_len: int):
    """A/B: identical shared-prefix workload with the prefix cache off
    vs on.  Dies loudly if outputs diverge or nothing was skipped —
    these are the PR's correctness bars, not tunables."""
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, 2048, prefix_len).astype(np.int32)
    prompts = [np.concatenate(
        [prefix, rng.integers(0, 2048, int(s)).astype(np.int32)])
        for s in rng.integers(4, 9, size=n_requests)]

    def serve(prefix_cache: bool):
        reqs = [ServeRequest(prompt=p.copy(), max_new_tokens=tokens,
                             rid=i) for i, p in enumerate(prompts)]
        eng = PagedServeEngine(model, params, max_batch=batch,
                               max_seq=max_seq, page_size=page_size,
                               prefill_chunk=16,
                               prefix_cache=prefix_cache)
        warm_engine(eng)        # the warm prompt is disjoint from the
        t0 = time.monotonic()   # shared prefix, so it seeds no match
        eng.run(reqs)
        return reqs, eng.summary(), time.monotonic() - t0

    base_reqs, mb, wall_b = serve(prefix_cache=False)
    shared_reqs, ms, wall_s = serve(prefix_cache=True)

    identical = all(b.out_tokens == s.out_tokens
                    for b, s in zip(base_reqs, shared_reqs))
    assert identical, "prefix sharing changed greedy decode output"
    skipped = ms["prefill_tokens_skipped"]
    assert skipped > 0, "shared-prefix workload skipped no prefill"

    return {
        "mode": "shared-prefix", "batch": batch,
        "n_requests": n_requests, "prefix_len": prefix_len,
        "outputs_byte_identical": identical,
        "prefill_tokens_skipped": skipped,
        "prefix_hit_rate": ms["prefix_hit_rate"],
        "kv_pages_shared": ms["kv_pages_shared"],
        "cow_copies": ms["cow_copies"],
        "prefill_tokens_unshared": mb["prefill_tokens"],
        "prefill_tokens_shared": ms["prefill_tokens"],
        "ttft_mean_s_unshared": mb["ttft_mean_s"],
        "ttft_mean_s_shared": ms["ttft_mean_s"],
        "ttft_speedup": mb["ttft_mean_s"] / ms["ttft_mean_s"],
        "wall_s_unshared": wall_b, "wall_s_shared": wall_s,
    }


def run_family(model, params, *, family: str, batch: int, n_requests: int,
               tokens: int, max_seq: int, page_size: int):
    """Unified decode-state workload: mixed-length prompts under
    continuous admission, gated on byte-identical greedy output vs
    serving every request alone (same engine shape).  The identity gate
    is the PR's correctness bar — continuous batching of recurrent
    state must be invisible in the emitted tokens."""
    rng = np.random.default_rng(0)
    lens = rng.integers(4, 33, size=n_requests)

    def engine():
        return PagedServeEngine(model, params, max_batch=batch,
                                max_seq=max_seq, page_size=page_size,
                                prefill_chunk=16)

    reqs = [ServeRequest(prompt=rng.integers(0, 2048, int(n)
                                             ).astype(np.int32),
                         max_new_tokens=tokens, rid=i)
            for i, n in enumerate(lens)]
    prompts = [r.prompt.copy() for r in reqs]
    eng = engine()
    warm_engine(eng)
    t0 = time.monotonic()
    eng.run(reqs)
    wall = time.monotonic() - t0
    m = eng.summary()

    # reference: one engine, one request at a time (identical graph
    # shapes; reused so the jitted step compiles once)
    ref_eng = engine()
    warm_engine(ref_eng)
    identical = True
    for req, prompt in zip(reqs, prompts):
        solo = ServeRequest(prompt=prompt, max_new_tokens=tokens, rid=0)
        ref_eng.run([solo])
        identical &= req.out_tokens == solo.out_tokens
    assert identical, (f"{family}: continuous batching changed greedy "
                       "output vs single-request serving")

    return {
        "mode": "family", "family": family, "batch": batch,
        "n_requests": n_requests,
        "outputs_byte_identical": identical,
        "wall_s": wall,
        "tokens_per_s_wall": m["tokens"] / wall,
        "tokens_per_s_decode": eng.throughput(),
        "ttft_p50_s": m["ttft_p50_s"], "ttft_p99_s": m["ttft_p99_s"],
        "tpot_p50_s": m["tpot_p50_s"], "tpot_p99_s": m["tpot_p99_s"],
        "state_slot_occupancy_peak": m["state_slot_occupancy_peak"],
        "state_bytes": m["state_bytes"],
        "lane_steps": m[f"lane_steps_{model.cfg.family}"],
        "kv_bytes_paged": eng.cache.kv_bytes(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--batches", type=int, nargs="+", default=[2, 4])
    ap.add_argument("--shared-prefix", action="store_true",
                    help="add the prefix-cache A/B workload")
    ap.add_argument("--prefix-len", type=int, default=64,
                    help="common prefix tokens for --shared-prefix")
    ap.add_argument("--family", default="dense",
                    choices=["dense", "mamba2", "xlstm", "zamba"],
                    help="bench the unified decode-state runtime on a "
                         "recurrent/hybrid family (writes "
                         "serve_bench_<family>.json)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.family != "dense":
        model, params = build_model(args.scale, args.family)
        print(f"model[{args.family}]: {model.n_params()/1e6:.1f}M params, "
              f"backend={jax.default_backend()}")
        rows = []
        for batch in args.batches:
            r = run_family(model, params, family=args.family, batch=batch,
                           n_requests=args.requests, tokens=args.tokens,
                           max_seq=args.max_seq, page_size=args.page_size)
            rows.append(r)
            print(f"{args.family},batch={batch}: "
                  f"{r['tokens_per_s_decode']:.1f} tok/s decode, "
                  f"ttft_p50 {r['ttft_p50_s']*1e3:.0f} ms, "
                  f"tpot_p50 {r['tpot_p50_s']*1e3:.1f} ms, "
                  f"state slots peak "
                  f"{r['state_slot_occupancy_peak']*100:.0f}%, "
                  f"outputs byte-identical")
        save_json(f"serve_bench_{args.family}", rows)
        return

    model, params = build_model(args.scale)
    print(f"model: {model.n_params()/1e6:.1f}M params, "
          f"backend={jax.default_backend()}")
    print("batch,mix,tok/s(decode),tok/s(wall),ttft_p50_ms,ttft_p99_ms,"
          "tpot_p50_ms,tpot_p99_ms,kv_peak_occ,kv_savings_vs_dense")
    rows = []
    for batch in args.batches:
        for mix in PROMPT_MIXES:
            r = run_one(model, params, batch=batch, mix=mix,
                        n_requests=args.requests, tokens=args.tokens,
                        max_seq=args.max_seq, page_size=args.page_size)
            rows.append(r)
            print(f"{r['batch']},{r['mix']},"
                  f"{r['tokens_per_s_decode']:.1f},"
                  f"{r['tokens_per_s_wall']:.1f},"
                  f"{r['ttft_p50_s']*1e3:.0f},{r['ttft_p99_s']*1e3:.0f},"
                  f"{r['tpot_p50_s']*1e3:.1f},{r['tpot_p99_s']*1e3:.1f},"
                  f"{r['kv_occupancy_peak']:.2f},"
                  f"{r['kv_savings']*100:.0f}%")
    if args.shared_prefix:
        r = run_shared_prefix(model, params, batch=max(args.batches),
                              n_requests=args.requests,
                              tokens=args.tokens, max_seq=args.max_seq,
                              page_size=args.page_size,
                              prefix_len=args.prefix_len)
        rows.append(r)
        print(f"shared-prefix: {int(r['prefill_tokens_skipped'])} prefill "
              f"tokens skipped (hit rate "
              f"{r['prefix_hit_rate']*100:.0f}%), ttft mean "
              f"{r['ttft_mean_s_unshared']*1e3:.0f} -> "
              f"{r['ttft_mean_s_shared']*1e3:.0f} ms "
              f"({r['ttft_speedup']:.2f}x), outputs byte-identical")
    save_json("serve_bench", rows)


if __name__ == "__main__":
    main()
