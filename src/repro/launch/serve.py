"""Serving launcher (CLI driver for the e2e serve story).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke \
      --quant int4 --requests 8 --tokens 32

Every token-input family runs on the unified continuous-batching
engine: attention layers on paged KV, recurrent layers (xlstm/zamba) on
per-lane StateArena slots.  Prefix caching and speculative decoding are
attention-only capabilities — `--spec` on a recurrent-state family is a
hard error, and `--no-prefix-cache` is auto-implied for hybrid/
recurrent families (see `check_capabilities`).

`--smoke` serves the arch's small config in float32; without it the
arch runs at its published widths in its own dtype (bf16), with random
weights from a seed.  On a CPU backend (the tests) the Pallas kernels
run in interpret mode and the serve path takes their jnp references;
on a TPU the same code runs the compiled kernels.  `chip_smoke.py` at
the repo root drives this launcher's `load_model`/`build_engines` on
the chip: `python chip_smoke.py` serves qwen2.5-3b at full width on one
chip, and `python chip_smoke.py --chips 4` checks two tp=2 replicas
against one tp=1 engine on a four-chip host.
"""
import argparse

import numpy as np


def load_model(arch: str, smoke: bool, seed: int = 0):
    """(model, params) for `arch` with random weights from `seed`: the
    smoke config in float32, or the full config at its published widths
    in its own dtype."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, get_smoke_config
    from repro.models import DecoderLM, init_params
    if smoke:
        cfg = get_smoke_config(arch).replace(dtype="float32", remat=False)
    else:
        cfg = get_config(arch).replace(remat=False)
    if not cfg.embed_inputs:
        raise ValueError(f"{arch} takes frontend-stub embeddings; the "
                         "token engine serves token-input archs")
    model = DecoderLM(cfg)
    # one program: eagerly, each leaf's f32 draw and its scaled copy sit
    # beside the weights already built — about twice the model's bytes
    # at peak, which a 3B model in bf16 cannot afford on a 16 GB chip
    init = jax.jit(lambda key: init_params(
        model.param_specs(), key,
        dtype_override=jnp.float32 if smoke else None))
    return model, init(jax.random.PRNGKey(seed))


def build_engines(model, params, serve_cfg, spec=None, n=None):
    """`n` (default `serve_cfg.replicas`) engines serving `params`.

    The first engine quantizes float params when the config asks, and
    later replicas adopt its packed tensors.  Replica i runs on devices
    [i*tp, (i+1)*tp) of `jax.devices()` when there are enough for every
    replica; otherwise all of them share the first tp devices."""
    import jax
    from repro.serve import PagedServeEngine
    n = serve_cfg.replicas if n is None else n
    tp = serve_cfg.tp
    devs = jax.devices()
    own = len(devs) >= n * tp
    engines = []
    for i in range(n):
        eng = PagedServeEngine(
            model, params, serve_cfg, spec=spec,
            devices=devs[i * tp:(i + 1) * tp] if own else devs[:tp])
        params = eng.params          # share (possibly packed) weights
        engines.append(eng)
    return engines


def check_capabilities(model, spec_mode: str, no_prefix_cache: bool):
    """Validate CLI capability flags against the model's decode-state
    layout; returns the `prefix_cache` flag for `PagedServeEngine`.

    Prefix sharing and speculative decoding operate on attention KV
    pages only.  A model with recurrent state layers cannot rewind or
    adopt that state, so `--spec` raises a ValueError naming the
    capability, and the prefix cache is auto-disabled (`--no-prefix-
    cache` implied) rather than erroring — there is no affirmative
    prefix flag to contradict.
    """
    from repro.serve.engine import capability_error
    if model.supports_paged():
        return not no_prefix_cache
    if spec_mode != "off":
        raise ValueError(f"--spec {spec_mode}: "
                         + capability_error(model, "speculative-decoding"))
    if not no_prefix_cache:
        print(f"[serve] family {model.cfg.family!r} has recurrent state "
              "layers: --no-prefix-cache implied (prefix sharing is an "
              "attention-only capability)")
    return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--precision", default=None,
                    choices=["fp", "int8", "int4"],
                    help="serving precision (ServeConfig.precision): "
                         "int4 is the paper's CIM operating point "
                         "(default); fp serves float weights + bf16 KV")
    ap.add_argument("--quant", default=None,
                    choices=["bf16", "int8", "int4"],
                    help="DEPRECATED alias for --precision "
                         "(bf16 maps to fp)")
    ap.add_argument("--kv-dtype", default="auto",
                    choices=["auto", "bf16", "f32", "int8"],
                    help="paged KV pool storage; auto follows precision "
                         "(int8 pools when quantized)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4,
                    help="max concurrent decode lanes")
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=0,
                    help="KV pool pages (0 = dense-equivalent worst case)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--spec", default="off",
                    choices=["off", "ngram", "model"],
                    help="speculative decoding drafter (model: a 1-layer "
                         "half-width smoke draft of the same arch)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft window (tokens per verify step)")
    ap.add_argument("--spec-autok", action="store_true",
                    help="autotune the per-step draft length 1..k from "
                         "an EMA of the measured acceptance rate")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable radix-trie prefix sharing of prompt "
                         "KV pages (enabled by default)")
    ap.add_argument("--gateway", action="store_true",
                    help="serve HTTP instead of the offline request "
                         "sweep: SSE streaming POST /v1/completions + "
                         "GET /metrics until Ctrl-C")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8151)
    ap.add_argument("--max-pending", type=int, default=32,
                    help="gateway backpressure: samples in flight PER "
                         "REPLICA before new requests shed fleet-wide "
                         "with 429 + Retry-After")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind the "
                         "gateway (same model; --gateway mode only)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel devices per engine (shards "
                         "heads/FFN/vocab over a ('model',) mesh; "
                         "composes with --replicas as replicas x tp; "
                         "on CPU force a host mesh with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--policy", default="least-loaded",
                    choices=["rr", "least-loaded", "prefix"],
                    help="fleet dispatch policy: rr cycles replicas, "
                         "least-loaded follows pending depth + KV "
                         "occupancy, prefix routes repeated prompts to "
                         "the replica holding their committed KV pages")
    ap.add_argument("--trace", action="store_true",
                    help="record request/engine spans in the in-memory "
                         "tracer; dump a Perfetto-loadable Chrome trace "
                         "from GET /debug/trace (equivalent to "
                         "REPRO_TRACE=1)")
    ap.add_argument("--slo", default=None, nargs="*", metavar="SPEC",
                    help="enable the SLO engine (--gateway mode): pass "
                         "spec strings like 'ttft_p95_s < 0.5' "
                         "'error_rate < 0.01', or no specs for the "
                         "defaults; burn-rate alerts + per-replica "
                         "drift audit served at GET /debug/slo")
    ap.add_argument("--slo-timescale", type=float, default=1.0,
                    help="compress the SRE burn-rate windows by this "
                         "factor (1/600 maps the 1h page window to "
                         "6 s — bench/smoke timescales)")
    ap.add_argument("--access-log", default=None, metavar="PATH",
                    help="append one structured JSON line per gateway "
                         "request (rid, replica, policy, status, ttft, "
                         "tokens) to PATH ('-' for stderr)")
    args = ap.parse_args()

    if args.trace:
        from repro.obs import get_tracer
        get_tracer().enable()

    import warnings

    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import DecoderLM, init_params
    from repro.quant import quantized_fraction
    from repro.serve import SamplingParams, ServeConfig, ServeRequest

    enable_compile_cache()
    # --quant predates ServeConfig; keep it working as an alias
    precision = args.precision
    if args.quant is not None:
        if precision is not None:
            raise SystemExit("pass --precision or --quant, not both")
        warnings.warn("--quant is deprecated; use --precision "
                      "(bf16 -> fp)", DeprecationWarning)
        precision = {"bf16": "fp", "int8": "int8",
                     "int4": "int4"}[args.quant]
    if precision is None:
        precision = "int4"          # the paper's operating point

    try:
        model, params = load_model(args.arch, args.smoke)
    except ValueError as e:
        raise SystemExit(str(e))
    cfg = model.cfg

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, int(n)).astype(np.int32)
               for n in rng.integers(4, 17, size=args.requests)]

    if args.max_seq % args.page_size:
        raise SystemExit(f"--max-seq {args.max_seq} must be a multiple of "
                         f"--page-size {args.page_size}")
    prefix_cache = check_capabilities(model, args.spec, args.no_prefix_cache)
    spec_cfg = None
    if args.spec != "off":
        from repro.spec import SpecConfig
        if args.spec == "model":
            dcfg = cfg.replace(name=cfg.name + "-draft", n_layers=1,
                               d_model=max(cfg.d_model // 2, 32),
                               d_ff=max(cfg.d_ff // 2, 64))
            draft = DecoderLM(dcfg)
            dparams = init_params(draft.param_specs(),
                                  jax.random.PRNGKey(7),
                                  dtype_override=jnp.float32
                                  if args.smoke else None)
            spec_cfg = SpecConfig(k=args.spec_k, drafter="model",
                                  draft_model=draft,
                                  draft_params=dparams,
                                  draft_page_size=args.page_size,
                                  autok=args.spec_autok)
        else:
            spec_cfg = SpecConfig(k=args.spec_k, drafter="ngram",
                                  autok=args.spec_autok)
    if args.replicas < 1:
        raise SystemExit(f"--replicas {args.replicas}: need at least 1")
    if args.replicas > 1 and not args.gateway:
        raise SystemExit("--replicas > 1 requires --gateway (the offline "
                         "sweep runs one engine)")
    if args.slo is not None and not args.gateway:
        raise SystemExit("--slo requires --gateway (burn-rate alerting "
                         "evaluates the live serving loop)")

    if args.tp < 1:
        raise SystemExit(f"--tp {args.tp}: need at least 1")

    serve_cfg = ServeConfig(
        precision=precision, kv_dtype=args.kv_dtype,
        quant_group=16 if args.smoke else 128,
        max_batch=args.batch, max_seq=args.max_seq,
        page_size=args.page_size, n_pages=args.pages or None,
        prefix_cache=prefix_cache, replicas=args.replicas,
        policy=args.policy, max_pending=args.max_pending,
        tp=args.tp)

    # in gateway mode every replica is built; the offline sweep runs one
    engines = build_engines(model, params, serve_cfg, spec=spec_cfg,
                            n=args.replicas if args.gateway else 1)
    eng = engines[0]
    params = eng.params
    if serve_cfg.quantized():
        # report from the ENGINE's config: it pins auto-resolutions the
        # request couldn't know about (e.g. MLA degrades auto-int8 KV
        # back to bf16)
        print(f"[serve] {quantized_fraction(params)*100:.0f}% of param "
              f"bytes quantized ({precision}, kv "
              f"{eng.config.as_dict()['kv_dtype_resolved']})")
    if args.gateway:
        import asyncio
        from repro.api import Gateway
        from repro.fleet import FleetRouter
        router = FleetRouter(engines)
        import sys
        access_log = (sys.stderr if args.access_log == "-"
                      else args.access_log)
        slos = slo_policy = None
        if args.slo is not None:
            from repro.obs.slo import DEFAULT_SLOS, BurnRatePolicy
            slos = list(args.slo) or list(DEFAULT_SLOS)
            slo_policy = BurnRatePolicy(timescale=args.slo_timescale)
            print(f"[serve] SLOs: {', '.join(slos)} "
                  f"(timescale {args.slo_timescale:g}, GET /debug/slo)")
        gw = Gateway(router, access_log=access_log, slos=slos,
                     slo_policy=slo_policy)
        try:
            asyncio.run(gw.serve_forever(args.host, args.port))
        except KeyboardInterrupt:
            print("[api] gateway stopped")
        return
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p)
    reqs = [ServeRequest(prompt=p, max_new_tokens=args.tokens, rid=i,
                         sampling=sampling)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    m = eng.summary()
    spec_msg = ""
    if spec_cfg is not None:
        acc = m["spec_acceptance_rate"]
        acc_txt = (f"{acc*100:.0f}%" if np.isfinite(acc)
                   else "n/a (0 drafted)")
        spec_msg = (f", spec[{args.spec} k={args.spec_k}] "
                    f"acc {acc_txt} "
                    f"{m['tokens_per_decode_step']:.2f} tok/step")
    prefix_msg = ""
    if prefix_cache:
        hr = m["prefix_hit_rate"]
        prefix_msg = (f", prefix hit "
                      f"{hr*100:.0f}%" if np.isfinite(hr) else
                      ", prefix hit n/a")
        prefix_msg += (f" ({int(m['prefill_tokens_skipped'])} prefill "
                       f"tokens skipped)")
    state_msg = ""
    if eng.arena is not None:
        state_msg = (f", state slots peak "
                     f"{m['state_slot_occupancy_peak']*100:.0f}% "
                     f"({int(m['state_bytes'])/1024:.0f} KiB arena)")
    print(f"[serve] {int(m['tokens'])} tokens, "
          f"{eng.throughput():.0f} tok/s decode, "
          f"ttft p50 {m['ttft_p50_s']*1e3:.0f} ms / "
          f"p99 {m['ttft_p99_s']*1e3:.0f} ms, "
          f"tpot p50 {m['tpot_p50_s']*1e3:.1f} ms, "
          f"kv occupancy peak {m['kv_occupancy_peak']*100:.0f}%"
          f"{spec_msg}{prefix_msg}{state_msg} "
          f"({jax.default_backend()} backend"
          f"{f', tp={args.tp}' if args.tp > 1 else ''})")


if __name__ == "__main__":
    main()
