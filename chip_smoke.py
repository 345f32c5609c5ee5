#!/usr/bin/env python3
"""Chip smoke: the serving path end to end on a TPU, at published width.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # a four-chip host

One chip: serves qwen2.5-3b (36 layers, d_model 2048, vocab 151936, random
weights from --seed) twice, in fp (bf16 weights and KV) and in int4 (the
paper's operating point; KV resolves to int8).  Each run goes through the
launcher's `load_model`/`build_engines`, an in-process `Gateway` over
HTTP, `FleetRouter` and `PagedServeEngine` down to the Pallas kernels.
It checks that every request streams its full token count, that the
gateway's tokens equal an offline `PagedServeEngine.run` on the same
prompts, and that the compiled decode step holds the Pallas kernels.
Before serving it compares the served kernels with their `kernels/ref.py`
oracles at the model's shapes.

Four chips (--chips 4): only the multi-chip path — two tp=2 replicas of the
int4 model behind `FleetRouter`, replica i on devices [2i, 2i+2), against
one tp=1 engine on the same prompts: first-step logits within a stated
tolerance, greedy agreement reported.

Progress lines go to stdout; the last line is the JSON result.  With no
TPU, or when any check fails, the script exits non-zero and prints no
result.  Everything runs in this one process, which holds the chip(s).
"""
import argparse
import asyncio
import json
import os
import sys
import time

ARCH = "qwen2.5-3b"
MAX_BATCH = 8
MAX_SEQ = 640            # 512-token prompts + 32 new tokens, in 16-token pages
PAGE_SIZE = 16
NEW_TOKENS = 32
N_REQUESTS = 8
PROMPT_LENS = (128, 512)

# kernel-vs-oracle tolerances: max |kernel - ref| / max |ref|.  The
# kernels accumulate in f32 and write bf16, so the bound is a few bf16
# ulps; the oracles run in f32 at the highest matmul precision.
TOL_ATTENTION = 2e-2
TOL_SWIGLU = 2e-2
# tp=2 vs tp=1 first-step logits: rms(diff) / rms(ref).  Weights are the
# same packed tensors; only the all-reduce's bf16 summation order differs.
TOL_TP_LOGITS = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ----------------------------------------------------------------------------
# kernels at the model's shapes vs their oracles
# ----------------------------------------------------------------------------
def kernel_checks(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_flash_decode import paged_flash_decode
    from repro.kernels.ref import ref_paged_decode, ref_swiglu_qgemv
    from repro.kernels.swiglu_gemv import swiglu_qgemv
    from repro.quant.qarray import quantize

    rng = np.random.default_rng(seed)
    b, g, hd = MAX_BATCH, cfg.n_kv_heads, cfg.hd()
    qpk = cfg.n_heads // g
    mp = MAX_SEQ // PAGE_SIZE
    n_pages = b * mp
    q = jnp.asarray(rng.normal(size=(b, g, qpk, hd)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(n_pages).reshape(b, mp), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, MAX_SEQ + 1, b), jnp.int32)
    shape = (n_pages, g, PAGE_SIZE, hd)

    def rel_err(out, ref):
        out = np.asarray(out, np.float32)
        ref = np.asarray(ref, np.float32)
        return float(np.abs(out - ref).max() / np.abs(ref).max())

    kp = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    out = paged_flash_decode(q, kp, vp, tables, lengths)
    with jax.default_matmul_precision("highest"):
        ref = ref_paged_decode(q.astype(jnp.float32), kp.astype(jnp.float32),
                               vp.astype(jnp.float32), tables, lengths)
    err = rel_err(out, ref)
    log(f"[kernel] paged_flash_decode bf16 KV {shape}: max rel err "
        f"{err:.3e} (tol {TOL_ATTENTION})")
    check(err <= TOL_ATTENTION, "paged_flash_decode bf16 KV vs ref")

    def int8_pool():
        vals = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        # per-(token, kv-head) scales: f16-rounded values stored as f32
        scales = jnp.asarray(rng.uniform(0.5, 1.5, shape[:3]) / 127,
                             jnp.float16).astype(jnp.float32)
        return vals, scales

    (ki, ks), (vi, vs) = int8_pool(), int8_pool()
    out = paged_flash_decode(q, ki, vi, tables, lengths, k_scales=ks,
                             v_scales=vs)
    with jax.default_matmul_precision("highest"):
        ref = ref_paged_decode(q.astype(jnp.float32), ki, vi, tables,
                               lengths, k_scales=ks, v_scales=vs)
    err = rel_err(out, ref)
    log(f"[kernel] paged_flash_decode int8 KV {shape}: max rel err "
        f"{err:.3e} (tol {TOL_ATTENTION})")
    check(err <= TOL_ATTENTION, "paged_flash_decode int8 KV vs ref")

    d, f = cfg.d_model, cfg.d_ff
    key = jax.random.PRNGKey(seed)
    kg, ku, kx = jax.random.split(key, 3)
    wg = quantize(jax.random.normal(kg, (d, f)) / d ** 0.5, 4, 128)
    wu = quantize(jax.random.normal(ku, (d, f)) / d ** 0.5, 4, 128)
    x = jax.random.normal(kx, (b, d)).astype(jnp.bfloat16)
    out = swiglu_qgemv(x, wg.data, wg.scales, wu.data, wu.scales, bits=4,
                       group=128)
    with jax.default_matmul_precision("highest"):
        ref = ref_swiglu_qgemv(x.astype(jnp.float32), wg, wu)
    err = rel_err(out, ref)
    log(f"[kernel] swiglu_qgemv int4 ({b}, {d}) x ({d}, {f}): max rel err "
        f"{err:.3e} (tol {TOL_SWIGLU})")
    check(err <= TOL_SWIGLU, "swiglu_qgemv int4 vs ref")


# ----------------------------------------------------------------------------
# serving
# ----------------------------------------------------------------------------
def make_prompts(vocab: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lens]


def offline_tokens(eng, prompts):
    from repro.serve import ServeRequest
    reqs = [ServeRequest(prompt=p.copy(), max_new_tokens=NEW_TOKENS, rid=i)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    return [list(r.out_tokens) for r in reqs]


async def _post_stream(host: str, port: int, prompt) -> tuple:
    from repro.api import iter_sse
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_tokens": NEW_TOKENS, "stream": True}).encode()
    reader, writer = await asyncio.open_connection(host, port)
    writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: smoke\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    status = int(raw.split(b"\r\n", 1)[0].split()[1])
    tokens, finish = [], None
    for event in iter_sse(raw.partition(b"\r\n\r\n")[2]):
        if "token" in event:
            tokens.append(event["token"])
        elif "finish_reason" in event:
            finish = event["finish_reason"]
    return status, tokens, finish, raw.rstrip().endswith(b"[DONE]")


def gateway_tokens(engines, prompts):
    """Serve `prompts` concurrently over HTTP through a Gateway over a
    FleetRouter of `engines`; returns each request's streamed tokens."""
    from repro.api import Gateway
    from repro.fleet import FleetRouter

    async def run():
        router = FleetRouter(engines)
        gw = Gateway(router)
        host, port = await gw.start(port=0)
        try:
            results = await asyncio.gather(
                *[_post_stream(host, port, p) for p in prompts])
            alive = router.alive
        finally:
            await gw.stop()
        return results, alive

    results, alive = asyncio.run(run())
    check(alive, "a replica's engine driver died while serving")
    out = []
    for i, (status, tokens, finish, done) in enumerate(results):
        check(status == 200, f"request {i}: HTTP {status}")
        check(done and finish == "length" and len(tokens) == NEW_TOKENS,
              f"request {i}: {len(tokens)}/{NEW_TOKENS} tokens, finish "
              f"{finish!r}, [DONE] {done}")
        out.append(tokens)
    return out


def step_args(eng, s: int):
    """Arguments of the engine's jitted step for a (max_batch, s) call on
    the engine's own weights and scratch copies of its KV pools (the step
    donates its state)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    b, mp = eng.max_batch, eng.cache.max_pages
    state = jax.tree.map(lambda x: x * 0, eng.cache.pools)
    tables = (np.arange(b * mp, dtype=np.int32).reshape(b, mp)
              % eng.cache.allocator.n_pages)
    return (eng.params, state, {"tokens": jnp.zeros((b, s), jnp.int32)},
            jnp.asarray(tables), jnp.zeros(b, jnp.int32),
            jnp.full(b, s, jnp.int32))


def decode_hlo(eng) -> str:
    # the engine's own jitted decode program (a private handle: the
    # smoke inspects the graph the engine serves with, not a copy of it)
    return eng._decode_fn.lower(eng.model,
                                *step_args(eng, 1)).compile().as_text()


def custom_calls(hlo: str, name: str) -> int:
    return sum(1 for line in hlo.splitlines()
               if "tpu_custom_call" in line and name in line)


class CompileLog:
    """Backend compile seconds per jitted function, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((kw.get("fun_name", "?"), duration))

    def report(self, since: int, min_s: float = 0.5) -> None:
        for name, secs in self.events[since:]:
            if secs >= min_s:
                log(f"[compile] {name}: {secs:.2f} s")


def peak_bytes() -> str:
    import jax
    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out.append(f"{d.id}:{stats.get('peak_bytes_in_use', 0)}")
    return " ".join(out)


def serve_one_chip(precision: str, seed: int, clog: CompileLog) -> int:
    import gc
    from repro.launch.serve import build_engines, load_model
    from repro.quant.qarray import dequant_counters, reset_dequant_counters
    from repro.serve import ServeConfig

    t0 = time.monotonic()
    model, params = load_model(ARCH, smoke=False, seed=seed)
    cfg = ServeConfig(precision=precision, max_batch=MAX_BATCH,
                      max_seq=MAX_SEQ, page_size=PAGE_SIZE)
    reset_dequant_counters()
    offline = build_engines(model, params, cfg)[0]
    params = offline.params              # packed weights when quantized
    gateway = build_engines(model, params, cfg)[0]
    log(f"[{precision}] {ARCH} built in {time.monotonic() - t0:.1f} s "
        f"(kv {offline.config.as_dict()['kv_dtype_resolved']}, "
        f"{model.cfg.n_layers} layers, d_model {model.cfg.d_model})")

    prompts = make_prompts(model.cfg.vocab, seed)
    mark = len(clog.events)
    want = offline_tokens(offline, prompts)
    clog.report(mark)
    got = gateway_tokens([gateway], prompts)
    check(got == want, f"[{precision}] gateway tokens differ from offline")
    served = sum(len(t) for t in got)
    log(f"[{precision}] {len(prompts)} requests (prompts "
        f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens): "
        f"{served} tokens served, gateway == offline")

    hlo = decode_hlo(gateway)
    n_attn = custom_calls(hlo, "paged_flash_attention")
    n_ffn = custom_calls(hlo, "swiglu_qgemv")
    log(f"[{precision}] decode step HLO: {n_attn} paged_flash_attention, "
        f"{n_ffn} swiglu_qgemv custom calls")
    check(n_attn > 0, f"[{precision}] decode step lacks the paged kernel")
    dq = dequant_counters()
    log(f"[{precision}] weight_fused_dequants "
        f"{gateway.summary()['weight_fused_dequants']:.0f}, "
        f"weight_full_dequants {dq['full_dequant']}")
    if precision == "int4":
        check(n_ffn > 0, "int4 decode step lacks swiglu_qgemv")
        check(gateway.summary()["weight_fused_dequants"] > 0,
              "int4 run counted no fused dequant")
        check(dq["full_dequant"] == 0, "int4 run dequantized a whole weight")
    log(f"[{precision}] peak_bytes_in_use {peak_bytes()}")
    del offline, gateway, params, model
    gc.collect()
    return served


# ----------------------------------------------------------------------------
# four chips: 2 replicas x tp=2 vs one tp=1 engine
# ----------------------------------------------------------------------------
def first_step_logits(eng, prompts):
    """Last-position logits of one prefill chunk per lane (lane i holds
    the first chunk of prompt i), through the engine's jitted step."""
    import jax.numpy as jnp
    import numpy as np
    s = eng.scheduler.prefill_chunk
    args = list(step_args(eng, s))
    args[2] = {"tokens": jnp.asarray(np.stack([p[:s] for p in prompts]))}
    logits, _ = eng._step_fn(*args)
    return np.asarray(logits[:, -1, :], np.float32)


def four_chips(seed: int) -> None:
    import dataclasses
    import gc

    import jax
    import numpy as np
    from repro.launch.serve import build_engines, load_model
    from repro.serve import ServeConfig

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, found "
          f"{len(jax.devices())}")
    model, params = load_model(ARCH, smoke=False, seed=seed)
    cfg1 = ServeConfig(precision="int4", max_batch=MAX_BATCH,
                       max_seq=MAX_SEQ, page_size=PAGE_SIZE)
    ref = build_engines(model, params, cfg1)[0]
    params = ref.params
    gc.collect()
    cfg2 = dataclasses.replace(cfg1, tp=2, replicas=2)
    reps = build_engines(model, params, cfg2)
    devsets = [frozenset(d.id for leaf in jax.tree_util.tree_leaves(e.params)
                         for d in leaf.sharding.device_set) for e in reps]
    log(f"[tp] replica devices {[sorted(s) for s in devsets]}")
    check(all(len(s) == 2 for s in devsets) and not devsets[0] & devsets[1],
          "tp=2 replicas do not own disjoint device pairs")

    prompts = make_prompts(model.cfg.vocab, seed)
    base = first_step_logits(ref, prompts)
    for i, rep in enumerate(reps):
        diff = first_step_logits(rep, prompts) - base
        rel = float(np.sqrt(np.mean(diff ** 2) / np.mean(base ** 2)))
        log(f"[tp] replica {i} first-step logits: rms rel diff {rel:.3e}, "
            f"max abs diff {np.abs(diff).max():.3e} (tol {TOL_TP_LOGITS})")
        check(rel <= TOL_TP_LOGITS, f"replica {i} logits vs tp=1")

    want = offline_tokens(ref, prompts)
    got = gateway_tokens(reps, prompts)
    same = sum(a == b for w, g in zip(want, got) for a, b in zip(w, g))
    ident = sum(w == g for w, g in zip(want, got))
    log(f"[tp] greedy agreement with tp=1: {same}/{len(prompts) * NEW_TOKENS}"
        f" tokens, {ident}/{len(prompts)} requests identical")
    log(f"[tp] peak_bytes_in_use {peak_bytes()}")


# ----------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"compile cache {enable_compile_cache()}")
    clog = CompileLog()
    try:
        if args.chips == 4:
            four_chips(args.seed)
        else:
            kernel_checks(get_config(ARCH), args.seed)
            served = sum(serve_one_chip(p, args.seed, clog)
                         for p in ("fp", "int4"))
            log(f"[serve] {served} tokens served in all")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
