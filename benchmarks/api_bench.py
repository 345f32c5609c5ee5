"""Open-loop load benchmark for the streaming gateway (`repro.api`),
with a data-parallel fleet axis (`repro.fleet`).

Closed-loop benchmarks (serve_bench) measure the engine at its own
pace; real edge traffic does not wait its turn.  This generator fires
requests at the gateway with POISSON arrivals at a configured rate —
open loop: a slow server does NOT slow the arrival process, so queueing
delay shows up in the tail where it belongs (the coordinated-omission
trap closed-loop generators fall into).

Per (replicas, policy, rate) cell it reports the streaming client's
actual experience over real HTTP + SSE: TTFT and inter-token-latency
percentiles (measured from intended arrival, so scheduler queue time
counts), goodput, how many requests were shed as 429s by the fleet's
admission budget, and — for the fleet — the engine-level prefix hit
rate plus the router's affinity hit counters.

Two workloads:
  uniform        pairwise-independent random prompts (the scaling
                 story: goodput vs replica count at fixed offered load)
  shared-prefix  two waves; wave 2 repeats wave 1's prompts after one
                 parity-flip unique prompt, so deterministic rr
                 alternation lands every repeat on the OPPOSITE replica
                 (engine prefix hit rate ~0) while prefix-affinity
                 routes it to the holder of its committed KV pages
                 (hit rate > 0, prefill skipped).  Repeats are asserted
                 token-identical to their originals (greedy).

  PYTHONPATH=src python benchmarks/api_bench.py --scale 32 --tokens 8 \
      --requests 12 --rates 8 32 --replicas 1 2 --policies least-loaded
"""
import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from common import save_json  # noqa: E402
from serve_bench import build_model, warm_engine  # noqa: E402

from repro.api import Gateway  # noqa: E402
from repro.api.protocol import DONE_SENTINEL  # noqa: E402
from repro.fleet import FleetRouter  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.quant.qarray import (dequant_counters,  # noqa: E402
                                reset_dequant_counters)
from repro.serve import (PagedServeEngine, SamplingParams,  # noqa: E402
                         ServeConfig, ServeRequest)

QUANT_GROUP = 32        # bench models are narrow; 128 wouldn't divide


def _serve_config(precision, *, batch, max_seq, page_size, max_pending,
                  policy, replicas, kv_dtype="auto",
                  tp=1) -> ServeConfig:
    return ServeConfig(
        precision=precision or "fp", kv_dtype=kv_dtype,
        quant_group=QUANT_GROUP, max_batch=batch, max_seq=max_seq,
        page_size=page_size, prefill_chunk=16, max_pending=max_pending,
        policy=policy, replicas=replicas, tp=tp)


def _kv_bytes_per_token(engine) -> float:
    """Resident KV bytes per token lane across all layers (pool bytes /
    pool token capacity) — scale pages count against the quantized
    pools, so the capacity claim is honest."""
    import jax
    total = sum(v.nbytes for v in
                jax.tree_util.tree_leaves(engine.cache.pools))
    tokens = engine.cache.allocator.n_pages * engine.cache.page_size
    return total / tokens if tokens else 0.0


def quality_probe(model, params_fp, params_q, base_cfg: ServeConfig,
                  *, tokens: int = 24, seed: int = 7) -> dict:
    """Quantization quality vs the fp stack on a fixed probe prompt:

      quality_logit_mse        MSE of the full-sequence forward logits
      quality_greedy_match_len length of the common greedy prefix
                               (engine serve path, temperature 0)
      quality_greedy_tokens    probe length (match_len's denominator)
    """
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, model.cfg.vocab, 12).astype(np.int32)
    lf = model.forward(params_fp, {"tokens": jnp.asarray(prompt[None])})
    lq = model.forward(params_q, {"tokens": jnp.asarray(prompt[None])})
    mse = float(jnp.mean((lf.astype(jnp.float32)
                          - lq.astype(jnp.float32)) ** 2))

    def greedy(params, cfg):
        eng = PagedServeEngine(model, params, cfg)
        req = ServeRequest(prompt=prompt, max_new_tokens=tokens, rid=0,
                           sampling=SamplingParams(temperature=0.0))
        eng.run([req])
        return req.out_tokens

    fp_cfg = dataclasses.replace(base_cfg, precision="fp",
                                 kv_dtype="auto")
    tf = greedy(params_fp, fp_cfg)
    tq = greedy(params_q, base_cfg)
    match = 0
    for a, b in zip(tf, tq):
        if a != b:
            break
        match += 1
    return {"quality_logit_mse": mse,
            "quality_greedy_match_len": float(match),
            "quality_greedy_tokens": float(len(tf))}


def _pct(vals, q):
    return float(np.percentile(np.asarray(vals, np.float64), q)) \
        if vals else float("nan")


async def _drive_one(host, port, body: dict, t_arrival: float) -> dict:
    """POST one streaming completion; parse SSE incrementally so TTFT
    and inter-token gaps are timed as bytes actually land."""
    out = {"status": 0, "ttft_s": None, "gaps": [], "tokens": 0,
           "done_s": None, "out_tokens": []}
    payload = json.dumps(body).encode()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                      f"Content-Length: {len(payload)}\r\n\r\n"
                      ).encode() + payload)
        await writer.drain()
        status_line = await reader.readline()
        out["status"] = int(status_line.split()[1])
        while (await reader.readline()) not in (b"\r\n", b""):
            pass                                    # drain headers
        if out["status"] != 200:
            await reader.read()
            return out
        t_last = None
        while True:
            line = await reader.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):]
            if data.decode("utf-8", "replace") == DONE_SENTINEL:
                break
            event = json.loads(data)
            now = time.monotonic()
            if "token" in event:
                out["tokens"] += 1
                out["out_tokens"].append(event["token"])
                if out["ttft_s"] is None:
                    out["ttft_s"] = now - t_arrival
                elif t_last is not None:
                    out["gaps"].append(now - t_last)
                t_last = now
        out["done_s"] = time.monotonic() - t_arrival
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, OSError):
            pass
    return out


async def _http_get_json(host, port, path):
    """GET a JSON document from the gateway (used for /debug/trace, so
    the trace artifact exercises the real endpoint, not an in-process
    shortcut)."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\nHost: bench\r\n\r\n"
                     .encode())
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        length = None
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b""):
                break
            key, _, val = line.decode().partition(":")
            if key.strip().lower() == "content-length":
                length = int(val)
        body = (await reader.readexactly(length) if length is not None
                else await reader.read())
        return status, json.loads(body)
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, OSError):
            pass


def _check_trace_correlation(doc: dict) -> None:
    """The point of the tracer is cross-layer correlation: a request id
    minted at the gateway must reappear on the router's dispatch event
    and inside the engine's decode-step spans (which ran on a different
    thread).  Assert it on the real capture."""
    events = doc["traceEvents"]
    gw_rids = {e["args"]["rid"] for e in events
               if e.get("name") == "request" and e.get("ph") == "X"}
    route_rids = {r for e in events if e.get("name") == "route_dispatch"
                  for r in e["args"].get("rids", [])}
    decode_rids = {r for e in events if e.get("name") == "decode_step"
                   for r in e["args"].get("rids", [])}
    assert gw_rids, "trace has no gateway request spans"
    shared = gw_rids & route_rids & decode_rids
    assert shared, (
        "no request id is shared across gateway/router/engine spans: "
        f"gateway={sorted(gw_rids)[:4]} router={sorted(route_rids)[:4]} "
        f"engine={sorted(decode_rids)[:4]}")


async def _fire_wave(host, port, bodies, rate, rng):
    """Open-loop Poisson wave with a coordinated-omission-safe intended
    arrival schedule fixed up front: TTFT is measured from the INTENDED
    arrival, so event-loop lateness in firing a request counts against
    the server's tail instead of silently vanishing."""
    gaps_s = rng.exponential(1.0 / rate, size=len(bodies))
    arrivals = time.monotonic() + np.cumsum(gaps_s)
    tasks = []
    for body, t_arrival in zip(bodies, arrivals):
        await asyncio.sleep(max(0.0, t_arrival - time.monotonic()))
        tasks.append(asyncio.ensure_future(
            _drive_one(host, port, body, float(t_arrival))))
    return await asyncio.gather(*tasks)


def _distinct_prompts(rng, count, length, vocab):
    seen, out = set(), []
    while len(out) < count:
        p = [int(t) for t in rng.integers(0, vocab, length)]
        if tuple(p) not in seen:        # pairwise distinct: no
            seen.add(tuple(p))          # accidental cross-prompt hits
            out.append(p)
    return out


async def run_rate(model, params, *, rate: float, n_requests: int,
                   tokens: int, n: int, batch: int, max_seq: int,
                   page_size: int, max_pending: int, prompt_lo: int,
                   prompt_hi: int, replicas: int = 1,
                   policy: str = "least-loaded",
                   shared_prefix: bool = False, seed: int = 0,
                   trace=None, precision=None, tp=None, slo=None):
    """One (replicas, policy, rate) cell.  `trace` is tri-state: None
    leaves the tracer alone and omits the `tracing` identity field
    (plain sweeps stay comparable to their committed baselines);
    True/False force the tracer on/off and label the row, so an A/B
    pair from the SAME run feeds check_bench's tracing-overhead gate.
    `precision` is tri-state the same way: None keeps the pre-quant
    row identity; "fp"/"int8"/"int4" labels the row and serves at that
    ServeConfig precision (`params` must already match — packed
    QTensors for the quantized tiers).  `tp` likewise: None keeps the
    pre-TP row identity; an int shards every engine that many ways
    (`ServeConfig.tp`) and attaches a `greedy_digest` of the completed
    token streams so check_bench's tp-identity gate can assert tp>1
    cells byte-match the tp=1 cell from the SAME run.  `slo` tri-state
    too: True serves the cell under the default SLO set with
    bench-compressed burn-rate windows (timescale 1/600) and a fast
    evaluation poll, labels the row `slo=true`, attaches alert/drift
    columns from the REAL `/debug/slo` endpoint, and returns its
    payload for the `<out>.slo.json` artifact (tools/slo_report.py).
    Returns (row, chrome_trace_doc_or_None, slo_doc_or_None)."""
    cfg = _serve_config(precision, batch=batch, max_seq=max_seq,
                        page_size=page_size, max_pending=max_pending,
                        policy=policy, replicas=replicas, tp=tp or 1)
    quantized = precision in ("int8", "int4")
    # trace-time counters: every engine jits its own step graphs, so a
    # full-weight float materialization ANYWHERE in this cell's traced
    # decode/prefill graphs would bump full_dequant
    reset_dequant_counters()
    engines = []
    for _ in range(replicas):
        eng = PagedServeEngine(model, params, cfg)
        warm_engine(eng)    # compile prefill/decode BEFORE the driver
        engines.append(eng)
    kv_bytes_per_token = _kv_bytes_per_token(engines[0])
    tracer = None
    if trace is not None:
        from repro.obs import get_tracer
        tracer = get_tracer()
        tracer.clear()
        tracer.enable() if trace else tracer.disable()
    # max_pending is PER REPLICA: the fleet's admission capacity scales
    # with the fleet, which is the scaling story being measured
    router = FleetRouter(engines, policy=policy, max_pending=max_pending)
    gw_kwargs = {}
    if slo:
        from repro.obs.slo import DEFAULT_SLOS, BurnRatePolicy
        # timescale 1/600 maps the SRE 1h page window to 6 s; the fast
        # poll gives the short windows enough evaluation ticks inside a
        # few-second smoke cell
        gw_kwargs = dict(slos=list(DEFAULT_SLOS),
                         slo_policy=BurnRatePolicy(timescale=1 / 600),
                         slo_poll_s=0.05)
    gw = Gateway(router, **gw_kwargs)
    host, port = await gw.start()
    rng = np.random.default_rng(seed)

    def body(prompt):
        return {"prompt": prompt, "max_tokens": tokens, "n": n,
                "stream": True, "temperature": 0.0}

    pairs_checked = pairs_identical = 0
    t0 = time.monotonic()
    if shared_prefix:
        # wave 1: k distinct prompts (k even keeps rr's parity flip
        # deterministic); wave 2: ONE unique prompt, then wave 1 again —
        # under rr every repeat lands on the opposite replica, under
        # prefix-affinity on the holder of its committed pages
        k = max(2, (n_requests // 2) & ~1)
        length = max(prompt_hi, 2 * page_size + page_size // 2)
        originals = _distinct_prompts(rng, k + 1, length,
                                      model.cfg.vocab)
        wave1, odd = originals[:k], originals[k]
        first = await _fire_wave(host, port, [body(p) for p in wave1],
                                 rate, rng)
        second = await _fire_wave(
            host, port, [body(odd)] + [body(p) for p in wave1], rate,
            rng)
        results = first + second
        for orig, rep in zip(first, second[1:]):
            if orig["status"] == 200 and rep["status"] == 200:
                pairs_checked += 1
                pairs_identical += \
                    orig["out_tokens"] == rep["out_tokens"]
        assert pairs_identical == pairs_checked, \
            "a prefix-adopted repeat diverged from its original stream"
    else:
        bodies = [body([int(t) for t in
                        rng.integers(0, model.cfg.vocab,
                                     int(rng.integers(prompt_lo,
                                                      prompt_hi + 1)))])
                  for _ in range(n_requests)]
        results = await _fire_wave(host, port, bodies, rate, rng)
    wall = time.monotonic() - t0
    metrics = await gw._metrics()
    trace_doc = slo_doc = None
    if trace:
        status, trace_doc = await _http_get_json(host, port,
                                                 "/debug/trace")
        assert status == 200, f"/debug/trace returned {status}"
        _check_trace_correlation(trace_doc)
    if slo:
        # let a couple more evaluation ticks land after the wave so the
        # drift auditor sees the final decode clock deltas
        await asyncio.sleep(0.2)
        status, slo_doc = await _http_get_json(host, port, "/debug/slo")
        assert status == 200, f"/debug/slo returned {status}"
    await gw.stop()
    if tracer is not None:
        tracer.disable()

    ok = [r for r in results if r["status"] == 200 and r["done_s"]]
    ttft = [r["ttft_s"] for r in ok if r["ttft_s"] is not None]
    gaps = [g for r in ok for g in r["gaps"]]
    total_tokens = sum(r["tokens"] for r in ok)
    eng_agg = metrics["engine"] or {}
    fleet = metrics["fleet"]
    dq = dequant_counters()
    if quantized:
        # the residency guarantee: no traced graph in this cell ever
        # materialized a full float weight (ISSUE-8 acceptance)
        assert dq["full_dequant"] == 0, \
            (f"{precision} cell traced {dq['full_dequant']} full-weight "
             "dequantizations — float weights leaked onto the hot path")
        assert dq["fused_dequant"] > 0, \
            "quantized cell traced no fused-dequant contraction"
    row = {
        "mode": "open-loop", "rate": float(rate),
        "workload": "shared-prefix" if shared_prefix else "uniform",
        "replicas": replicas, "policy": policy,
        **({"precision": precision} if precision is not None else {}),
        **({"tracing": bool(trace)} if trace is not None else {}),
        **({"tp": int(tp)} if tp is not None else {}),
        **({"slo": bool(slo)} if slo is not None else {}),
        "n_requests": len(results), "n": n, "batch": batch,
        "completed": len(ok),
        "rejected_429": sum(r["status"] == 429 for r in results),
        "errors": sum(r["status"] not in (200, 429) for r in results),
        "tokens": total_tokens,
        "goodput_tokens_per_s": total_tokens / wall if wall else 0.0,
        "wall_s": wall,
        "ttft_p50_s": _pct(ttft, 50), "ttft_p95_s": _pct(ttft, 95),
        "ttft_p99_s": _pct(ttft, 99),
        "itl_p50_s": _pct(gaps, 50), "itl_p95_s": _pct(gaps, 95),
        "itl_p99_s": _pct(gaps, 99),
        "prefix_hit_rate": float(eng_agg.get("prefix_hit_rate",
                                             float("nan"))),
        "prefill_tokens_skipped": float(
            eng_agg.get("prefill_tokens_skipped", 0.0)),
        "affinity_hits": fleet.get("affinity_hits"),
        "affinity_misses": fleet.get("affinity_misses"),
        "pairs_checked": pairs_checked,
        "pairs_identical": pairs_identical,
        # CIM cost-model energy attribution for the traffic this cell
        # actually served (sim_* = simulated, not measured)
        "sim_energy_j": float(eng_agg.get("sim_energy_j", 0.0)),
        "sim_tokens_per_j": float(eng_agg.get("sim_tokens_per_j", 0.0)),
    }
    if precision is not None:
        row["kv_dtype"] = cfg.as_dict()["kv_dtype_resolved"]
        row["kv_bytes_per_token"] = kv_bytes_per_token
        row["weight_full_dequants"] = float(dq["full_dequant"])
        row["weight_fused_dequants"] = float(dq["fused_dequant"])
    if slo_doc is not None:
        import math
        trans = slo_doc.get("transitions") or []
        drift = slo_doc.get("drift") or {}
        # worst-case replica: the calibrated drift ratio farthest from
        # 1.0 (JSON sanitize maps an uncalibrated NaN to None)
        ratios = [d.get("sim_drift_ratio") for d in drift.values()]
        ratios = [r for r in ratios
                  if isinstance(r, (int, float)) and math.isfinite(r)
                  and r > 0]
        row.update({
            "slo_worst": slo_doc.get("worst", "ok"),
            "slo_page_alerts": float(sum(t.get("to") == "page"
                                         for t in trans)),
            "slo_warn_alerts": float(sum(t.get("to") == "warn"
                                         for t in trans)),
            "sim_drift_ratio": (max(ratios,
                                    key=lambda r: abs(math.log(r)))
                                if ratios else float("nan")),
            "sim_drift_alarms": float(sum(
                d.get("sim_drift_alarms") or 0.0
                for d in drift.values())),
            "sim_drift_ticks": float(sum(
                d.get("sim_drift_ticks") or 0.0
                for d in drift.values())),
        })
    if tp is not None:
        # every cell serves greedily (temperature 0.0) and the arrival
        # schedule/prompts are seed-deterministic, so the completed
        # streams are comparable across tp cells of the same sweep:
        # request index i saw the same prompt in both.  The digest is
        # what check_bench's tp-identity rule byte-compares.
        import hashlib
        streams = [[i, r["out_tokens"]] for i, r in enumerate(results)
                   if r["status"] == 200]
        row["greedy_digest"] = hashlib.sha256(
            json.dumps(streams).encode()).hexdigest()[:16]
        row["sim_tp"] = float(eng_agg.get("sim_tp", 1.0))
    return row, trace_doc, slo_doc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rates", type=float, nargs="+", default=[8.0, 32.0],
                    help="mean Poisson arrival rates (requests/s)")
    ap.add_argument("--n", type=int, default=1,
                    help="parallel samples per request (KV fork)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-pending", type=int, default=64,
                    help="fleet 429 threshold (samples in flight PER "
                         "replica)")
    ap.add_argument("--prompt-lo", type=int, default=4)
    ap.add_argument("--prompt-hi", type=int, default=24)
    ap.add_argument("--replicas", type=int, nargs="+", default=[1],
                    help="fleet sizes to sweep (data-parallel engine "
                         "replicas behind one gateway)")
    ap.add_argument("--policies", nargs="+", default=["least-loaded"],
                    choices=["rr", "least-loaded", "prefix"],
                    help="dispatch policies to sweep")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="two-wave repeated-prompt workload (prefix "
                         "affinity A/B) instead of uniform random")
    ap.add_argument("--precision", nargs="+", default=None,
                    choices=["fp", "int8", "int4"],
                    help="serving precisions to sweep (ServeConfig "
                         "tiers); labels rows with a `precision` "
                         "identity field, attaches quality probes "
                         "(logit MSE, greedy divergence vs fp) and the "
                         "quantized-KV capacity ratio, and asserts the "
                         "quantized cells traced no full-weight "
                         "dequantization")
    ap.add_argument("--tp", type=int, nargs="+", default=None,
                    help="tensor-parallel widths to sweep "
                         "(ServeConfig.tp); labels rows with a `tp` "
                         "identity field plus a greedy stream digest so "
                         "check_bench can assert tp>1 cells "
                         "byte-identical to tp=1 within the run; on CPU "
                         "force a host mesh with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--slo", action="store_true",
                    help="serve every cell under the default SLO set "
                         "with bench-compressed burn-rate windows; "
                         "labels rows with a `slo` identity field plus "
                         "alert/drift columns (gated by check_bench's "
                         "check_slo) and saves the final /debug/slo "
                         "payload as <out>.slo.json for "
                         "tools/slo_report.py")
    ap.add_argument("--trace", action="store_true",
                    help="run every cell twice — tracing off then on — "
                         "label rows with a `tracing` field for "
                         "check_bench's overhead gate, and save the "
                         "traced run's Chrome trace (Perfetto-loadable) "
                         "as an artifact")
    ap.add_argument("--trace-artifact", default=None, metavar="PATH",
                    help="where to write the Chrome trace JSON "
                         "(default results/benchmarks/<out>.trace.json)")
    ap.add_argument("--out", default="api_bench",
                    help="results/benchmarks/<out>.json basename")
    args = ap.parse_args()
    enable_compile_cache()

    import jax
    from repro.quant import quantize_params
    model, params = build_model(args.scale)
    tps = args.tp or [None]
    if args.tp and max(args.tp) > 1:
        # the smoke-scale bench model runs GQA down to ONE kv head,
        # which no mesh can split — give the tp sweep an MHA variant of
        # the same shape instead (both tp cells share it, and the sweep
        # writes its own baseline file, so no other bench moves)
        need = max(args.tp)
        cfg = model.cfg
        if cfg.n_kv_heads % need or cfg.n_heads % need or cfg.d_ff % need:
            import jax.numpy as jnp
            from repro.models import DecoderLM, init_params
            cfg = cfg.replace(name=cfg.name + "-tp",
                              n_kv_heads=cfg.n_heads)
            model = DecoderLM(cfg)
            params = init_params(model.param_specs(),
                                 jax.random.PRNGKey(0),
                                 dtype_override=jnp.float32)
        model.validate_tp(need)     # non-dividing dims fail loudly here
    print(f"model: {model.n_params()/1e6:.1f}M params, "
          f"backend={jax.default_backend()}")

    # one packed copy per quantized tier, shared by every cell of that
    # tier (replicas share them too — engines see QTensor leaves and
    # skip re-quantizing)
    precisions = args.precision or [None]
    params_by_prec = {None: params, "fp": params}
    quality_by_prec, fp32_kv_bpt = {}, None
    for prec in precisions:
        if prec in ("int8", "int4"):
            params_by_prec[prec] = quantize_params(
                params, bits=4 if prec == "int4" else 8,
                group=QUANT_GROUP)
            base = _serve_config(prec, batch=1, max_seq=args.max_seq,
                                 page_size=args.page_size,
                                 max_pending=args.max_pending,
                                 policy="least-loaded", replicas=1)
            quality_by_prec[prec] = quality_probe(
                model, params, params_by_prec[prec], base,
                tokens=args.tokens)
            if fp32_kv_bpt is None:
                # f32-KV reference pool for the capacity ratio: pool
                # construction only (never run, never compiled)
                ref = PagedServeEngine(
                    model, params,
                    dataclasses.replace(base, precision="fp",
                                        kv_dtype="f32"))
                fp32_kv_bpt = _kv_bytes_per_token(ref)
            q = quality_by_prec[prec]
            print(f"quality[{prec}]: logit mse {q['quality_logit_mse']:.3e}"
                  f", greedy match {q['quality_greedy_match_len']:.0f}"
                  f"/{q['quality_greedy_tokens']:.0f}")

    print("precision,tp,replicas,policy,rate_rps,tracing,completed,"
          "shed_429,goodput_tok/s,ttft_p50_ms,ttft_p99_ms,itl_p50_ms,"
          "itl_p99_ms,prefix_hit,sim_tok/J")
    rows, trace_doc, slo_doc = [], None, None
    trace_modes = [False, True] if args.trace else [None]
    for precision in precisions:
      for tp in tps:
        for replicas in args.replicas:
            for policy in args.policies:
                for rate in args.rates:
                    for tracing in trace_modes:
                        r, doc, sdoc = asyncio.run(run_rate(
                            model, params_by_prec[precision], rate=rate,
                            n_requests=args.requests,
                            tokens=args.tokens, n=args.n,
                            batch=args.batch, max_seq=args.max_seq,
                            page_size=args.page_size,
                            max_pending=args.max_pending,
                            prompt_lo=args.prompt_lo,
                            prompt_hi=args.prompt_hi,
                            replicas=replicas, policy=policy,
                            shared_prefix=args.shared_prefix,
                            trace=tracing, precision=precision, tp=tp,
                            slo=True if args.slo else None))
                        if precision in quality_by_prec:
                            r.update(quality_by_prec[precision])
                            r["kv_lanes_ratio_vs_fp32"] = (
                                fp32_kv_bpt / r["kv_bytes_per_token"])
                        rows.append(r)
                        if doc is not None:
                            trace_doc = doc   # keep the last traced cell
                        if sdoc is not None:
                            slo_doc = sdoc    # keep the last SLO cell
                        hit = r["prefix_hit_rate"]
                        print(
                            f"{precision or '-'},{tp or '-'},"
                            f"{replicas},{policy},{r['rate']:g},"
                            f"{'-' if tracing is None else int(tracing)},"
                            f"{r['completed']},{r['rejected_429']},"
                            f"{r['goodput_tokens_per_s']:.1f},"
                            f"{r['ttft_p50_s']*1e3:.0f},"
                            f"{r['ttft_p99_s']*1e3:.0f},"
                            f"{r['itl_p50_s']*1e3:.1f},"
                            f"{r['itl_p99_s']*1e3:.1f},"
                            f"{hit if np.isfinite(hit) else float('nan'):.2f},"
                            f"{r['sim_tokens_per_j']:.1f}")
                        assert r["errors"] == 0, \
                            f"gateway returned errors at rate {rate}"
    save_json(args.out, rows)
    if trace_doc is not None:
        from common import RESULTS_DIR
        path = args.trace_artifact or os.path.join(
            RESULTS_DIR, args.out + ".trace.json")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(trace_doc, f)
        print(f"chrome trace ({len(trace_doc['traceEvents'])} events) "
              f"-> {path}")
    if slo_doc is not None:
        from common import RESULTS_DIR
        path = os.path.join(RESULTS_DIR, args.out + ".slo.json")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(slo_doc, f, indent=1)
        print(f"slo payload ({len(slo_doc.get('states', []))} alert "
              f"states) -> {path}  (report: PYTHONPATH=src python "
              f"tools/slo_report.py {path})")


if __name__ == "__main__":
    main()
