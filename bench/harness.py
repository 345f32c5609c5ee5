"""One run of one cell: build, warm up, serve the window, check.

Set-up makes the configuration's int4 weights on the device from the
seed, builds the engines as the launcher does, and warms up the two
serve graphs the window uses (a `(max_batch, prefill_chunk)` prefill and
a `(max_batch, 1)` decode).  The window drives streaming
`POST /v1/completions` on an in-process `Gateway`; the load comes from
`client.py` in a child process that never imports JAX, so the chip stays
with this process.  Once the window has closed and the peak memory is
read, the engines are freed and the plain reference judges a sample of
the served requests.

Clocks: the client's records, the telemetry snapshots and the program's
spans are on the host's monotonic clock; the profiler's trace counts
nanoseconds from the start of its session.  Once `start_trace` has
returned, a host annotation (ANCHOR) is opened at a monotonic time the
harness reads: its start in the trace ties the two clocks, and the
traced window runs from it, so the profiler's own start-up falls
outside the window.
"""
from __future__ import annotations

import asyncio
import gc
import json
import os
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import cells
import device
import endtoend
import program

HERE = os.path.dirname(os.path.abspath(__file__))
CLIENT = os.path.join(HERE, "client.py")
CLIENT_START_S = 1.0        # the client child's start-up, before load
TRACE_S = 5.0               # longest traced stretch of the window
SNAPSHOT_TIMEOUT_S = 30.0
CLIENT_GRACE_S = 30.0       # past its last deadline before the client is killed
OFF_CHIP_PEAKS = "TPU v5 lite"      # the tests' stand-in for a device kind


def enable_compile_cache() -> str:
    """JAX's persistent cache at `$JAX_COMPILATION_CACHE_DIR`, else at
    `.jax_cache/` in the checkout: a fixed path, so every run after the
    first finds its programs."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        cells.ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCount:
    """Backend compilations, from JAX's own monitoring events: none may
    fall inside the window."""

    def __init__(self):
        import jax
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t < t1)


async def _at(t: float) -> None:
    await asyncio.sleep(max(0.0, t - time.monotonic()))


async def _snapshot(driver, t: float) -> Dict:
    await _at(t)
    return await asyncio.wait_for(asyncio.wrap_future(
        driver.call(program.telemetry_snapshot)), SNAPSHOT_TIMEOUT_S)


def host_cpu() -> Optional[List[int]]:
    """The machine's CPU time counters in clock ticks, from /proc/stat:
    user, nice, system, idle, iowait, irq, softirq, steal."""
    try:
        with open("/proc/stat") as f:
            return [int(x) for x in f.readline().split()[1:9]]
    except (OSError, ValueError):
        return None


def host_load(a: Dict, b: Dict) -> Dict:
    """Shares of all the machine's CPU time between two readings of
    `_cpu_at`: busy, stolen by the hypervisor, waiting on I/O, and this
    process's own (the served path; the load client is not in it)."""
    if a["ticks"] is None or b["ticks"] is None:
        return {}
    d = [y - x for x, y in zip(a["ticks"], b["ticks"])]
    total = max(sum(d), 1)
    own = b["own_s"] - a["own_s"]
    return {"host_busy_pct": 100.0 * (d[0] + d[1] + d[2] + d[5] + d[6])
            / total,
            "host_steal_pct": 100.0 * d[7] / total,
            "host_iowait_pct": 100.0 * d[4] / total,
            "bench_cpu_pct": 100.0 * own * os.sysconf("SC_CLK_TCK") / total}


async def _cpu_at(t: float) -> Dict:
    await _at(t)
    own = os.times()
    return {"ticks": host_cpu(), "own_s": own.user + own.system}


ANCHOR = "bench_trace_anchor"


async def _trace(t_start: float, seconds: float, logdir: str) -> Dict:
    """Profile [m0, m0 + seconds), m0 being read inside the ANCHOR
    annotation just after the profiler has started, with the program's
    tracer on."""
    import jax
    await _at(t_start)
    loop = asyncio.get_running_loop()
    spans = program.tracer()
    spans.clear()
    spans.enable()
    # the Python tracer would slow the host it measures several-fold
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    await loop.run_in_executor(None, lambda: jax.profiler.start_trace(
        logdir, profiler_options=opts))
    with jax.profiler.TraceAnnotation(ANCHOR):
        m0 = time.monotonic()
    await _at(m0 + seconds)
    m1 = time.monotonic()
    await loop.run_in_executor(None, jax.profiler.stop_trace)
    spans.disable()
    return {"m0": m0, "m1": m1, "spans": [
        e for e in spans.events() if e["ph"] == "X" and m0 <= e["t_s"] < m1]}


async def serve(engines, schedule, traffic: Dict, seconds: float,
                trace: bool, tmp: str) -> Dict:
    """Serve the schedule through the gateway; the client's records,
    telemetry snapshots at the window's edges, and the trace."""
    router, gw = program.router_and_gateway(engines)
    host, port = await gw.start(port=0)
    driver = router.replicas[0].driver
    proc = None
    try:
        sched = os.path.join(tmp, "schedule.json")
        out = os.path.join(tmp, "records.json")
        with open(sched, "w") as f:
            json.dump(schedule, f)
        proc = await asyncio.create_subprocess_exec(
            sys.executable, CLIENT, stdin=asyncio.subprocess.PIPE)
        t_load = time.monotonic() + CLIENT_START_S
        t0 = t_load + traffic["lead_s"]
        t1 = t0 + seconds
        proc.stdin.write((json.dumps({
            "host": host, "port": port, "loop": traffic["loop"],
            "t_load": t_load, "t0": t0, "t1": t1,
            "drain_until": t1 + traffic.get("drain_s", 0.0),
            "schedule": sched, "out": out}) + "\n").encode())
        await proc.stdin.drain()
        proc.stdin.close()
        snaps = [asyncio.ensure_future(_snapshot(driver, t))
                 for t in (t0, t1)]
        cpu = [asyncio.ensure_future(_cpu_at(t)) for t in (t0, t1)]
        tr = None
        if trace:
            span = min(TRACE_S, seconds)
            tr = asyncio.ensure_future(_trace(
                t0 + (seconds - span) / 2, span, os.path.join(tmp, "prof")))
        rc = await asyncio.wait_for(
            proc.wait(), t1 + traffic.get("drain_s", 0.0) + CLIENT_GRACE_S
            - time.monotonic())
        if rc != 0:
            raise RuntimeError(f"load client exited with {rc}")
        got = await asyncio.gather(*snaps)
        load = host_load(*await asyncio.gather(*cpu))
        traced = await tr if tr is not None else None
        if not router.alive:
            raise RuntimeError(f"the engine died: {driver.error!r}")
    finally:
        if proc is not None and proc.returncode is None:
            proc.kill()
            await proc.wait()
        await gw.stop()
    with open(out) as f:
        client = json.load(f)
    return {"records": client["records"], "t_stop": client["t_stop"],
            "t_load": t_load, "t0": t0, "t1": t1,
            "telemetry": {"t0": got[0], "t1": got[1]}, "trace": traced,
            "host_load": load}


def decode_contexts(records: List[Dict], lo: float, hi: float) -> List[int]:
    """Context length (KV rows attended) of each token a decode step
    produced, for the tokens that reached the client in [lo, hi): token
    j >= 1 of a request was produced by a step that attended over its
    prompt and j earlier tokens."""
    return [r["prompt_len"] + j for r in records
            for j, t in enumerate(r["times"]) if j >= 1 and lo <= t < hi]


DISPATCHES = ("prefill_chunk", "decode_step")
BETWEEN = "engine host work between dispatches"
WAITING = "engine waiting for requests"
WAITING_AFTER_S = 1.0


def span_rows(spans: List[Dict], m0: float, at_ns: float) -> List:
    """The program's spans as host rows on the trace's clock, monotonic
    time m0 lying at `at_ns` in the trace; and between consecutive engine
    dispatches a row for the engine's own host work (sampling, emitting,
    admission, tables): the trace has no event for it.  A pause longer
    than WAITING_AFTER_S is the engine waiting."""
    import trace_reduce as T

    def row(name, t, dur):
        return (T.HOST_PLANE, "program spans", name,
                at_ns + (t - m0) * 1e9, dur * 1e9, "")
    rows = [row(s["name"], s["t_s"], s["dur_s"]) for s in spans]
    disp = sorted((s["t_s"], s["t_s"] + s["dur_s"]) for s in spans
                  if s["name"] in DISPATCHES)
    for (_, end), (start, _) in zip(disp, disp[1:]):
        if start > end:
            rows.append(row(BETWEEN if start - end < WAITING_AFTER_S
                            else WAITING, end, start - end))
    return rows


def trace_numbers(traced: Dict, logdir: str) -> Dict:
    """The trace reduced over the traced window, which starts at the
    ANCHOR annotation (monotonic m0) and lasts m1 - m0."""
    import trace_reduce as T
    rows = T.events(T.xplane_path(logdir))
    lo = T.host_event_ns(rows, ANCHOR)
    rows += span_rows(traced["spans"], traced["m0"], lo)
    return T.reduce(rows, lo, lo + (traced["m1"] - traced["m0"]) * 1e9)


def correctness(cfg: Dict, seed: int, records: List[Dict],
                prompts: Dict[int, List[int]], lim: Dict,
                control: bool = False) -> Dict:
    """The reference over a seeded sample of the served requests (the
    longest always among them): the widest gap by which a served token's
    reference logit lies below the reference's best.  With `control`,
    the control's tokens (at each served position, the first choice of
    the reference one precision step lower) stand in the program's place
    and go through the same comparison; the program's own reading is
    kept beside it.  The reference is the one the configuration's family
    names."""
    R = cells.reference(cells.family(cfg))
    pool = [r for r in records if len(r["tokens"]) >= 2
            and not endtoend.refused(r)]
    if not pool:
        return {"checks": {"served_requests": {"value": 0, "limit": 1}},
                "correct": False}
    longest = max(pool, key=lambda r: (len(r["tokens"]), r["id"]))
    rest = [r for r in pool if r is not longest]
    rng = np.random.default_rng(seed)
    n = min(len(rest), lim["sample_requests"] - 1)
    pick = [longest] + [rest[i] for i in sorted(
        rng.choice(len(rest), n, replace=False))] if n else [longest]
    seqs = [{"prompt": prompts[r["id"]], "served": r["tokens"]} for r in pick]
    t = time.monotonic()
    own = None
    if control:
        both = R.control_gap(cfg, seed, seqs)
        gap, own = both["control"], both["served"]
    else:
        gap = R.served_gap(cfg, seed, seqs)
    limit = lim["widest_gap"]["limit"]
    # a stream that ended as finished must hold every token it was due
    short = sum(1 for r in records if r["finish"] == "length"
                and len(r["tokens"]) != r["max_tokens"])
    checks = {"widest_gap": {"value": gap["widest_gap"], "limit": limit},
              "short_streams": {"value": short, "limit": 0}}
    out = {"checks": checks,
           "correct": gap["widest_gap"] <= limit and short == 0,
           "compared_tokens": gap["tokens"], "mean_gap": gap["mean_gap"],
           "compared_requests": len(pick),
           "reference_s": time.monotonic() - t}
    if own is not None:
        out["program_widest_gap"] = own["widest_gap"]
        out["program_mean_gap"] = own["mean_gap"]
    return out


def run_cell(cell: Dict, cfg: Dict, traffic: Dict, lim: Dict, seed: int,
             seconds: float, trace: bool, *, per_layer: List[Dict],
             end_to_end: List[Dict], t_process: float,
             require_tpu: bool = True,
             plant: Optional[Callable] = None,
             control: bool = False) -> Dict:
    """One run; returns the result line's object (and extra keys).
    Off the chip (`require_tpu=False`, the benchmark's own tests) the
    run keeps no compile cache and the per-layer readers take the peaks
    of OFF_CHIP_PEAKS."""
    devs = device.check(cell["chips"], require_tpu)
    compiles = CompileCount()
    if require_tpu:
        enable_compile_cache()
    family = cells.family(cfg)
    dims = family.dims(cfg)
    gen = cells.generator(traffic["generator"])
    schedule = gen.build(traffic, seed, dims["v"], seconds)
    geom = traffic["engine"]
    model, engines = program.build(cfg, geom, seed)
    if plant is not None:
        plant(engines, dims["v"])
    program.warm_up(engines, geom, dims["v"])
    kv_name = program.kv_dtype_name(engines)
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        served = asyncio.run(serve(engines, schedule, traffic, seconds,
                                   trace, tmp))
        red = (trace_numbers(served["trace"], os.path.join(tmp, "prof"))
               if trace else None)
    setup_s = served["t_load"] - t_process
    mem = device.memory_peak_bytes(devs)
    program.free(engines)
    del engines, model
    gc.collect()

    records = served["records"]
    if traffic["loop"] == "open":
        e2e = endtoend.open_loop(records, served["t_stop"])
    else:
        e2e = endtoend.closed_loop(records, served["t0"], served["t1"])
    flat = schedule if traffic["loop"] == "open" else [
        r for client in schedule for r in client]
    prompts = {r["id"]: r["prompt"] for r in flat}
    chk = correctness(cfg, seed, records, prompts, lim, control)

    values: Dict[str, float] = {}
    if not trace:
        values["setup_s"] = setup_s
        if traffic["loop"] == "open":
            values["ttft_p90_ms"] = 1e3 * endtoend.tail(e2e["ttft_s"], 90)
            values["itl_p99_ms"] = 1e3 * endtoend.tail(e2e["itl_s"], 99)
        else:
            values["output_tok_s"] = e2e["tokens"] / seconds
        wanted = end_to_end
    else:
        ctx = {"cfg": cfg, "family": family, "dims": dims, "engine": geom,
               "kv_bytes": 1 if kv_name == "int8" else 2,
               "peaks": device.peaks(devs[0].device_kind if require_tpu
                                     else OFF_CHIP_PEAKS),
               "records": records, "served": served,
               "trace": red, "spans": served["trace"]["spans"],
               "telemetry": served["telemetry"],
               "decode_contexts": decode_contexts(
                   records, served["trace"]["m0"], served["trace"]["m1"])}
        for m in per_layer:
            v = cells.metric_reader(m["name"]).read(ctx)
            if v is not None:
                values[m["name"]] = v
        wanted = per_layer
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if m["name"] in values}
    dev = dict(device.describe(devs), memory_peak_bytes=mem)
    out = {"correct": chk["correct"], "attempted": e2e["attempted"],
           "failed": e2e["failed"], "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = chk["checks"]
    out["_extra"] = {
        "compared_tokens": chk.get("compared_tokens"),
        "compared_requests": chk.get("compared_requests"),
        "mean_gap": chk.get("mean_gap"),
        "reference_s": chk.get("reference_s"),
        "program_widest_gap": chk.get("program_widest_gap"),
        "program_mean_gap": chk.get("program_mean_gap"),
        "generator_lateness_s": endtoend.generator_lateness_s(records),
        "window_lateness_s": endtoend.generator_lateness_s(
            endtoend.window_requests(records)),
        **served["host_load"],
        "misses": e2e.get("misses"), "unfinished": e2e.get("unfinished"),
        "kv_dtype": kv_name,
        "compiles_in_window": compiles.between(served["t0"], served["t1"]),
        "requests_sent": len(records)}
    return out
