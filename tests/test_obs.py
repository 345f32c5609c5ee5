"""Observability layer (repro.obs): span tracer ring semantics and
thread safety, Chrome-trace and Prometheus exporters (including a real
2-replica gateway capture with request ids correlated across
gateway/router/engine spans), flight-recorder postmortems on driver
death, the CIM-cost-model energy meter, and the structured access log.
"""
import json
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Gateway, iter_sse
from repro.api.driver import EngineDriver
from repro.fleet import FleetRouter
from repro.fleet.router import aggregate_summaries
from repro.models import DecoderLM, ModelConfig, init_params
from repro.obs import (EnergyMeter, FlightRecorder, chrome_trace,
                       get_tracer, prometheus_text,
                       slm_spec_from_model_config)
from repro.obs.trace import NULL_SPAN, Tracer
from repro.serve import PagedServeEngine, ServeRequest


def _cfg():
    return ModelConfig(name="s", family="dense", n_layers=2, d_model=32,
                       n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                       head_dim=16, dtype="float32", remat=False)


@pytest.fixture(scope="module")
def model_params():
    cfg = _cfg()
    model = DecoderLM(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0),
                        dtype_override=jnp.float32)
    return model, params


def _engine(model, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq", 64)
    kw.setdefault("page_size", 8)
    return PagedServeEngine(model, params, **kw)


@pytest.fixture
def tracing():
    """Enable the process tracer for one test, then restore the quiet
    default so unrelated tests stay un-instrumented."""
    tr = get_tracer()
    tr.clear()
    tr.enable()
    yield tr
    tr.disable()
    tr.clear()


# ----------------------------------------------------------------------------
# tracer: ring semantics
# ----------------------------------------------------------------------------
def test_disabled_tracer_records_nothing():
    tr = Tracer()
    assert not tr.enabled
    assert tr.span("x") is NULL_SPAN         # shared no-op singleton
    with tr.span("x", cat="engine", k=1):
        pass
    tr.instant("y", rid=3)
    tr.complete("z", 0.0, 1.0)
    assert tr.events() == []
    assert tr.dropped() == 0


def test_ring_wraparound_keeps_newest_and_counts_dropped():
    tr = Tracer(capacity=8).enable()
    for i in range(20):
        tr.instant("e", i=i)
    evs = tr.events()
    assert len(evs) == 8
    assert [e["args"]["i"] for e in evs] == list(range(12, 20))
    assert tr.dropped() == 12
    tr.clear()
    assert tr.events() == [] and tr.dropped() == 0


def test_span_and_complete_record_durations():
    t = [0.0]

    def clock():
        t[0] += 0.5
        return t[0]

    tr = Tracer(clock=clock).enable()
    with tr.span("work", cat="driver", job=7):
        pass
    tr.complete("measured", t0=10.0, dur_s=0.25, cat="engine", rids=[1])
    spans = {e["name"]: e for e in tr.events()}
    assert spans["work"]["ph"] == "X"
    assert spans["work"]["dur_s"] == pytest.approx(0.5)
    assert spans["work"]["args"] == {"job": 7}
    assert spans["measured"]["t_s"] == 10.0
    assert spans["measured"]["dur_s"] == 0.25
    assert spans["measured"]["args"]["rids"] == [1]


def test_per_thread_rings_and_unique_request_ids():
    tr = Tracer(capacity=256).enable()
    ids, errs = [], []

    def worker(k):
        try:
            for i in range(100):
                tr.instant("e", w=k)
                ids.append(tr.next_request_id())
        except Exception as e:      # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    rings = tr.rings()
    assert len(rings) == 4              # one ring per worker thread
    # each worker wrote its own ring, never a shared one (the OS may
    # reuse thread idents, so count per-ring events, not distinct tids)
    assert [len(r.events) for r in rings] == [100] * 4
    assert len(tr.events()) == 400 and tr.dropped() == 0
    assert len(set(ids)) == 400         # process-unique correlation ids


def test_spans_record_ids_parents_and_self_time():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tr = Tracer(clock=clock).enable()
    with tr.step_span("loop", 7, cat="driver"):
        with tr.span("step"):
            with tr.span("a"):
                pass
            tr.instant("mark")
            with tr.span("b"):
                pass
        tr.complete("inbox", t0=0.5, dur_s=0.25, cat="driver")
    evs = tr.events()
    by = {e["name"]: e for e in evs}
    ids = [e["id"] for e in evs if e["ph"] == "X"]
    assert len(set(ids)) == len(ids) and None not in ids
    assert by["loop"]["parent"] is None
    assert by["step"]["parent"] == by["loop"]["id"]
    assert by["a"]["parent"] == by["b"]["parent"] == by["step"]["id"]
    assert by["mark"]["parent"] == by["step"]["id"]
    assert by["mark"]["id"] is None
    # complete() records a measured interval: no parent, ring only
    assert by["inbox"]["parent"] is None
    # self time = duration less the children's
    kids = {}
    for e in evs:
        if e["ph"] == "X" and e["parent"] is not None:
            kids[e["parent"]] = kids.get(e["parent"], 0.0) + e["dur_s"]
    self_s = {e["name"]: e["dur_s"] - kids.get(e["id"], 0.0)
              for e in evs if e["ph"] == "X"}
    assert by["a"]["dur_s"] == by["b"]["dur_s"] == 1.0
    assert by["step"]["dur_s"] == 6.0 and self_s["step"] == 4.0
    assert by["loop"]["dur_s"] == 8.0 and self_s["loop"] == 2.0
    # the stack unwinds: a later span is top level again
    with tr.span("after"):
        pass
    assert tr.events()[-1]["parent"] is None


def test_spans_open_profiler_annotations_only_while_enabled(monkeypatch):
    import repro.obs.trace as trace_mod
    built = []

    class Note:
        def __init__(self, name, **kw):
            built.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(trace_mod, "_annotations", lambda: (Note, Note))
    tr = Tracer()
    with tr.span("x"), tr.step_span("loop", 3):
        pass
    with tr.timed("dispatch") as call:
        pass
    assert call.dur_s >= 0.0             # timed with tracing off too
    assert tr.step_span("loop", 3) is NULL_SPAN
    assert built == [] and tr.events() == []
    tr.enable()
    with tr.step_span("loop", 3), tr.span("x"):
        with tr.timed("dispatch") as call:
            pass
    assert built == [("loop", {"step_num": 3}), ("x", {}),
                     ("dispatch", {})]
    ev = next(e for e in tr.events() if e["name"] == "dispatch")
    assert ev["dur_s"] == call.dur_s     # the span is the timed interval


def test_obs_does_not_import_jax():
    import subprocess
    import sys
    code = ("import sys; import repro.obs.trace as t; tr = t.Tracer(); "
            "tr.span('x'); tr.instant('i'); "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True)
    assert out.stdout.strip() == "False"


# ----------------------------------------------------------------------------
# Chrome trace export
# ----------------------------------------------------------------------------
def test_chrome_trace_event_shape():
    tr = Tracer(clock=lambda: 2.0).enable()
    with tr.span("s", cat="engine", rids=[0]):
        pass
    tr.instant("i", cat="gateway", rid=0)
    doc = json.loads(json.dumps(chrome_trace(tr)))     # serializable
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert {m["name"] for m in metas} >= {"process_name", "thread_name"}
    for e in evs:
        assert {"ph", "name", "pid"} <= set(e)
        if e["ph"] != "M":
            assert "ts" in e and "tid" in e
            assert e["ts"] == pytest.approx(2.0e6)     # microseconds
    span = next(e for e in evs if e["name"] == "s")
    assert span["ph"] == "X" and span["dur"] == 0.0
    assert span["args"]["rids"] == [0]
    inst = next(e for e in evs if e["name"] == "i")
    assert inst["ph"] == "i" and inst["s"] == "t"
    assert doc["metadata"]["dropped_events"] == 0


# ----------------------------------------------------------------------------
# flight recorder
# ----------------------------------------------------------------------------
def test_flight_recorder_ring_and_dump(tmp_path):
    rec = FlightRecorder(capacity=4, label="unit",
                         clock=iter(np.arange(100.0)).__next__)
    for i in range(10):
        rec.record("step", i=i)
    assert rec.dropped == 6
    snap = rec.snapshot()
    assert [e["i"] for e in snap] == [6, 7, 8, 9]
    assert all(e["kind"] == "step" for e in snap)
    path = rec.dump(reason="boom", directory=str(tmp_path))
    with open(path) as f:
        payload = json.load(f)
    assert payload["label"] == "unit" and payload["reason"] == "boom"
    assert payload["dropped"] == 6 and len(payload["events"]) == 4


def test_driver_death_dumps_flight_record(model_params, tmp_path,
                                          monkeypatch):
    """A fatal engine step must leave a postmortem on disk: the ring of
    events leading up to the crash plus the recorded reason."""
    monkeypatch.setenv("REPRO_FLIGHT_DIR", str(tmp_path))
    model, params = model_params
    eng = _engine(model, params)
    boom = RuntimeError("induced step failure")

    def bad_step():
        raise boom
    eng.step = bad_step
    drv = EngineDriver(eng, idle_wait_s=0.01).start()
    done = threading.Event()
    fut = drv.submit([ServeRequest(prompt=np.array([1, 2, 3], np.int32),
                                   max_new_tokens=4, rid=0)],
                     lambda req: done.set())
    fut.result(timeout=5)
    drv._thread.join(timeout=5)
    assert not drv.alive and drv.error is boom
    assert done.wait(timeout=5)         # watcher failed over, not hung
    assert drv.flight_path is not None
    with open(drv.flight_path) as f:
        payload = json.load(f)
    assert repr(boom) in payload["reason"]
    kinds = [e["kind"] for e in payload["events"]]
    assert kinds[-1] == "fatal"         # last event is the crash itself
    assert "submit" in kinds            # ...preceded by engine history


# ----------------------------------------------------------------------------
# energy meter
# ----------------------------------------------------------------------------
def test_energy_meter_linear_fit_and_accounting():
    meter = EnergyMeter(_cfg())
    # the fitted per-token cost must match a direct simulator call
    from repro.core.hw import HWConfig
    from repro.core.simulator import EdgeCIMSimulator
    direct = EdgeCIMSimulator().decode_token(
        slm_spec_from_model_config(_cfg()), HWConfig(), 256.0,
        w_bits=4, a_bits=8)
    assert meter.decode_cost_j(256.0) == pytest.approx(direct.joules,
                                                       rel=1e-9)
    meter.charge_decode(10, mean_seq=256.0)
    meter.charge_prefill(64)
    assert meter.decode_j == pytest.approx(10 * direct.joules)
    assert meter.prefill_j > 0 and meter.total_j > meter.decode_j
    assert meter.tokens_per_j() == pytest.approx(10 / meter.total_j)
    s = meter.summary()
    assert s["sim_decode_tokens"] == 10.0
    assert s["sim_tokens_per_j"] > 0 and s["sim_tokens_per_s"] > 0
    meter.reset()
    assert meter.total_j == 0.0 and meter.summary()["sim_tokens_per_j"] == 0.0


def test_engine_summary_reports_simulated_energy(model_params):
    model, params = model_params
    eng = _engine(model, params)
    reqs = [ServeRequest(prompt=np.array([1, 2, 3, 4], np.int32),
                         max_new_tokens=5, rid=i) for i in range(2)]
    eng.run(reqs)
    m = eng.summary()
    assert m["sim_energy_j"] > 0
    # each request's first token comes off the prefill graph; all the
    # rest are decode tokens the meter charged
    assert m["sim_decode_tokens"] == m["tokens"] - m["requests"]
    assert m["sim_tokens_per_j"] == pytest.approx(
        m["sim_decode_tokens"] / m["sim_energy_j"])


def test_fleet_aggregation_recomputes_energy_ratios():
    a = {"sim_energy_j": 2.0, "sim_decode_tokens": 100.0,
         "sim_time_s": 1.0, "tokens": 110.0}
    b = {"sim_energy_j": 6.0, "sim_decode_tokens": 200.0,
         "sim_time_s": 3.0, "tokens": 220.0}
    agg = aggregate_summaries([a, b])
    assert agg["sim_energy_j"] == pytest.approx(8.0)
    # ratio recomputed from fleet sums, NOT averaged per replica
    assert agg["sim_tokens_per_j"] == pytest.approx(300.0 / 8.0)
    assert agg["sim_tokens_per_s"] == pytest.approx(300.0 / 4.0)


# ----------------------------------------------------------------------------
# prometheus exposition
# ----------------------------------------------------------------------------
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"            # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""  # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
    r" (NaN|[+-]?Inf|[-+0-9.eE]+)$")


def _parse_prom(text):
    """Parse exposition text into {name: {labelstr: float}}; asserts
    every non-comment line matches the 0.0.4 grammar."""
    samples = {}
    for line in text.strip().split("\n"):
        if line.startswith("# TYPE "):
            parts = line.split()
            assert len(parts) == 4
            assert parts[3] in ("counter", "gauge", "histogram")
            continue
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
        name_labels, _, value = line.rpartition(" ")
        name, _, labels = name_labels.partition("{")
        samples.setdefault(name, {})[labels] = float(value)
    return samples


def test_prometheus_text_grammar_and_agreement():
    payload = {
        "schema_version": 2,
        "engine": {"tokens": 42.0, "requests": 7.0,
                   "ttft_p50_s": 0.0125, "spec_acceptance_rate":
                   float("nan"), "sim_tokens_per_j": 173.0},
        "n_running": 3, "n_queued": 0, "kv_pages_free": 11,
        "gateway": {"http_requests": 9, "inflight": 2,
                    "max_pending": 64},
        "fleet": {"n_replicas": 2, "n_live": 2,
                  "counters": {"dispatches": 5},
                  "affinity_hits": 4,
                  "replicas": {
                      "0": {"alive": True, "pending": 1,
                            "dispatches": 3,
                            "snapshot": {"kv_occupancy": 0.5}},
                      "1": {"alive": False, "pending": 0,
                            "dispatches": 2, "snapshot": {}}}},
        "histograms": {"ttft_s": {
            "edges_s": [0.0, 0.1, 1.0, "inf"], "counts": [2, 3, 1]}},
    }
    text = prometheus_text(payload)
    samples = _parse_prom(text)
    assert samples["repro_engine_tokens_total"][""] == 42.0
    assert samples["repro_engine_requests_total"][""] == 7.0
    assert samples["repro_engine_ttft_p50_s"][""] == 0.0125
    assert samples["repro_engine_sim_tokens_per_j"][""] == 173.0
    assert samples["repro_gateway_http_requests_total"][""] == 9.0
    assert samples["repro_gateway_inflight"][""] == 2.0
    assert samples["repro_fleet_dispatches_total"][""] == 5.0
    assert samples["repro_fleet_affinity_hits_total"][""] == 4.0
    up = samples["repro_replica_up"]
    assert up['replica="0"}'] == 1.0 and up['replica="1"}'] == 0.0
    # histogram: cumulative buckets ending in +Inf == count
    buckets = samples["repro_ttft_seconds_bucket"]
    assert buckets['le="0.1"}'] == 2.0
    assert buckets['le="1.0"}'] == 5.0
    assert buckets['le="+Inf"}'] == 6.0
    assert samples["repro_ttft_seconds_count"][""] == 6.0
    # "no data yet" is an ABSENT series, never a NaN sample: a NaN line
    # poisons every Prometheus recording rule that aggregates over it
    assert "repro_engine_spec_acceptance_rate" not in text
    assert "NaN" not in text


# ----------------------------------------------------------------------------
# end-to-end: 2-replica gateway capture
# ----------------------------------------------------------------------------
async def _get(host, port, path):
    import asyncio
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    await writer.drain()
    data = await reader.read()
    writer.close()
    return data


async def _post(host, port, body):
    import asyncio
    payload = json.dumps(body).encode()
    reader, writer = await asyncio.open_connection(host, port)
    writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                  f"Content-Length: {len(payload)}\r\n\r\n"
                  ).encode() + payload)
    await writer.drain()
    data = await reader.read()
    writer.close()
    return data


def _status(raw):
    return int(raw.split(b"\r\n", 1)[0].split()[1])


def _body(raw):
    return raw.partition(b"\r\n\r\n")[2]


def test_gateway_trace_prometheus_and_access_log(model_params, tracing,
                                                 tmp_path):
    import asyncio
    import io
    model, params = model_params
    log = io.StringIO()

    async def run():
        engines = [_engine(model, params) for _ in range(2)]
        gw = Gateway(FleetRouter(engines, policy="rr", max_pending=16),
                     access_log=log)
        host, port = await gw.start()
        try:
            raws = await asyncio.gather(*[
                _post(host, port, {"prompt": [1 + i, 2, 3],
                                   "max_tokens": 4}) for i in range(4)])
            trace_raw = await _get(host, port, "/debug/trace")
            prom_raw = await _get(host, port,
                                  "/metrics?format=prometheus")
            json_raw = await _get(host, port, "/metrics")
        finally:
            await gw.stop()
        return raws, trace_raw, prom_raw, json_raw

    raws, trace_raw, prom_raw, json_raw = asyncio.run(run())
    assert all(_status(r) == 200 for r in raws)

    # -- Chrome trace: request ids correlate across all three layers
    doc = json.loads(_body(trace_raw))
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} >= {"M", "X", "i"}
    gw_spans = [e for e in evs
                if e.get("name") == "request" and e["ph"] == "X"]
    assert len(gw_spans) == 4
    gw_rids = {e["args"]["rid"] for e in gw_spans}
    route_rids = {r for e in evs if e.get("name") == "route_dispatch"
                  for r in e["args"]["rids"]}
    decode_rids = {r for e in evs if e.get("name") == "decode_step"
                   for r in e["args"]["rids"]}
    assert gw_rids <= route_rids, "router missed dispatch events"
    assert gw_rids <= decode_rids, \
        "engine decode spans don't carry the gateway's request ids"
    # distinct per-replica driver tracks, named by the fleet
    thread_names = {e["args"]["name"] for e in evs
                    if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"engine-driver-0", "engine-driver-1"} <= thread_names
    # rr over 4 requests lands work on both replicas
    driver_tids = {e["tid"] for e in evs
                   if e.get("name") == "decode_step"}
    assert len(driver_tids) == 2

    # -- Prometheus view parses and agrees with the JSON payload
    assert b"text/plain; version=0.0.4" in prom_raw
    samples = _parse_prom(_body(prom_raw).decode())
    payload = json.loads(_body(json_raw))
    assert payload["schema_version"] == 3
    assert samples["repro_metrics_schema_version"][""] == 3.0
    # scraped AFTER the json view, but the server was idle in between:
    # token counters must agree exactly
    assert samples["repro_engine_tokens_total"][""] == \
        payload["engine"]["tokens"]
    assert samples["repro_gateway_completed_samples_total"][""] == \
        payload["gateway"]["completed_samples"]
    assert payload["engine"]["sim_energy_j"] > 0
    assert payload["engine"]["sim_tokens_per_j"] > 0
    assert samples["repro_engine_sim_tokens_per_j"][""] > 0
    assert samples["repro_ttft_seconds_count"][""] == 4.0

    # -- structured access log: one JSON line per request
    lines = [json.loads(ln) for ln in
             log.getvalue().strip().split("\n")]
    assert len(lines) == 4
    for ln in lines:
        assert ln["status"] == "ok" and ln["tokens"] == 4
        assert ln["replica"] in (0, 1) and ln["policy"] == "rr"
        assert ln["ttft_s"] > 0 and ln["dur_s"] >= ln["ttft_s"]
    assert {ln["rid"] for ln in lines} <= gw_rids


def test_debug_trace_404_when_disabled(model_params):
    import asyncio
    model, params = model_params
    get_tracer().disable()

    async def run():
        gw = Gateway(_engine(model, params))
        host, port = await gw.start()
        try:
            return await _get(host, port, "/debug/trace")
        finally:
            await gw.stop()

    raw = asyncio.run(run())
    assert _status(raw) == 404
    assert b"tracing disabled" in raw


def test_tracing_disabled_emits_no_events(model_params):
    """The default path must stay quiet: an untraced engine run leaves
    the process tracer empty (the recorder, by contrast, is always
    on)."""
    model, params = model_params
    tr = get_tracer()
    tr.disable()
    tr.clear()
    eng = _engine(model, params)
    eng.run([ServeRequest(prompt=np.array([1, 2, 3], np.int32),
                          max_new_tokens=3, rid=0)])
    assert tr.events() == []
    assert eng.recorder.pushes > 0


def test_driven_engine_emits_the_phase_tree(model_params, tracing):
    """One loop iteration per step: driver_loop > (driver_job,
    sweep_done, engine_step > (admit, build_inputs, dispatch >
    (enqueue, device_wait), sample, emit), tap); the dispatch spans sum
    to the engine's own step clocks, and a submit's wait in the driver's
    inbox is a ring-only span carrying its request ids.  Idle iterations
    record no loop span: the idle stretch before the work is one
    ring-only idle_wait, and the quiet stretch after it adds nothing."""
    import time
    model, params = model_params
    eng = _engine(model, params, prefill_chunk=8)
    driver = EngineDriver(eng, tap=lambda engine: None).start()
    time.sleep(0.1)                 # idle iterations before the work
    reqs = [ServeRequest(prompt=np.arange(1, 12, dtype=np.int32),
                         max_new_tokens=4, rid=i) for i in range(2)]
    for i, r in enumerate(reqs):
        r.trace_id = 100 + i
    finished = threading.Event()
    driver.submit(reqs, lambda r: finished.set()
                  if all(q.done for q in reqs) else None).result(60)
    assert finished.wait(60)
    time.sleep(0.2)                 # idle iterations after the work
    driver.stop()
    assert not driver.alive
    evs = [e for e in tracing.events() if e["ph"] == "X"]
    by_id = {e["id"]: e for e in evs}
    kids = {}
    for e in evs:
        kids.setdefault(e["parent"], []).append(e)

    def names(e):
        return [k["name"] for k in sorted(kids.get(e["id"], []),
                                          key=lambda k: k["t_s"])]

    steps = [e for e in evs if e["name"] == "engine_step"]
    assert len(steps) == eng.telemetry.steps
    loops = [e for e in evs if e["name"] == "driver_loop"]
    assert len(loops) == len(steps)
    for st in steps:
        loop = by_id[st["parent"]]
        assert loop["name"] == "driver_loop"
        assert names(loop)[-1] == "tap"
        ph = names(st)
        assert ph[0] == "admit"
        # each dispatch comes with its inputs before and sampling /
        # emission after, in that order
        for i, n in enumerate(ph):
            if n in ("prefill_chunk", "decode_step"):
                assert ph[i - 1] == "build_inputs"
                assert ph[i + 1] in ("sample", "emit")
    # jobs and sweeps run inside a stepping loop, or at top level in
    # an idle iteration (the first submit, the last completions)
    for name in ("driver_job", "sweep_done"):
        found = [e for e in evs if e["name"] == name]
        assert found and all(e["parent"] is None
                             or by_id[e["parent"]]["name"] == "driver_loop"
                             for e in found)
    disp = [e for e in evs if e["name"] in ("prefill_chunk", "decode_step")]
    assert {e["name"] for e in disp} == {"prefill_chunk", "decode_step"}
    for d in disp:
        assert names(d) == ["enqueue", "device_wait"]
        wait = max(kids[d["id"]], key=lambda k: k["t_s"])
        assert d["t_s"] <= wait["t_s"]
        assert wait["t_s"] + wait["dur_s"] <= d["t_s"] + d["dur_s"]
        assert set(d["args"]["rids"]) <= {100, 101}
    tel = eng.telemetry
    assert sum(e["dur_s"] for e in disp if e["name"] == "prefill_chunk") \
        == pytest.approx(tel.prefill_s, rel=1e-12, abs=1e-12)
    assert sum(e["dur_s"] for e in disp if e["name"] == "decode_step") \
        == pytest.approx(tel.decode_s, rel=1e-12, abs=1e-12)
    idle = [e for e in evs if e["name"] == "idle_wait"]
    assert len(idle) == 1 and idle[0]["parent"] is None
    assert idle[0]["t_s"] + idle[0]["dur_s"] <= min(e["t_s"] for e in loops)
    inbox = [e for e in evs if e["name"] == "driver_inbox"]
    assert any(e["args"]["rids"] == [100, 101] for e in inbox)
    assert all(e["parent"] is None and e["cat"] == "driver"
               for e in inbox)
    # the Chrome export carries the nesting in args
    doc = chrome_trace(tracing)
    dec = next(e for e in doc["traceEvents"] if e["name"] == "decode_step")
    assert by_id[dec["args"]["parent"]]["name"] == "engine_step"
    assert dec["args"]["id"] in by_id


def test_serve_programs_are_named_and_scope_every_projection(model_params):
    """Prefill and decode lower as two programs of their own names, and
    each projection's ops carry its scope; no scope matches a kernel's
    name, which the benchmark's trace reduction looks for."""
    from repro.serve import ServeConfig
    model, params = model_params
    eng = PagedServeEngine(model, params, ServeConfig(
        precision="int4", max_batch=2, max_seq=32, page_size=8,
        prefill_chunk=8))
    scopes = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_up",
              "down_proj", "lm_head", "embed")
    b = 2
    for fn, s, name in ((eng._prefill_fn, 8, "jit_serve_prefill"),
                        (eng._decode_fn, 1, "jit_serve_decode")):
        text = fn.lower(
            eng.model, eng.params, dict(eng.cache.pools),
            {"tokens": jnp.zeros((b, s), jnp.int32)},
            jnp.zeros((b, eng.cache.max_pages), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32)
        ).as_text(dialect="hlo", debug_info=True)
        assert text.startswith(f"HloModule {name},")
        for scope in scopes:
            assert f"{scope}/" in text, (name, scope)
            assert "paged_flash_attention" not in scope
            assert "swiglu_qgemv" not in scope
    # the engine's one step entry routes each shape to its program
    seen = []
    for attr in ("_prefill_fn", "_decode_fn"):
        orig = getattr(eng, attr)
        setattr(eng, attr, lambda *a, _o=orig, _n=attr: (
            seen.append(_n), _o(*a))[1])
    eng.run([ServeRequest(prompt=np.arange(1, 12, dtype=np.int32),
                          max_new_tokens=3, rid=0)])
    assert seen[:2] == ["_prefill_fn", "_prefill_fn"]
    assert set(seen[2:]) == {"_decode_fn"}


# ----------------------------------------------------------------------------
# trace_view CLI
# ----------------------------------------------------------------------------
def test_trace_view_rollup(tmp_path, capsys):
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "trace_view.py"))
    tv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tv)

    doc = {"traceEvents": [
        {"ph": "X", "name": "request", "cat": "gateway", "ts": 0,
         "dur": 5000.0, "pid": 1, "tid": 1,
         "args": {"rid": 7, "status": "ok", "tokens": 3}},
        {"ph": "X", "name": "decode_step", "cat": "engine", "ts": 100,
         "dur": 1000.0, "pid": 1, "tid": 2, "args": {"rids": [7, 8]}},
        {"ph": "X", "name": "decode_step", "cat": "engine", "ts": 1200,
         "dur": 2000.0, "pid": 1, "tid": 2, "args": {"rids": [7]}},
        {"ph": "i", "name": "admit", "cat": "engine", "ts": 50,
         "pid": 1, "tid": 2, "args": {"rid": 7}},
    ]}
    path = tmp_path / "t.json"
    path.write_text(json.dumps(doc))

    events = tv.load_events(str(path))
    agg = tv.phase_breakdown(events)
    assert agg["decode_step"]["n"] == 2
    assert agg["decode_step"]["total_us"] == pytest.approx(3000.0)
    reqs = tv.per_request(events)
    assert reqs[7]["wall_us"] == pytest.approx(5000.0)
    # rid 7 is charged BOTH decode steps; rid 8 only the shared one
    assert reqs[7]["phases"]["decode_step"] == pytest.approx(3000.0)
    assert reqs[8]["phases"]["decode_step"] == pytest.approx(1000.0)
    assert tv.main([str(path), "--top", "2"]) == 0
    out = capsys.readouterr().out
    assert "decode_step" in out and "slowest requests" in out


def test_trace_view_counts_nested_time_once(tmp_path, capsys):
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "trace_view.py"))
    tv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tv)

    def x(name, ts, dur, sid, parent=None):
        args = {"id": sid}
        if parent is not None:
            args["parent"] = parent
        return {"ph": "X", "name": name, "cat": "engine", "ts": ts,
                "dur": dur, "pid": 1, "tid": 2, "args": args}

    events = [x("driver_loop", 0, 1000.0, 1),
              x("engine_step", 10, 900.0, 2, 1),
              x("decode_step", 20, 600.0, 3, 2),
              x("enqueue", 20, 100.0, 4, 3),
              x("device_wait", 120, 500.0, 5, 3),
              x("sample", 620, 200.0, 6, 2)]
    agg = tv.phase_breakdown(events)
    assert agg["driver_loop"]["self_us"] == pytest.approx(100.0)
    assert agg["engine_step"]["self_us"] == pytest.approx(100.0)
    assert agg["decode_step"]["self_us"] == pytest.approx(0.0)
    assert agg["decode_step"]["total_us"] == pytest.approx(600.0)
    assert agg["device_wait"]["self_us"] == pytest.approx(500.0)
    # self times add up to the outermost span: nothing counted twice
    assert sum(a["self_us"] for a in agg.values()) == pytest.approx(1000.0)
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"traceEvents": events}))
    assert tv.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "self" in out and "device_wait" in out
