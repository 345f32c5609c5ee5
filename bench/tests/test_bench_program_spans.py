"""The readers of the program's own spans, on synthetic spans: host time
per engine-running loop iteration, and the wait in the driver's inbox."""
import pytest

import cells
import program_spans as S


def span(name, t, dur, sid=None, parent=None, **args):
    return {"name": name, "t_s": t, "dur_s": dur, "id": sid,
            "parent": parent, "args": args or None}


# two loop iterations that ran a step, one idle iteration, and a loop
# still open at the window's end (m1 = 10.0)
SPANS = [
    span("driver_loop", 0.0, 1.0, 1),
    span("engine_step", 0.1, 0.8, 2, 1),
    span("decode_step", 0.2, 0.5, 3, 2),
    span("enqueue", 0.2, 0.1, 4, 3),
    span("device_wait", 0.3, 0.4, 5, 3),
    span("sample", 0.7, 0.1, 6, 2),
    span("tap", 0.9, 0.05, 7, 1),
    span("driver_loop", 1.0, 2.0, 8),
    span("engine_step", 1.0, 1.9, 9, 8),
    span("prefill_chunk", 1.0, 1.0, 10, 9),
    span("device_wait", 1.2, 0.7, 11, 10),
    span("decode_step", 2.0, 0.8, 12, 9),
    span("device_wait", 2.1, 0.6, 13, 12),
    span("driver_loop", 3.0, 0.05, 14),
    span("idle_wait", 3.0, 0.05, 15, 14),
    span("driver_loop", 9.5, 1.0, 16),
    span("engine_step", 9.5, 0.9, 17, 16),
    span("driver_inbox", 0.5, 0.2, 18, rids=[3, 4]),
    span("driver_inbox", 0.6, 0.1, 19, rids=None),
    span("driver_inbox", 2.5, 0.4, 20, rids=[5]),
]


def test_step_host_ms_leaves_out_device_and_idle_waits():
    # iteration 1: 1.0 - 0.4; iteration 2: 2.0 - 0.7 - 0.6; the idle
    # iteration ran no step and the last one ends past the window
    assert S.step_host_ms(SPANS, 10.0) == pytest.approx(
        1e3 * ((1.0 - 0.4) + (2.0 - 1.3)) / 2)


def test_driver_inbox_ms_reads_only_jobs_with_requests():
    assert S.driver_inbox_ms(SPANS) == pytest.approx(1e3 * (0.2 + 0.4) / 2)


@pytest.mark.parametrize("spans", [
    [],
    # a program whose spans carry no nesting and no loop spans at all
    [{"name": "decode_step", "t_s": 0.0, "dur_s": 0.1, "args": None}],
    [span("driver_loop", 0.0, 0.1, 1), span("idle_wait", 0.0, 0.1, 2, 1),
     span("driver_inbox", 0.0, 0.1, 3, rids=None)],
])
def test_no_sample_reads_none(spans):
    ctx = {"spans": spans, "served": {"trace": {"m0": 0.0, "m1": 5.0}}}
    for name in ("step_host_ms.chat", "step_host_ms.batch",
                 "driver_inbox_ms"):
        assert cells.metric_reader(name).read(ctx) is None


def test_metric_files_read_the_traced_stretch():
    ctx = {"spans": SPANS, "served": {"trace": {"m0": 0.0, "m1": 10.0}}}
    want = S.step_host_ms(SPANS, 10.0)
    assert cells.metric_reader("step_host_ms.chat").read(ctx) == want
    assert cells.metric_reader("step_host_ms.batch").read(ctx) == want
    assert cells.metric_reader("driver_inbox_ms").read(ctx) == \
        S.driver_inbox_ms(SPANS)
