"""The reduction from a profiler trace to device numbers, on a synthetic
trace whose answers are worked out by hand."""
import pytest

import trace_reduce as T

DEV = "/device:TPU:0"
H = T.HOST_PLANE


def op(name, start, dur, meta=""):
    return (DEV, T.OPS_LINE, name, float(start), float(dur), meta)


def mod(name, start, dur):
    return (DEV, T.MODULES_LINE, name, float(start), float(dur), "")


def host(name, start, dur, line="python"):
    return (H, line, name, float(start), float(dur), "")


# two runs of a decode program and one of a prefill program, in ns:
#   decode [0, 100): ops fusion [0, 30), attention kernel [20, 60)
#   prefill [150, 250): ops fusion [150, 250)
#   decode [300, 400): attention kernel [300, 340), swiglu [350, 390)
ROWS = [
    mod("jit_serve_step(1)", 0, 100), mod("jit_serve_step(2)", 150, 100),
    mod("jit_serve_step(1)", 300, 100),
    op("fusion.1", 0, 30), op("custom-call.7", 20, 40,
                              "jit(serve_step)/paged_flash_attention"),
    op("fusion.2", 150, 100),
    op("custom-call.7", 300, 40, "jit(serve_step)/paged_flash_attention"),
    op("swiglu_qgemv", 350, 40),
    host("$engine.py:553 step", 0, 500),
    host("$engine.py:438 _sample_rows", 60, 90),
    host("$threading.py:323 wait", 400, 100),
]


def test_union_merges_overlaps():
    assert T.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_busy_counts_overlapping_ops_once_and_clips_to_window():
    # ops cover [0, 60) + [150, 250) + [300, 340) + [350, 390) = 240
    assert T.busy_ns(ROWS, DEV, 0, 500) == 240
    # window [50, 320): [50, 60) + [150, 250) + [300, 320) = 130
    assert T.busy_ns(ROWS, DEV, 50, 320) == 130


def test_idle_gaps_fill_the_rest_of_the_window():
    gaps = T.idle_gaps(ROWS, DEV, 0, 500)
    assert gaps == [(60, 150), (250, 300), (340, 350), (390, 500)]
    assert sum(e - s for s, e in gaps) + 240 == 500


def test_ops_are_attributed_to_the_program_run_that_holds_them():
    progs = T.programs(ROWS, DEV, 0, 500)
    dec, pre = progs["jit_serve_step(1)"], progs["jit_serve_step(2)"]
    assert dec["runs"] == 2 and dec["seconds"] == pytest.approx(200e-9)
    assert pre["runs"] == 1 and set(pre["ops"]) == {"fusion.2"}
    k = T.kernel(dec["ops"], "paged_flash_attention")
    assert k["count"] == 2 and k["seconds"] == pytest.approx(80e-9)
    assert T.kernel(dec["ops"], "swiglu_qgemv")["count"] == 1
    assert T.most_run(progs, "paged_flash_attention") is dec
    assert T.most_run(progs, "no_such_kernel") is None


def test_leaf_ops_leave_out_the_loops_that_hold_them():
    loop = op("while.5", 0, 100)
    body = [op("fusion.1", 0, 30), op("fusion.2", 40, 50)]
    assert T.leaf_ops([loop] + body) == body
    assert T.leaf_ops(body) == body


def test_reduce_names_idle_gaps_by_the_innermost_host_event():
    red = T.reduce(ROWS, 0, 500)
    assert red["busy_s"] == pytest.approx(240e-9)
    assert red["window_s"] == pytest.approx(500e-9)
    gaps = dict(red["idle_gaps"])
    # [60, 150) lies inside _sample_rows; [390, 500) mostly inside wait;
    # [250, 300) and [340, 350) only inside the whole step
    assert gaps["$engine.py:438 _sample_rows"] == pytest.approx(90e-9)
    assert gaps["$threading.py:323 wait"] == pytest.approx(110e-9)
    assert gaps["$engine.py:553 step"] == pytest.approx(60e-9)
    assert red["device_ops"][0] == ["fusion.2", pytest.approx(100e-9)]


def test_no_device_plane_gives_no_device_time():
    red = T.reduce([r for r in ROWS if r[0] == H], 0, 500)
    assert red["planes"] == 0 and red["busy_s"] == 0.0
    assert T.HostIndex([]).label(0, 1) == T.NO_HOST_EVENT


def test_engine_host_work_between_dispatches_names_the_gaps():
    import harness
    spans = [{"name": "decode_step", "t_s": 10.0, "dur_s": 0.1},
             {"name": "prefill_chunk", "t_s": 10.15, "dur_s": 0.2},
             {"name": "decode_step", "t_s": 12.0, "dur_s": 0.1},
             {"name": "request", "t_s": 9.0, "dur_s": 5.0}]
    rows = harness.span_rows(spans, m0=10.0, at_ns=0.0)
    extra = {r[2]: (r[3], r[4]) for r in rows[len(spans):]}
    assert extra[harness.BETWEEN] == pytest.approx((0.1e9, 0.05e9))
    assert extra[harness.WAITING] == pytest.approx((0.35e9, 1.65e9))
    dev = [op("fusion.1", 0, 0.1e9), op("fusion.2", 0.15e9, 0.2e9),
           op("fusion.3", 2.0e9, 0.1e9)]
    gaps = dict(T.reduce(dev + rows, 0, 2.1e9)["idle_gaps"])
    assert gaps[harness.BETWEEN] == pytest.approx(0.05)
    assert gaps[harness.WAITING] == pytest.approx(1.65)


def test_host_rows_are_placed_by_the_anchor_event():
    import harness
    # the profiler started 3 ms before the anchor, which opened at
    # monotonic 10.0; a span at monotonic 10.5 lies 0.5 s after it
    trace = [host("profiler start-up", 0, 3e6),
             host(harness.ANCHOR, 3e6, 1e3),
             op("fusion.1", 3e6, 1e9)]
    lo = T.host_event_ns(trace, harness.ANCHOR)
    assert lo == 3e6
    rows = harness.span_rows(
        [{"name": "decode_step", "t_s": 10.5, "dur_s": 0.1}], 10.0, lo)
    assert rows[0][3] == pytest.approx(3e6 + 0.5e9)
    # the window opens at the anchor: the start-up is not idle time
    red = T.reduce(trace + rows, lo, lo + 1e9)
    assert red["busy_s"] == pytest.approx(red["window_s"])
    with pytest.raises(ValueError):
        T.host_event_ns([op("fusion.1", 0, 1)], harness.ANCHOR)
