"""Tail arithmetic: refused requests are misses, and a request still
waiting when the client stopped counts at its wait so far."""
import math

import endtoend as E


def rec(due, times=(), status=200, error=None, max_tokens=2, seg="window"):
    return {"id": 0, "due": due, "sent": due, "status": status,
            "error": error, "times": list(times), "tokens": [1] * len(times),
            "max_tokens": max_tokens, "segment": seg, "done": None}


def test_tail_is_nearest_rank():
    v = list(range(1, 11))                  # 1..10
    assert E.tail(v, 90) == 9 and E.tail(v, 99) == 10
    assert E.tail(v, 50) == 5 and E.tail([3.0], 90) == 3.0


def test_refused_and_still_waiting_requests_count_in_the_tail():
    recs = [rec(0.0, (0.1, 0.2)) for _ in range(8)]
    recs.append(rec(1.0, status=429))           # refused: a miss
    recs.append(rec(2.0))                       # still waiting at 12.0
    recs.append(rec(0.0, (0.1, 0.2), seg="lead"))     # not in the window
    got = E.open_loop(recs, t_stop=12.0)
    assert got["attempted"] == 10 and got["misses"] == 1
    assert got["failed"] == 1 and got["unfinished"] == 1
    assert sorted(got["ttft_s"])[-2:] == [10.0, math.inf]
    assert E.tail(got["ttft_s"], 90) == 10.0     # the waiting one
    assert E.tail(got["ttft_s"], 99) == math.inf
    assert len(got["itl_s"]) == 8


def test_a_failed_stream_is_a_miss():
    got = E.open_loop([rec(0.0, error="ConnectionResetError()")], 5.0)
    assert got["ttft_s"] == [math.inf] and got["failed"] == 1


def test_closed_loop_counts_tokens_inside_the_window():
    a = rec(0.0, (0.5, 1.5, 2.5, 3.5), max_tokens=4)
    a["done"] = 3.6
    b = rec(2.0, (2.2, 5.0), max_tokens=9)
    got = E.closed_loop([a, b], 1.0, 3.0)
    assert got["tokens"] == 3 and got["attempted"] == 2


def test_host_load_shares_of_the_machine():
    import os

    import harness
    tck = os.sysconf("SC_CLK_TCK")
    # user nice system idle iowait irq softirq steal, over 1000 ticks:
    # 300 busy, 100 stolen, 50 waiting on I/O; this process 1 s of it
    a = {"ticks": [0] * 8, "own_s": 2.0}
    b = {"ticks": [200, 0, 100, 550, 50, 0, 0, 100], "own_s": 3.0}
    got = harness.host_load(a, b)
    assert got["host_busy_pct"] == 30.0 and got["host_steal_pct"] == 10.0
    assert got["host_iowait_pct"] == 5.0
    assert abs(got["bench_cpu_pct"] - 100.0 * tck / 1000) < 1e-9
    assert harness.host_load({"ticks": None, "own_s": 0}, b) == {}
