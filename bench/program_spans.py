"""Readers of the program's own spans in the traced stretch.

Each span is a dict of the program's tracer (`repro.obs.trace`): name,
start `t_s`, `dur_s`, `args`, and, where the program records nesting,
its `id` and the `parent` span open around it on the same thread.  A
program that records no such span gives None, never an error.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

LOOP = "driver_loop"        # one iteration of the engine thread's loop
STEP = "engine_step"
WAITS = ("device_wait", "idle_wait")    # the thread waits, not works


def _children(spans: List[Dict]) -> Dict[int, List[Dict]]:
    kids: Dict[int, List[Dict]] = defaultdict(list)
    for s in spans:
        if s.get("parent") is not None:
            kids[s["parent"]].append(s)
    return kids


def _waited(sid: int, kids: Dict[int, List[Dict]]) -> float:
    """Seconds of the WAITS spans under span `sid`."""
    total = 0.0
    for k in kids.get(sid, ()):
        total += k["dur_s"] if k["name"] in WAITS else _waited(k["id"],
                                                                kids)
    return total


def step_host_ms(spans: List[Dict], m1: float) -> Optional[float]:
    """Mean host time of a loop iteration that ran an engine step: the
    `driver_loop` span less its `device_wait` and `idle_wait`
    descendants, over the iterations that ended by `m1`."""
    kids = _children(spans)
    loops = [s for s in spans if s["name"] == LOOP
             and s["t_s"] + s["dur_s"] <= m1
             and any(k["name"] == STEP for k in kids.get(s["id"], ()))]
    if not loops:
        return None
    return 1e3 * sum(s["dur_s"] - _waited(s["id"], kids)
                     for s in loops) / len(loops)


def driver_inbox_ms(spans: List[Dict]) -> Optional[float]:
    """Mean wait in the engine driver's inbox of the jobs that carried
    requests (`driver_inbox` spans with request ids)."""
    d = [s["dur_s"] for s in spans if s["name"] == "driver_inbox"
         and (s.get("args") or {}).get("rids")]
    return 1e3 * sum(d) / len(d) if d else None
