"""Reduce a profiler trace (`.xplane.pb`) to the benchmark's device numbers.

`events()` flattens the trace into rows (plane, line, name, start_ns,
dur_ns, meta); everything else works on such rows, so the tests can feed
it a synthetic trace.  `meta` joins the event's string stats (the HLO
op's long name, its source op), which is where a kernel's own name
appears when the event is named after an HLO instruction.

On a TPU each chip has a plane `/device:TPU:<n>`; its "XLA Ops" line
holds one event per operation that ran, kernels among them, named by
their HLO text (a loop's event contains the events of its body), and
its "XLA Modules" line one event per compiled program executed.  Host
threads are the lines of `/host:CPU`, with the runtime's own events
(dispatch, transfers); the caller may add the program's spans there.
Times are nanoseconds from the start of the trace session.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Row = Tuple[str, str, str, float, float, str]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
_META_STATS = ("long_name", "tf_op", "hlo_op", "name", "kernel_details",
               "source")


def xplane_path(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def events(path: str) -> List[Row]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    rows: List[Row] = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            for ev in line.events:
                meta = ""
                if device:
                    meta = " ".join(str(v) for k, v in ev.stats
                                    if k in _META_STATS and isinstance(v, str))
                rows.append((plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns), meta))
    return rows


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) of possibly overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def device_planes(rows: Sequence[Row]) -> List[str]:
    return sorted({r[0] for r in rows if r[0].startswith(DEVICE_PREFIX)})


def ops(rows: Sequence[Row], plane: str, lo: float, hi: float) -> List[Row]:
    return [r for r in rows if r[0] == plane and r[1] == OPS_LINE
            and lo <= r[3] < hi]


def busy_ns(rows: Sequence[Row], plane: str, lo: float, hi: float) -> float:
    """Time of [lo, hi) in which some operation ran on the device."""
    ivs = [c for r in rows if r[0] == plane and r[1] == OPS_LINE
           for c in [clip(r[3], r[3] + r[4], lo, hi)] if c]
    return sum(e - s for s, e in union(ivs))


def idle_gaps(rows: Sequence[Row], plane: str, lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi) in which no operation ran on `plane`."""
    gaps, t = [], lo
    for s, e in union(c for r in rows if r[0] == plane and r[1] == OPS_LINE
                      for c in [clip(r[3], r[3] + r[4], lo, hi)] if c):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def programs(rows: Sequence[Row], plane: str, lo: float, hi: float
             ) -> Dict[str, Dict]:
    """Per compiled program run on `plane` in [lo, hi): its runs, their
    device seconds, and per operation name (and meta) the seconds and
    count of the operations that ran inside those runs."""
    mods = sorted((r[3], r[3] + r[4], r[2]) for r in rows
                  if r[0] == plane and r[1] == MODULES_LINE
                  and lo <= r[3] < hi)
    starts = [m[0] for m in mods]
    out: Dict[str, Dict] = {}
    for s, e, name in mods:
        p = out.setdefault(name, {"runs": 0, "seconds": 0.0, "ops": {}})
        p["runs"] += 1
        p["seconds"] += (e - s) * 1e-9
    for r in ops(rows, plane, lo, hi):
        i = bisect.bisect_right(starts, r[3]) - 1
        if i < 0 or r[3] >= mods[i][1]:
            continue
        o = out[mods[i][2]]["ops"].setdefault(
            r[2], {"seconds": 0.0, "count": 0, "meta": r[5]})
        o["seconds"] += r[4] * 1e-9
        o["count"] += 1
    return out


def leaf_ops(rows: Sequence[Row]) -> List[Row]:
    """The op events that contain no other event of their line (a loop's
    event holds its body's), so their times add up without counting
    twice."""
    srt = sorted(rows, key=lambda r: (r[0], r[1], r[3], -r[4]))
    return [r for r, nxt in zip(srt, srt[1:] + [None])
            if nxt is None or nxt[:2] != r[:2] or nxt[3] >= r[3] + r[4]]


def kernel(prog_ops: Dict[str, Dict], name: str) -> Dict:
    """Seconds and count of the operations that are kernel `name`, by
    the op's name or its meta."""
    hit = [v for k, v in prog_ops.items() if name in k or name in v["meta"]]
    return {"seconds": sum(v["seconds"] for v in hit),
            "count": sum(v["count"] for v in hit)}


def most_run(progs: Dict[str, Dict], with_kernel: str) -> Optional[Dict]:
    """The program run most often among those that hold `with_kernel`:
    the decode step, where every engine step decodes and only some
    prefill."""
    have = [p for p in progs.values()
            if kernel(p["ops"], with_kernel)["count"]]
    return max(have, key=lambda p: p["runs"]) if have else None


def host_event_ns(rows: Sequence[Row], name: str) -> float:
    """Start of the first host event called `name`: where a clock the
    caller read inside that event meets the trace's."""
    hits = [r[3] for r in rows if r[0] == HOST_PLANE and r[2] == name]
    if not hits:
        raise ValueError(f"no host event {name!r} in the trace")
    return min(hits)


NO_HOST_EVENT = "no host event"


class HostIndex:
    """The host's events, for asking what the host was doing in an
    interval."""

    def __init__(self, rows: Sequence[Row]):
        import numpy as np
        host = [(n, s, d) for p, l, n, s, d, _ in rows if p == HOST_PLANE]
        self.names = [h[0] for h in host]
        self.start = np.array([h[1] for h in host], np.float64)
        self.dur = np.array([h[2] for h in host], np.float64)

    def label(self, start: float, end: float) -> str:
        """The shortest host event covering most of [start, end), else
        the one overlapping it most; NO_HOST_EVENT when none overlaps."""
        import numpy as np
        if not self.names:
            return NO_HOST_EVENT
        ov = (np.minimum(end, self.start + self.dur)
              - np.maximum(start, self.start))
        if ov.max() <= 0:
            return NO_HOST_EVENT
        covers = ov >= 0.5 * (end - start)
        if covers.any():
            i = np.flatnonzero(covers)[np.argmin(self.dur[covers])]
        else:
            i = int(np.argmax(ov))
        return self.names[int(i)]


LABELLED_GAPS = 200     # the longest gaps are named; the rest are summed
SHORT_GAPS = "gaps shorter than the named ones"


def reduce(rows: Sequence[Row], lo: float, hi: float, top: int = 10
           ) -> Dict:
    """Device numbers of the traced window [lo, hi) ns: busy seconds
    (averaged over the device planes), the programs of the first device,
    the operations that took most time (loops' bodies, not the loops),
    and the idle gaps summed by what the host was doing."""
    planes = device_planes(rows)
    window_s = (hi - lo) * 1e-9
    if not planes:
        return {"busy_s": 0.0, "window_s": window_s, "planes": 0,
                "programs": {}, "device_ops": [], "idle_gaps": []}
    busy = sum(busy_ns(rows, p, lo, hi) for p in planes) / len(planes)
    by_op: Dict[str, float] = defaultdict(float)
    for r in leaf_ops(ops(rows, planes[0], lo, hi)):
        by_op[r[2]] += r[4] * 1e-9
    dev = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(rows, planes[0], lo, hi),
                  key=lambda g: g[0] - g[1])
    host = HostIndex(rows)
    by_host: Dict[str, float] = defaultdict(float)
    for i, (s, e) in enumerate(gaps):
        name = host.label(s, e) if i < LABELLED_GAPS else SHORT_GAPS
        by_host[name] += (e - s) * 1e-9
    named = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy * 1e-9, "window_s": window_s,
            "planes": len(planes),
            "programs": programs(rows, planes[0], lo, hi),
            "device_ops": [[k, v] for k, v in dev],
            "idle_gaps": [[k, v] for k, v in named]}
