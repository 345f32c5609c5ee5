"""End-to-end metrics from the client's records, on the host clock.

Each record is one request as the client saw it (see `client.py`):
`due` (open loop: the intended send time; closed loop: the send time),
`status`, `error`, and the arrival time of every streamed token.

Tails are nearest-rank percentiles over every request due in the
window.  A request that was refused or failed is a miss, which ranks
above any latency; one still waiting when the client stopped counts at
its wait so far, so a stall cannot hide.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

MISS = math.inf


def tail(values: Sequence[float], q: float) -> float:
    """Nearest-rank q-th percentile: the least value with at least q% of
    the samples at or below it."""
    if not values:
        raise ValueError("no samples")
    v = sorted(values)
    return v[max(1, math.ceil(q / 100.0 * len(v))) - 1]


def refused(rec: Dict) -> bool:
    return rec["error"] is not None or rec["status"] not in (None, 200)


def ttft_s(rec: Dict, t_stop: float) -> float:
    if rec["times"]:
        return rec["times"][0] - rec["due"]
    if refused(rec):
        return MISS
    return t_stop - rec["due"]


def window_requests(records: List[Dict]) -> List[Dict]:
    return [r for r in records if r.get("segment") == "window"]


def open_loop(records: List[Dict], t_stop: float) -> Dict:
    """TTFT and inter-token gaps of the requests due in the window."""
    win = window_requests(records)
    ttft = [ttft_s(r, t_stop) for r in win]
    gaps = [b - a for r in win for a, b in zip(r["times"], r["times"][1:])]
    return {"ttft_s": ttft, "itl_s": gaps, "attempted": len(win),
            "failed": sum(1 for r in win if refused(r)),
            "unfinished": sum(1 for r in win if not refused(r)
                              and len(r["times"]) < r["max_tokens"]),
            "misses": sum(1 for t in ttft if t == MISS)}


def tokens_between(records: List[Dict], t0: float, t1: float) -> int:
    return sum(1 for r in records for t in r["times"] if t0 <= t < t1)


def closed_loop(records: List[Dict], t0: float, t1: float) -> Dict:
    """Tokens streamed to clients in [t0, t1), and the requests that were
    active in it."""
    active = [r for r in records
              if (r["sent"] is not None and r["sent"] < t1
                  and (r["done"] is None or r["done"] >= t0))]
    return {"tokens": tokens_between(records, t0, t1),
            "attempted": len(active),
            "failed": sum(1 for r in active if refused(r))}


def generator_lateness_s(records: List[Dict]) -> float:
    """How late the open-loop client sent its latest request."""
    late = [r["sent"] - r["due"] for r in records if r["sent"] is not None]
    return max(late) if late else 0.0
