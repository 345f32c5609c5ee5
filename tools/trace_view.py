#!/usr/bin/env python
"""Offline viewer for Chrome trace-event dumps (`/debug/trace`).

Perfetto answers "show me the timeline"; this answers the two questions
you ask before opening a UI at all:

  * which requests were slowest, and where did their time go
    (queue vs prefill vs decode), and
  * what does a decode step cost per phase across the whole capture.

A span's self time is its duration less its children's (the spans
whose `args.parent` is its `args.id`), so nested phases — a
`decode_step` inside `engine_step` inside `driver_loop` — are not
counted twice: the self times of a capture add up to its traced time.

Usage:
    python tools/trace_view.py trace.json [--top 10]
    curl -s localhost:8151/debug/trace | python tools/trace_view.py -

Works on the exact JSON the gateway serves (or api_bench --trace
saves): request correlation uses the `rid`/`rids` args every span
carries, so a request's engine time is attributed even though its spans
ran on a different thread than its gateway lifecycle.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Dict, List


def load_events(path: str) -> List[Dict]:
    fh = sys.stdin if path == "-" else open(path)
    try:
        doc = json.load(fh)
    finally:
        if fh is not sys.stdin:
            fh.close()
    return doc["traceEvents"] if isinstance(doc, dict) else doc


def _ms(us: float) -> str:
    return f"{us / 1e3:10.3f}ms"


def phase_breakdown(events: List[Dict]) -> Dict[str, Dict[str, float]]:
    """Aggregate complete spans by name: count, total and self time
    (total less the time of the spans nested in them)."""
    spans = [ev for ev in events if ev.get("ph") == "X"]
    nested: Dict[int, float] = defaultdict(float)
    for ev in spans:
        parent = (ev.get("args") or {}).get("parent")
        if parent is not None:
            nested[parent] += ev.get("dur", 0.0)
    agg: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"n": 0, "total_us": 0.0, "self_us": 0.0})
    for ev in spans:
        a = agg[ev["name"]]
        dur = ev.get("dur", 0.0)
        a["n"] += 1
        a["total_us"] += dur
        a["self_us"] += dur - nested.get((ev.get("args") or {}).get("id"),
                                         0.0)
    return agg


def per_request(events: List[Dict]) -> Dict[int, Dict]:
    """Roll spans up per request id.

    The gateway's `request` span gives wall time; engine spans carrying
    this rid in `args.rids` contribute their duration split by name.
    An engine span shared by k requests (one batched decode step) is
    charged to each in full — it is wall time the request spent inside
    that phase, not an exclusive-cost accounting.
    """
    reqs: Dict[int, Dict] = {}

    def entry(rid: int) -> Dict:
        return reqs.setdefault(rid, {"wall_us": None, "status": "?",
                                     "tokens": 0,
                                     "phases": defaultdict(float)})

    for ev in events:
        args = ev.get("args") or {}
        if ev.get("ph") == "X" and ev.get("cat") == "gateway" \
                and ev.get("name") == "request":
            e = entry(args.get("rid", -1))
            e["wall_us"] = ev.get("dur", 0.0)
            e["status"] = args.get("status", "?")
            e["tokens"] = args.get("tokens", 0)
        elif ev.get("ph") == "X" and "rids" in args:
            for rid in args["rids"]:
                entry(rid)["phases"][ev["name"]] += ev.get("dur", 0.0)
    return reqs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome trace JSON path, or - for stdin")
    ap.add_argument("--top", type=int, default=10,
                    help="slowest requests to list (default 10)")
    args = ap.parse_args(argv)

    events = load_events(args.trace)
    if not events:
        print("empty trace")
        return 1

    print(f"{len(events)} events")
    print("\n== per-phase span breakdown ==")
    print(f"{'span':<16}{'count':>8}{'total':>14}{'self':>14}{'mean':>14}")
    agg = phase_breakdown(events)
    for name in sorted(agg, key=lambda n: -agg[n]["self_us"]):
        a = agg[name]
        print(f"{name:<16}{int(a['n']):>8}{_ms(a['total_us']):>14}"
              f"{_ms(a['self_us']):>14}{_ms(a['total_us'] / a['n']):>14}")

    reqs = {rid: r for rid, r in per_request(events).items()
            if r["wall_us"] is not None}
    if reqs:
        print(f"\n== top {args.top} slowest requests "
              f"(of {len(reqs)} with a gateway span) ==")
        print(f"{'rid':>6} {'status':<11}{'tokens':>7}{'wall':>13}"
              f"   phase time")
        by_wall = sorted(reqs.items(), key=lambda kv: -kv[1]["wall_us"])
        for rid, r in by_wall[:args.top]:
            phases = "  ".join(
                f"{n}={p / 1e3:.2f}ms"
                for n, p in sorted(r["phases"].items(),
                                   key=lambda kv: -kv[1]))
            print(f"{rid:>6} {r['status']:<11}{r['tokens']:>7}"
                  f"{_ms(r['wall_us']):>13}   {phases}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
