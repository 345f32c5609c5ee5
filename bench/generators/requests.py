"""The benchmark's one request generator: open and closed loops.

A traffic file names this generator and gives its parameters; the
generator turns them and a seed into a schedule.  It imports numpy and
the standard library only, so the load-generating child process never
touches JAX.

Every seed gets the same work in another order.  Lengths are the
distribution's quantiles at evenly spaced probabilities, paired prompt
to output by one fixed shuffle, and Poisson gaps the exponential's; the
seed shuffles the pairs and the gaps and draws the tokens.  So two seeds
differ in which request comes when and in the token ids, never in the
requests' sizes or how many a segment holds.

Open loop ("loop": "open"): arrivals at `rate_rps` in three segments,
`lead_s` before the window (warm traffic, not counted), the window
itself, and `drain_s` after it (arrivals go on while the window's
requests finish).  Each segment holds round(rate * length) requests whose
gaps are scaled to fill it exactly; the first arrives as it opens.

Closed loop ("loop": "closed"): `clients` callers, each sending its next
request when the previous one has finished.  Each client's first request
is cut to a seeded share of its length (a residual life), so the lanes
are at mixed points of their requests from the start.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

REQUESTS_PER_CLIENT = 16        # more than any window finishes


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """n integer lengths at the probabilities (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _gaps(rng, rate: float, n: int, span: float) -> np.ndarray:
    """n exponential gaps (mean 1/rate) in seeded order, scaled to sum to
    `span`."""
    u = (np.arange(n) + 0.5) / n
    g = rng.permutation(-np.log1p(-u) / rate)
    return g * (span / g.sum())


PAIRING_SEED = 0               # fixes which output length meets which prompt


def _requests(rng, traffic: Dict, n: int, vocab: int) -> List[Dict]:
    plen = quantiles(traffic["prompt"], n)
    olen = np.random.default_rng(PAIRING_SEED).permutation(
        quantiles(traffic["output"], n))
    order = rng.permutation(n)
    return [{"prompt": rng.integers(0, vocab, int(plen[i])).tolist(),
             "max_tokens": int(olen[i])} for i in order]


def open_loop(traffic: Dict, seed: int, vocab: int,
              seconds: float) -> List[Dict]:
    rng = np.random.default_rng(seed)
    rate = traffic["rate_rps"]
    out: List[Dict] = []
    start = -traffic["lead_s"]
    for name, span in (("lead", traffic["lead_s"]), ("window", seconds),
                       ("drain", traffic["drain_s"])):
        n = max(1, round(rate * span))
        g = _gaps(rng, rate, n, span)
        t = start + np.cumsum(g) - g
        for due, req in zip(t, _requests(rng, traffic, n, vocab)):
            out.append({**req, "due": float(due), "segment": name})
        start += span
    for i, r in enumerate(out):
        r["id"] = i
    return out


def closed_loop(traffic: Dict, seed: int, vocab: int) -> List[List[Dict]]:
    """One list of requests per client, in sending order.  The lists'
    sizes are fixed; the seed draws the tokens and which client sends
    which list, so every seed offers the same work."""
    rng = np.random.default_rng(seed)
    c = traffic["clients"]
    fixed = np.random.default_rng(PAIRING_SEED)
    n = c * REQUESTS_PER_CLIENT
    plen = fixed.permutation(quantiles(traffic["prompt"], n))
    olen = fixed.permutation(quantiles(traffic["output"], n))
    share = fixed.permutation((np.arange(c) + 0.5) / c)
    per = []
    for i in range(c):
        lst = [{"prompt_len": int(plen[j]), "max_tokens": int(olen[j])}
               for j in range(i, n, c)]
        # a first request cut to a share of its length (a residual
        # life): the lanes are at mixed points of their requests
        lst[0]["max_tokens"] = max(1, math.ceil(share[i]
                                                * lst[0]["max_tokens"]))
        per.append(lst)
    per = [per[i] for i in rng.permutation(c)]
    ident = 0
    for client, lst in enumerate(per):
        for r in lst:
            r["prompt"] = rng.integers(0, vocab, r.pop("prompt_len")).tolist()
            r["client"] = client
            r["id"] = ident
            ident += 1
    return per


def build(traffic: Dict, seed: int, vocab: int, seconds: float):
    if traffic["loop"] == "open":
        return open_loop(traffic, seed, vocab, seconds)
    if traffic["loop"] == "closed":
        return closed_loop(traffic, seed, vocab)
    raise ValueError(f"unknown loop {traffic['loop']!r}")
