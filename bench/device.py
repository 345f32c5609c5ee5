"""The chip a run measures: its check, its peaks and its memory.

A run that finds no TPU, fewer chips than its cell asks for, or a chip
that `peaks.json` does not list stops with an error and prints no
result: a number from another device is never written under a device
metric's name.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    pass


def peaks(kind: str) -> Dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def check(chips: int, require_tpu: bool = True) -> List:
    """The devices of this run: the first `chips` of `jax.devices()`."""
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
        peaks(devs[0].device_kind)
    return devs[:chips]


def describe(devs: List) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs: List) -> int:
    """Peak bytes in use on the fullest of `devs`."""
    out = 0
    for d in devs:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out
