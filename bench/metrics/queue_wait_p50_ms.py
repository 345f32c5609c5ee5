"""Scheduler: median wait from enqueue to admission of the requests the
engine admitted in the window (the `Telemetry` queue samples)."""
import numpy as np


def read(ctx):
    t0, t1 = ctx["telemetry"]["t0"]["t"], ctx["telemetry"]["t1"]["t"]
    waits = [a - e for e, a in ctx["telemetry"]["t1"]["queue"]
             if t0 <= a < t1]
    return 1e3 * float(np.median(waits)) if waits else None
