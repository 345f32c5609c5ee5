"""Scheduler: mean running lanes per engine step in the window, as a
share of `max_batch` (the `Telemetry` batch samples)."""


def read(ctx):
    a, b = ctx["telemetry"]["t0"], ctx["telemetry"]["t1"]
    n = b["steps"] - a["steps"]
    samples = b["batch_samples"][-n:] if n > 0 else []
    if not samples:
        return None
    return 100.0 * sum(samples) / len(samples) / ctx["engine"]["max_batch"]
