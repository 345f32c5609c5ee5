"""Engine step: mean decode step of the open-loop cells, `Telemetry.decode_s`
over decode-graph calls between the window's two telemetry snapshots."""


def read(ctx):
    a, b = ctx["telemetry"]["t0"], ctx["telemetry"]["t1"]
    steps = b["decode_steps"] - a["decode_steps"]
    return 1e3 * (b["decode_s"] - a["decode_s"]) / steps if steps else None
