"""Engine step: mean device-bound time of a chunked prefill call, from
the program's `prefill_chunk` spans in the traced stretch (each spans
dispatch to `block_until_ready`, the clock of `Telemetry.prefill_s`)."""


def read(ctx):
    d = [s["dur_s"] for s in ctx["spans"] if s["name"] == "prefill_chunk"]
    return 1e3 * sum(d) / len(d) if d else None
