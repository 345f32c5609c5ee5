"""Driver: mean wait of a request-carrying job in the engine driver's
inbox, from `EngineDriver.call` to the job's start (the program's
`driver_inbox` spans in the traced stretch)."""
import program_spans


def read(ctx):
    return program_spans.driver_inbox_ms(ctx["spans"])
