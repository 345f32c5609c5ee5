"""Engine step: mean host time of a driver-loop iteration that ran an
engine step, its device and idle waits left out (the program's
`driver_loop` spans and their descendants in the traced stretch)."""
import program_spans


def read(ctx):
    return program_spans.step_host_ms(ctx["spans"],
                                      ctx["served"]["trace"]["m1"])
