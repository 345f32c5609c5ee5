"""Device: share of the traced stretch in which no operation ran on the
chip (1 - union of the device's op intervals / stretch)."""


def read(ctx):
    t = ctx["trace"]
    if not t["planes"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
