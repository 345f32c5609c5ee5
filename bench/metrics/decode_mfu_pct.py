"""Model step: the FLOPs that the tokens decoded in the traced stretch
need (the family's `decode_work`, live tokens only), over the stretch's
length times the chip's bf16 peak."""


def read(ctx):
    ctxs = ctx["decode_contexts"]
    if not ctxs:
        return None
    w = ctx["family"].decode_work(ctx["dims"], ctxs, 0, 0, ctx["kv_bytes"])
    return 100.0 * w["flops"] / (ctx["trace"]["window_s"]
                                 * ctx["peaks"]["bf16_flops_per_s"])
