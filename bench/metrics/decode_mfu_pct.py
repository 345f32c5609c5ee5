"""Model step: the FLOPs that the tokens decoded in the traced stretch
need (`work.decode_step`, live tokens only), over the stretch's length
times the chip's bf16 peak."""
from work import decode_step


def read(ctx):
    ctxs = ctx["decode_contexts"]
    if not ctxs:
        return None
    w = decode_step.work(ctx["dims"], ctxs, 0, 0, ctx["kv_bytes"])
    return 100.0 * w["flops"] / (ctx["trace"]["window_s"]
                                 * ctx["peaks"]["bf16_flops_per_s"])
