"""Kernels: least time of the `paged_flash_attention` calls of the
traced decode steps, for the live KV rows of the tokens they decoded,
over the kernel's device time."""
import trace_reduce as T
from work import paged_flash_attention as attn


def read(ctx):
    name = "paged_flash_attention"
    prog = T.most_run(ctx["trace"]["programs"], name)
    ctxs = ctx["decode_contexts"]
    if prog is None or not ctxs:
        return None
    k = T.kernel(prog["ops"], name)
    if k["seconds"] <= 0:
        return None
    w = attn.total(ctx["dims"], ctxs, ctx["kv_bytes"])
    pk = ctx["peaks"]
    least = max(w["flops"] / pk["bf16_flops_per_s"],
                w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / k["seconds"]
