"""Kernels: least time of the `swiglu_qgemv` calls of the traced decode
steps (packed gate/up weights and scales streamed once a call, rows =
`max_batch`) over the kernel's device time."""
import trace_reduce as T
from work import swiglu_qgemv as ffn


def read(ctx):
    name = "swiglu_qgemv"
    prog = T.most_run(ctx["trace"]["programs"], "paged_flash_attention")
    if prog is None:
        return None
    k = T.kernel(prog["ops"], name)
    if k["seconds"] <= 0:
        return None
    w = ffn.per_call(ctx["dims"], ctx["engine"]["max_batch"])
    pk = ctx["peaks"]
    least = k["count"] * max(w["flops"] / pk["bf16_flops_per_s"],
                             w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / k["seconds"]
