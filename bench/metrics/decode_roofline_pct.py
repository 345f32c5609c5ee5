"""Model step: the least time of the traced decode steps (the larger of
their FLOPs over the bf16 peak and their bytes over HBM bandwidth; bytes
are the packed weights as stored, live KV rows and logits) over their
device time; the work is the family's `decode_work`.  The decode step
is the program that holds the paged attention kernel and runs most
often."""
import trace_reduce as T


def read(ctx):
    prog = T.most_run(ctx["trace"]["programs"], "paged_flash_attention")
    ctxs = ctx["decode_contexts"]
    if prog is None or not ctxs or prog["seconds"] <= 0:
        return None
    w = ctx["family"].decode_work(ctx["dims"], ctxs, prog["runs"],
                                  ctx["engine"]["max_batch"],
                                  ctx["kv_bytes"])
    pk = ctx["peaks"]
    least = max(w["flops"] / pk["bf16_flops_per_s"],
                w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / prog["seconds"]
