"""Latent (MLA) decode attention against its roofline: the least time the
traced decode steps' attention can take over their own contexts
(opcount_mla.mla_decode_least_seconds: the larger of the latent bytes at the
HBM peak and the absorbed form's FLOPs at the bf16 peak; the algorithm's 576
values a token, not a padded page) over the Mosaic decode kernel's summed
device time. Nothing to read where the program has no such kernel."""
import opcount_mla
import trace_reduce


def read(ctx):
    tr, span = ctx["trace"], ctx["trace_span"]
    cfg = ctx["config"]
    if "kv_lora_rank" not in cfg:
        return None
    kernel_s = trace_reduce.op_seconds(
        tr, lambda n: n.startswith("mosaic:") and "decode" in n)
    if not kernel_s:
        return None
    page_itemsize = 2                     # bf16 pages, as the config states
    contexts = [s[3] for s in ctx["steps"]
                if s[0] >= span[0] and s[1] <= span[1] and s[4]]
    least_s = opcount_mla.mla_decode_least_seconds(
        cfg, contexts, page_itemsize, ctx["peaks"])
    return 100.0 * least_s / kernel_s
