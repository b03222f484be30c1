"""Latent (MLA) decode attention against its roofline: the least time the
whole traced decode steps' attention can take over their own contexts
(opcount_mla.mla_decode_least_seconds: the larger of the latent bytes at the
HBM peak and the absorbed form's FLOPs at the bf16 peak; the algorithm's 576
values a token, not a padded page) over the Mosaic decode kernel's device
time in those same steps (program_spans.steps_with_whole_runs). Nothing to
read where the program has no such kernel."""
import opcount_mla
import program_spans
import trace_reduce


def read(ctx):
    cfg = ctx["config"]
    if "kv_lora_rank" not in cfg:
        return None
    steps, table = program_spans.steps_with_whole_runs(
        ctx, lambda name: "decode" in name)
    kernel_s = trace_reduce.op_seconds(table, trace_reduce.is_kernel)
    if not kernel_s:
        return None
    page_itemsize = 2                     # bf16 pages, as the config states
    least_s = opcount_mla.mla_decode_least_seconds(
        cfg, [s[3] for s in steps], page_itemsize, ctx["peaks"])
    return 100.0 * least_s / kernel_s
