"""Attention in the compressed latent (flash attention forward + backward)
against the compute roofline: opcount_zaya.attention_flops_train of the
whole traced steps over the bf16 peak, divided by the flash kernels' device
time in those same steps (zaya_trace.kernel_seconds tells them from the
grouped products by their order in a step)."""
import opcount_zaya
import zaya_trace


def read(ctx):
    found = zaya_trace.kernel_seconds(ctx)
    if not found or not found[0]:
        return None
    flash_s, _, n = found
    flops = n * opcount_zaya.attention_flops_train(
        ctx["config"], ctx["batch"], ctx["seq"])
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / flash_s
