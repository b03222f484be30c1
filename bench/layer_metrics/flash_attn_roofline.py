"""Flash attention (forward + backward) against the compute roofline:
opcount.attention_flops_train for the traced steps, each device's share,
over the bf16 peak, divided by the Mosaic kernels' summed device time."""
import opcount
import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    kernel_s = trace_reduce.op_seconds(tr, lambda n: n.startswith("mosaic:"))
    steps = len(trace_reduce.module_ms(tr, lambda n: n == "step"))
    if not kernel_s or not steps:
        return None
    flops = steps * opcount.attention_flops_train(
        ctx["config"], ctx["batch"], ctx["seq"]) / ctx["chips"]
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / kernel_s
