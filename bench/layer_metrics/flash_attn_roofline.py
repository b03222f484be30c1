"""Flash attention (forward + backward) against the compute roofline:
opcount.attention_flops_train of one step, each device's share, over the
bf16 peak, divided by the Mosaic kernels' device time per WHOLE traced step
(trace_reduce.inside_whole_runs: kernels and count of the same steps; a step
the span cuts is neither)."""
import opcount
import trace_reduce


def read(ctx):
    steps, n = trace_reduce.inside_whole_runs(ctx["trace"],
                                              lambda name: name == "step")
    kernel_s = trace_reduce.op_seconds(steps, trace_reduce.is_kernel)
    if not kernel_s or not n:
        return None
    flops = n * opcount.attention_flops_train(
        ctx["config"], ctx["batch"], ctx["seq"]) / ctx["chips"]
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / kernel_s
