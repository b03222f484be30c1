"""The expert layer's grouped products (forward, dx and dw of gate, up and
down) against the compute roofline: 3 x opcount_zaya.expert_flops_per_pair
a token-expert pair that the whole traced steps themselves counted
(zaya_trace.pairs_in_whole_steps: the held share of pairs swings from step
to step, so a mean over the run would not do) over the bf16 peak, divided by
the grouped kernels' device time in those same steps
(zaya_trace.kernel_seconds)."""
import opcount_zaya
import zaya_trace


def read(ctx):
    found = zaya_trace.kernel_seconds(ctx)
    if not found or not found[1]:
        return None
    _, grouped_s, n = found
    pairs = zaya_trace.pairs_in_whole_steps(ctx, n)
    if pairs is None:
        return None
    flops = 3.0 * pairs * opcount_zaya.expert_flops_per_pair(ctx["config"])
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / grouped_s
