"""Model FLOP/s utilisation: opcount.train_flops_per_token (forward +
backward matmuls and causal attention, no recomputation) x tokens/s of this
run, over chips x the bf16 peak. The rate is the window's own up to the
moment the profiler was asked for (the steps complete by then, over window
open -> the last of them complete), so that neither the host's stall inside
`start_trace` nor anything after it is in it; the whole window's where none
was asked for."""
import opcount


def read(ctx):
    asked = ctx.get("trace_requested")
    done = [t1 for _, t1 in ctx["steps"] if asked is None or t1 <= asked]
    # a record ends when the step BEFORE it is complete: the k-th, k steps
    if len(done) < 2:
        return None
    rate = (len(done) - 1) * ctx["batch"] * ctx["seq"] / (
        done[-1] - ctx["t_open"])
    flops = opcount.train_flops_per_token(ctx["config"], ctx["seq"])
    return 100.0 * flops * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
