"""Model FLOP/s utilisation of a sparse training step:
opcount_zaya.train_flops_per_token (projections, convolutions, router,
causal attention in the latent, the experts by the pairs the step COUNTED,
the head; forward + backward, no recomputation) x tokens/s of this run, over
chips x the bf16 peak. The rate is taken as `train_mfu` takes it: the steps
complete before the profiler was asked for."""
import opcount_zaya
import zaya_trace


def read(ctx):
    pairs = zaya_trace.pairs_per_token_layer(ctx)
    asked = ctx.get("trace_requested")
    done = [t1 for _, t1 in ctx["steps"] if asked is None or t1 <= asked]
    if pairs is None or len(done) < 2:
        return None
    rate = (len(done) - 1) * ctx["batch"] * ctx["seq"] / (
        done[-1] - ctx["t_open"])
    flops = opcount_zaya.train_flops_per_token(ctx["config"], ctx["seq"],
                                               pairs)
    return 100.0 * flops * rate / (ctx["chips"]
                                   * ctx["peaks"]["bf16_flops_per_s"])
