"""Model FLOP/s utilisation: opcount.train_flops_per_token (forward +
backward matmuls and causal attention, no recomputation) x tokens/s of this
run, over chips x the bf16 peak."""
import opcount


def read(ctx):
    rate = ctx["e2e"].get("train_tokens_per_s")
    if not rate:
        return None
    flops = opcount.train_flops_per_token(ctx["config"], ctx["seq"])
    return 100.0 * flops * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
