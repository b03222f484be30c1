"""The chunked gated delta rule against the compute roofline: the rule's own
operations for the real prompt tokens of the traced prefills
(opcount_hybrid.delta_rule_flops_per_token: 6 d_k d_v a token, head and
linear layer) over the bf16 peak, divided by the chunked form's device time
in the whole prefill runs.

What in the trace is the chunked form: the prefill program's `while`
operations (the scan across chunks, one a linear layer, with a chunk's
matmuls inside its body); a run that holds another count of them is not that
program, and the reader reads nothing. The tokens are those of the bench's
own requests whose first token came in the step record that holds the run;
a record whose prefill runs and first tokens differ in number reads
nothing."""
import hybrid_trace
import opcount_hybrid


def read(ctx):
    if "layer_types" not in ctx["config"]:
        return None
    cfg = ctx["config"]
    prompts = {}
    for lv in ctx["lives"]:
        if lv.sched is not None:
            prompts.setdefault(lv.sched, []).append(len(lv.req.prompt))
    tokens, loop_ns = 0, 0
    for rec, runs in hybrid_trace.records_with_runs(
            ctx, lambda name: "prefill" in name):
        mine = prompts.get(rec[0], [])
        if len(mine) != len(runs):
            return None
        for _, _, ops in runs:
            loops = [e for e in ops if e[0] == "while"]
            if len(loops) != opcount_hybrid.linear_layers(cfg):
                return None
            loop_ns += sum(e[2] for e in loops)
        tokens += sum(mine)
    if not loop_ns:
        return None
    least_s = tokens * opcount_hybrid.delta_rule_flops_per_token(cfg) \
        / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / (loop_ns / 1e9)
