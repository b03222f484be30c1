"""Device time of a prompt's prefill per thousand real prompt tokens: the
busy time of the runner's prefill programs (the pieces, the one-row head, the
ring's load and store: laguna_trace.PREFILL_PROGRAMS) in their whole traced
runs, over the prompt tokens of the bench's own requests whose first token
came in the step record that holds the runs. A record whose pieces are not
what its prompts need (one a PIECE_ROWS rows or part of them) is left out;
nothing to read where no record is left or the configuration is not this
family's."""
import hybrid_trace
import laguna_trace
import trace_reduce

PIECE_ROWS = 2048        # the runner's PREFILL_SPAN


def read(ctx):
    if "num_attention_heads_per_layer" not in ctx["config"]:
        return None
    prompts = {}
    for lv in ctx["lives"]:
        if lv.sched is not None:
            prompts.setdefault(lv.sched, []).append(len(lv.req.prompt))
    tokens, busy_ns = 0, 0
    for rec, runs in hybrid_trace.records_with_runs(
            ctx, laguna_trace.is_prefill):
        mine = prompts.get(rec[0], [])
        pieces = sum(1 for _, _, ops in runs if any(
            trace_reduce.is_kernel(e[0]) for e in ops))
        if not mine or pieces != sum(-(-n // PIECE_ROWS) for n in mine):
            continue
        busy_ns += sum(trace_reduce.union_ns(ops) for _, _, ops in runs)
        tokens += sum(mine)
    return busy_ns / 1e6 / (tokens / 1e3) if tokens else None
