"""Collectives' device time per traced training step during which no other
operation runs on the same device (trace_reduce.exposed_seconds over
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute,
averaged over the devices): what the layout's communication adds to a step
that overlap does not hide. Nothing to read on one chip."""
import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    steps = len(trace_reduce.module_ms(tr, lambda n: n == "step"))
    if not steps or not trace_reduce.op_seconds(tr, trace_reduce.is_collective):
        return None
    return 1e3 * trace_reduce.exposed_seconds(
        tr, trace_reduce.is_collective) / steps
