"""Collectives' device time per WHOLE traced training step during which no
other operation runs on the same device (trace_reduce.exposed_seconds over
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute
inside the whole runs of the step's program, averaged over the devices, over
the count of those runs): what the layout's communication adds to a step
that overlap does not hide. Nothing to read on one chip."""
import trace_reduce


def read(ctx):
    steps, n = trace_reduce.inside_whole_runs(ctx["trace"],
                                              lambda name: name == "step")
    if not n or not trace_reduce.op_seconds(steps, trace_reduce.is_collective):
        return None
    return 1e3 * trace_reduce.exposed_seconds(
        steps, trace_reduce.is_collective) / n
