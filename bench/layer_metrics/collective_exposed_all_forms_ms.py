"""`collective_exposed_ms` with the compiler's asynchronous forms among the
collectives: per WHOLE traced training step, the device time of all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute AND of the
`async-collective-start` / `async-collective-done` ops that an all-reduce
becomes where the TPU compiler overlaps it (PR 45), during which no other
operation runs on the same device. The products and loop fusions such an
all-reduce runs under (`fusion.N`) are the other operations: a wait inside
`-done`, or a `-start` nothing runs beside, is exposed here and out of
`collective_exposed_ms`'s sight. On a program with no asynchronous
all-reduce the two read the same. Nothing to read on one chip."""
import trace_reduce

ASYNC_FORMS = ("async-collective-start", "async-collective-done")


def is_collective(name):
    return trace_reduce.is_collective(name) or name.startswith(ASYNC_FORMS)


def read(ctx):
    steps, n = trace_reduce.inside_whole_runs(ctx["trace"],
                                              lambda name: name == "step")
    if not n or not trace_reduce.op_seconds(steps, is_collective):
        return None
    return 1e3 * trace_reduce.exposed_seconds(steps, is_collective) / n
