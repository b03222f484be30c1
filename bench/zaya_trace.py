"""What the ZAYA1 configuration's readers share: the training step's own
counters, and its Mosaic kernels told apart.

Counters: `paddle_tpu.profiler.step_counters()` (outputs of the step's
program, read from the device when asked). The sums run since the step was
built, the three check steps included: the readers take RATIOS of them.
`moe_train_pairs_by_step` is the pairs of each of the last steps, the newest
last: `pairs_in_whole_steps` sets a traced span's kernels against the pairs
of those same steps. A program without that read (an older commit) gives {}
and every reader returns None.

Kernels: the trace names every Mosaic call of the step's program alike
(`mosaic:step`). In one step each layer makes, forward, one flash attention
call and then three grouped products (gate, up, down), and backward, layers
reversed, six grouped calls (dw and dx of each product, in whatever order
the scheduler gives the two) and then flash attention's two (dQ, dK/dV);
the stream's gradient passes through the expert sublayer's products before
it reaches attention's, which fixes the order of the two KINDS. So of a whole
step's 12 x layers Mosaic calls the k-th's kind is known; a step with
another count is not this program and the readers read nothing.
"""

from __future__ import annotations

import trace_reduce

FORWARD = "FGGG"            # a layer's Mosaic calls, forward
BACKWARD = "GGGGGGFF"       # and backward: F flash attention, G grouped


def counters(ctx) -> dict:
    """The step's counters, read from the device once a run."""
    if "zaya_counters" not in ctx:
        try:
            from paddle_tpu import profiler
        except ImportError:
            profiler = None
        read = getattr(profiler, "step_counters", None)
        ctx["zaya_counters"] = read() if read else {}
    return ctx["zaya_counters"]


def pairs_per_token_layer(ctx):
    c = counters(ctx)
    if not c.get("moe_train_tokens"):
        return None
    return c["moe_train_pairs"] / c["moe_train_tokens"]


def pairs_in_whole_steps(ctx, n: int):
    """The pairs (all layers) of the trace's n whole steps. The profiler
    runs to the window's end and the last run it records is never whole
    (trace_reduce.whole_runs), so they are the n steps before the newest.
    None where the program keeps no such count or not that many steps."""
    by_step = counters(ctx).get("moe_train_pairs_by_step")
    if not by_step or not 0 < n < len(by_step):
        return None
    return sum(by_step[-(n + 1):-1])


def kernel_seconds(ctx):
    """(flash seconds, grouped seconds, whole steps) on the first device,
    or None where there is no device trace, no whole step, or a step's
    Mosaic calls are not 12 a layer."""
    tr = ctx.get("trace")
    if tr is None or not tr.modules:
        return None
    layers = ctx["config"]["num_hidden_layers"]
    kinds = FORWARD * layers + BACKWARD * layers
    runs = trace_reduce.whole_runs(tr, lambda name: name == "step",
                                   min(tr.modules))
    ns = {"F": 0, "G": 0}
    for _, _, ops in runs:
        kernels = [e for e in ops if trace_reduce.is_kernel(e[0])]
        if len(kernels) != len(kinds):
            return None
        for e, kind in zip(kernels, kinds):
            ns[kind] += e[2]
    if not runs:
        return None
    return ns["F"] / 1e9, ns["G"] / 1e9, len(runs)
