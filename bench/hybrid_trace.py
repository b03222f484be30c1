"""What the hybrid configuration's device-trace readers share: the bench's
own step records paired with the WHOLE runs of a program they hold, run by
run (program_spans.steps_with_whole_runs gives the runs' operations as one
table; these readers need each run's operations in their order).

The trace names every Mosaic call of a program alike (`mosaic:<program>`)
and every fusion `fusion`. In the decode program the hybrid runner makes ONE
Mosaic call a layer, in layer order: the gated delta rule's single-token
update in a `linear_attention` layer, the ragged attention kernel in a
`full_attention` layer. So the k-th Mosaic operation of a whole decode run
is layer k's, and `layer_types` says which kind: a run whose count is not
`num_hidden_layers` is not that program, and the reader reads nothing.
"""

from __future__ import annotations

import opcount_hybrid
import program_spans
import trace_reduce


def records_with_runs(ctx, match) -> list:
    """[(record, [run, ...])]: the bench's step records inside the traced
    span, each with the whole runs (trace_reduce.whole_runs: (t0, dur,
    ops)) of a program `match` accepts whose middle lies inside it; records
    that hold none are left out. Empty without a device trace or
    anchors."""
    tr, span = ctx.get("trace"), ctx.get("trace_span")
    al = program_spans.of(ctx).align()
    if tr is None or not tr.modules or al is None or not span:
        return []
    dev = min(tr.modules)
    inside = [rec for rec in ctx["steps"]
              if rec[0] >= span[0] and rec[1] <= span[1]]
    held = program_spans.runs_held(
        trace_reduce.whole_runs(tr, match, dev),
        [(rec[0] * 1e9 + al[0], rec[1] * 1e9 + al[0]) for rec in inside])
    return [(rec, runs) for rec, runs in zip(inside, held) if runs]


def decode_kernel_seconds(ctx, kind: str):
    """(records, seconds): the step records that hold a whole decode run,
    and the device time in those runs of the Mosaic calls of the layers of
    `kind` ("linear_attention" / "full_attention"). None where a run's
    Mosaic calls are not one a layer."""
    kinds = opcount_hybrid.layer_kinds(ctx["config"])
    records, ns = [], 0
    for rec, runs in records_with_runs(ctx, lambda name: "decode" in name):
        for _, _, ops in runs:
            kernels = [e for e in ops if trace_reduce.is_kernel(e[0])]
            if len(kernels) != len(kinds):
                return None
            ns += sum(e[2] for e, k in zip(kernels, kinds) if k == kind)
        records.append(rec)
    return records, ns / 1e9
