"""What the sparse-attention configuration's device-trace readers share. The
trace names every Mosaic call of a program alike (`mosaic:<program>`); in
the decode program a layer under a selection makes TWO Mosaic calls, in
layer order: the scan that scores the sequence's index keys, then the
attention (the latent kernel's walk under the selection). So of a whole
decode run's 2 x layers Mosaic operations the even ones are the scans and
the odd ones the attention, and a run whose count differs is not that
program: the reader reads nothing.
The selection itself (`block/dsa/select`) is plain XLA, fusions and loops
the trace names like any other, and no reader here can tell it apart: it
lies in `decode_weights_roofline`'s "rest", and PERF.md section 5 gives its
share from the traced run's breakdown."""

from __future__ import annotations

import hybrid_trace
import trace_reduce


def decode_kernel_seconds(ctx, which: int):
    """(records, seconds): the step records that hold a whole decode run,
    and the device time in those runs of each layer's `which`-th Mosaic
    call (0 the scan, 1 the attention). None where the configuration has
    no indexer or a run's Mosaic calls are not two a layer."""
    cfg = ctx["config"]
    if not cfg.get("index_topk"):
        return None
    per_run = 2 * cfg["num_hidden_layers"]
    records, ns = [], 0
    for rec, runs in hybrid_trace.records_with_runs(
            ctx, lambda name: "decode" in name):
        for _, _, ops in runs:
            kernels = [e for e in ops if trace_reduce.is_kernel(e[0])]
            if len(kernels) != per_run:
                return None
            ns += sum(e[2] for e in kernels[which::2])
        records.append(rec)
    return records, ns / 1e9
