"""What Phi-4-mini-flash's device-trace readers share. The trace names every
Mosaic call of a program alike (`mosaic:<program>`); in the decode program
the runner makes ONE Mosaic call in every layer but the gated memory units,
in layer order: the selective scan's update in a "mamba" layer, the ragged
attention kernel in a "window", the "full" and a "cross" layer
(opcount_phi4flash.kernel_layers). So the k-th Mosaic operation of a whole
decode run is that list's k-th, and a run whose count differs is not that
program: the reader reads nothing."""

from __future__ import annotations

import hybrid_trace
import opcount_phi4flash
import trace_reduce


def decode_kernel_seconds(ctx, kinds: tuple):
    """(records, seconds): the step records that hold a whole decode run,
    and the device time in those runs of the Mosaic calls of the layers
    whose kind is in `kinds`. None where the configuration is not this
    family's or a run's Mosaic calls are not one a kernel layer."""
    cfg = ctx["config"]
    if "mb_per_layer" not in cfg or "mamba_d_state" not in cfg:
        return None
    layers = opcount_phi4flash.kernel_layers(cfg)
    records, ns = [], 0
    for rec, runs in hybrid_trace.records_with_runs(
            ctx, lambda name: "decode" in name):
        for _, _, ops in runs:
            kernels = [e for e in ops if trace_reduce.is_kernel(e[0])]
            if len(kernels) != len(layers):
                return None
            ns += sum(e[2] for e, k in zip(kernels, layers) if k in kinds)
        records.append(rec)
    return records, ns / 1e9
