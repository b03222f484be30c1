"""What Laguna's device-trace readers share. The trace names every Mosaic
call of a program alike (`mosaic:<program>`), so a kernel is told by its
PLACE in a whole decode run: a layer makes ONE ragged attention call, and an
expert layer whose walk runs inside the grouped product makes THREE more
after it (gate, up, down); the loop form makes none. So a run of L layers,
S of them expert layers, holds L Mosaic operations (the loop form) or L + 3 S
(the grouped form), layer by layer, the attention first: a run whose count is
neither is not that program, and the readers read nothing. The grouped
product's calls are the expert layer's own time and are NOT attention's."""

from __future__ import annotations

import hybrid_trace
import opcount_laguna
import trace_reduce

# the runner's programs of one prompt's prefill, by their jitted names
PREFILL_PROGRAMS = ("_prefill_piece", "_piece_head", "_ring_load",
                    "_ring_store")


def is_decode(name: str) -> bool:
    return "decode" in name


def is_prefill(name: str) -> bool:
    return any(p in name for p in PREFILL_PROGRAMS)


def attention_calls(cfg: dict, ops: list):
    """The attention kernel's events of one whole decode run, a layer each,
    or None where the run's Mosaic calls are not this program's."""
    kernels = [e for e in ops if trace_reduce.is_kernel(e[0])]
    sparse = opcount_laguna.sparse_layers(cfg)
    L, S = len(sparse), sum(sparse)
    if len(kernels) == L:
        return kernels
    if len(kernels) != L + 3 * S:
        return None
    out, at = [], 0
    for is_sparse in sparse:
        out.append(kernels[at])
        at += 4 if is_sparse else 1
    return out


def decode_attention(ctx):
    """(records, {kind: seconds}, runs): the step records that hold a whole
    decode run, the device time in those runs of the attention kernel by
    layer kind, and the number of runs. None where the configuration is not
    this family's or a run is not this program's."""
    cfg = ctx["config"]
    if "num_attention_heads_per_layer" not in cfg:
        return None
    kinds = opcount_laguna.layer_kinds(cfg)
    records, ns, n_runs = [], dict.fromkeys(set(kinds), 0), 0
    for rec, runs in hybrid_trace.records_with_runs(ctx, is_decode):
        for _, _, ops in runs:
            calls = attention_calls(cfg, ops)
            if calls is None:
                return None
            for e, kind in zip(calls, kinds):
                ns[kind] += e[2]
            n_runs += 1
        records.append(rec)
    return records, {k: v / 1e9 for k, v in ns.items()}, n_runs
