"""Ragged paged attention at decode against the memory roofline: the K/V
bytes the whole traced decode steps had to read (context tokens of the
window's own requests, opcount.decode_attention_bytes) over the HBM peak,
divided by the Mosaic decode kernel's device time in those same steps
(program_spans.steps_with_whole_runs). Memory-bound: one query row per
sequence."""
import opcount
import program_spans
import trace_reduce


def read(ctx):
    steps, table = program_spans.steps_with_whole_runs(
        ctx, lambda name: "decode" in name)
    kernel_s = trace_reduce.op_seconds(table, trace_reduce.is_kernel)
    if not kernel_s:
        return None
    kv_itemsize = 2                       # bf16 pages, as the config states
    least_s = opcount.decode_attention_bytes(
        ctx["config"], [s[3] for s in steps],
        kv_itemsize) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
