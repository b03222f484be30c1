"""Attention over the SELECTED keys at decode against its roofline: the
least time the whole traced decode steps' attention can take
(opcount_dsa.sparse_attn_least_seconds: min(context, index_topk) rows of 576
values a sequence and layer at the HBM peak, or their 128 x 1088 MACs at the
bf16 peak, whichever is longer) over the attention kernels' device time in
those same steps (dsa_trace.decode_kernel_seconds: each layer's second
Mosaic call). A kernel that reads every live row to use a seventh of them
reads low here, by as much. A program that gathered the rows by XLA in
front of a kernel would hide the gather's time from this reader, which sees
kernels only, and read high by that much; the served step gathers nothing
(PERF.md section 5). Nothing to read where the configuration has no indexer
or a run's Mosaic calls are not two a layer.

A step record holds the SUM of its sequences' contexts, so min(context,
index_topk) is taken of the mean context, times the sequences decoding."""
import dsa_trace
import opcount_dsa


def read(ctx):
    found = dsa_trace.decode_kernel_seconds(ctx, 1)
    if not found or not found[1]:
        return None
    records, kernel_s = found
    page_itemsize = 2                     # bf16 pages, as the config states
    least_s = sum(opcount_dsa.sparse_attn_least_seconds(
        ctx["config"], [r[3] / r[4]] * r[4], page_itemsize, ctx["peaks"])
        for r in records if r[4])
    return 100.0 * least_s / kernel_s
