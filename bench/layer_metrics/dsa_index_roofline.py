"""The indexer's scan at decode against its roofline: the least time the
whole traced decode steps' scoring can take over their own contexts
(opcount_dsa.index_least_seconds: every live index key's bytes once a layer
at the HBM peak, or its 64 x 128 products at the bf16 peak, whichever is
longer) over the scan kernels' device time in those same steps
(dsa_trace.decode_kernel_seconds: each layer's first Mosaic call). The
selection that follows is plain XLA and is not in this time (dsa_trace's
head). Nothing to read where the configuration has no indexer or a run's
Mosaic calls are not two a layer."""
import dsa_trace
import opcount_dsa


def read(ctx):
    found = dsa_trace.decode_kernel_seconds(ctx, 0)
    if not found or not found[1]:
        return None
    records, kernel_s = found
    page_itemsize = 2                     # bf16 pages, as the config states
    least_s = sum(opcount_dsa.index_least_seconds(
        ctx["config"], [r[3]], page_itemsize, ctx["peaks"]) for r in records)
    return 100.0 * least_s / kernel_s
