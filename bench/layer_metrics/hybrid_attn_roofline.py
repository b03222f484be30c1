"""Ragged paged attention of the FOUR layers that page, at decode, against
the memory roofline: the K/V bytes the whole traced decode steps had to read
(context tokens of the window's own requests, the paged layers only:
opcount_hybrid.attention_kv_bytes) over the HBM peak, divided by the
attention kernel's device time in those same steps.

What in the trace is the kernel: the decode program's Mosaic calls in their
order against `layer_types` (hybrid_trace.decode_kernel_seconds), those of
the `full_attention` layers. Nothing to read where the configuration has no
`layer_types`, or a run's Mosaic calls are not one a layer."""
import hybrid_trace
import opcount_hybrid


def read(ctx):
    if "layer_types" not in ctx["config"]:
        return None
    found = hybrid_trace.decode_kernel_seconds(ctx, "full_attention")
    if not found or not found[1]:
        return None
    records, kernel_s = found
    kv_itemsize = 2                       # bf16 pages, as the config states
    least_s = opcount_hybrid.attention_kv_bytes(
        ctx["config"], sum(r[3] for r in records),
        kv_itemsize) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
