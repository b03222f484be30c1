"""The attention of the layers that read the ONE whole-context cache (the
full layer and every cross layer), at decode, against the memory roofline:
the bytes the whole traced decode steps had to read (context tokens of the
window's own requests, once a reading layer:
opcount_phi4flash.shared_kv_bytes) over the HBM peak, divided by those
layers' kernel time in the same steps.

What in the trace is the kernel: the decode program's Mosaic calls in their
order against the layer kinds (phi4_trace.decode_kernel_seconds). Nothing to
read where the configuration is not this family's, or a run's Mosaic calls
are not one a kernel layer."""
import opcount_phi4flash
import phi4_trace


def read(ctx):
    found = phi4_trace.decode_kernel_seconds(ctx, ("full", "cross"))
    if not found or not found[1]:
        return None
    records, kernel_s = found
    kv_itemsize = 2                       # bf16 pages, as the config states
    least_s = opcount_phi4flash.shared_kv_bytes(
        ctx["config"], sum(r[3] for r in records),
        kv_itemsize) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
