"""The window layers' attention, at decode, against the memory roofline: the
keys and values INSIDE the window that the whole traced decode steps had to
read (min(context, sliding_window) tokens a sequence and layer:
opcount_phi4flash.window_kv_bytes, at each step's mean context) over the HBM
peak, divided by those layers' kernel time in the same steps.

What in the trace is the kernel: the decode program's Mosaic calls in their
order against the layer kinds (phi4_trace.decode_kernel_seconds). Nothing to
read where the configuration is not this family's, or a run's Mosaic calls
are not one a kernel layer."""
import opcount_phi4flash
import phi4_trace


def read(ctx):
    found = phi4_trace.decode_kernel_seconds(ctx, ("window",))
    if not found or not found[1]:
        return None
    records, kernel_s = found
    kv_itemsize = 2                       # bf16 pages, as the config states
    least_s = sum(opcount_phi4flash.window_kv_bytes(
        ctx["config"], r[4], r[3] / r[4], kv_itemsize)
        for r in records if r[4]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
