"""The sliding layers' ragged kernel, at decode, against the memory roofline:
the keys and values INSIDE the window that the traced decode steps had to
read (min(context, sliding_window) tokens a sequence and layer:
opcount_laguna.window_kv_bytes, at each step's mean context) over the HBM
peak, divided by those layers' kernel time in the same steps
(laguna_trace.decode_attention). Nothing to read where the configuration is
not this family's."""
import laguna_trace
import opcount_laguna


def read(ctx):
    found = laguna_trace.decode_attention(ctx)
    if not found:
        return None
    records, seconds, _ = found
    kernel_s = seconds.get(opcount_laguna.SLIDING)
    if not kernel_s:
        return None
    kv_itemsize = 2                       # bf16 pages, as the config states
    least_s = sum(opcount_laguna.window_kv_bytes(
        ctx["config"], r[4], r[3] / r[4], kv_itemsize)
        for r in records if r[4]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
