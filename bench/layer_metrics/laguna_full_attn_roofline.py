"""The full-attention layers' ragged kernel, at decode, against the memory
roofline: the K/V bytes of the whole contexts that the traced decode steps
had to read (context tokens of the window's own requests, the full layers
only: opcount_laguna.full_kv_bytes) over the HBM peak, divided by those
layers' kernel time in the same steps (laguna_trace.decode_attention: the
kernel's calls by their place in the run). Nothing to read where the
configuration is not this family's."""
import laguna_trace
import opcount_laguna


def read(ctx):
    found = laguna_trace.decode_attention(ctx)
    if not found:
        return None
    records, seconds, _ = found
    kernel_s = seconds.get(opcount_laguna.FULL)
    if not kernel_s:
        return None
    kv_itemsize = 2                       # bf16 pages, as the config states
    least_s = opcount_laguna.full_kv_bytes(
        ctx["config"], sum(r[3] for r in records),
        kv_itemsize) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
