"""Ragged paged attention at decode against the memory roofline: the K/V
bytes the traced decode steps had to read (context tokens of the window's
own requests, opcount.decode_attention_bytes) over the HBM peak, divided by
the Mosaic decode kernel's summed device time. Memory-bound: one query row
per sequence."""
import opcount
import trace_reduce


def read(ctx):
    tr, span = ctx["trace"], ctx["trace_span"]
    kernel_s = trace_reduce.op_seconds(
        tr, lambda n: n.startswith("mosaic:") and "decode" in n)
    if not kernel_s:
        return None
    contexts = [s[3] for s in ctx["steps"]
                if s[0] >= span[0] and s[1] <= span[1] and s[4]]
    kv_itemsize = 2                       # bf16 pages, as the config states
    least_s = opcount.decode_attention_bytes(
        ctx["config"], contexts, kv_itemsize) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
