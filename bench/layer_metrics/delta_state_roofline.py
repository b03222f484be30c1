"""The gated delta rule's single-token update against the memory roofline:
the state and convolution-window bytes the whole traced decode steps had to
move (their live sequences x linear layers, each state read once and written
once: opcount_hybrid.delta_decode_bytes) over the HBM peak, divided by the
update kernel's device time in those same steps.

What in the trace is the kernel: the decode program's Mosaic calls in their
order against `layer_types` (hybrid_trace.decode_kernel_seconds), those of
the `linear_attention` layers. Nothing to read where the program keeps no
`delta_decode_seq_steps` counter, or a run's Mosaic calls are not one a
layer."""
import hybrid_trace
import opcount_hybrid


def read(ctx):
    if not ctx["counters"].get("delta_decode_seq_steps"):
        return None
    found = hybrid_trace.decode_kernel_seconds(ctx, "linear_attention")
    if not found or not found[1]:
        return None
    records, kernel_s = found
    cfg = ctx["config"]
    conv_itemsize = 2                     # bf16 rows, as the config states
    seq_layer_steps = sum(r[4] for r in records) * \
        opcount_hybrid.linear_layers(cfg)
    least_s = opcount_hybrid.delta_decode_bytes(
        cfg, seq_layer_steps, conv_itemsize) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
