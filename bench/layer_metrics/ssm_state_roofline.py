"""The selective scan's single-token update against the memory roofline: the
state bytes the whole traced decode steps had to move (their live sequences
x Mamba layers, each state read once and written once:
opcount_phi4flash.scan_decode_bytes) over the HBM peak, divided by the
update kernel's device time in those same steps.

What in the trace is the kernel: the decode program's Mosaic calls in their
order against the layer kinds (phi4_trace.decode_kernel_seconds), those of
the "mamba" layers. Nothing to read where the program keeps no
`ssm_decode_seq_steps` counter, or a run's Mosaic calls are not one a kernel
layer."""
import opcount_phi4flash
import phi4_trace


def read(ctx):
    if not ctx["counters"].get("ssm_decode_seq_steps"):
        return None
    found = phi4_trace.decode_kernel_seconds(ctx, ("mamba",))
    if not found or not found[1]:
        return None
    records, kernel_s = found
    cfg = ctx["config"]
    seq_layer_steps = sum(r[4] for r in records) * \
        opcount_phi4flash.layer_kinds(cfg).count("mamba")
    least_s = opcount_phi4flash.scan_decode_bytes(
        cfg, seq_layer_steps) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
