"""Everything of a decode step but its attention kernel against the weights
it must read: opcount_mla.decode_weight_bytes (all that is dense, plus the
window's `moe_experts_touched` per engine step times one expert's bytes) at
the HBM peak, over the decode program's median busy time less the Mosaic
kernel's time per whole run of it (trace_reduce.inside_whole_runs: kernel
seconds and count of the same runs). Sources: the device trace and the
program's counters. Nothing to read where the program keeps no such
counter."""
import opcount_mla
import trace_reduce


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    touched = c.get("moe_experts_touched")
    is_decode = lambda name: "decode" in name
    runs = trace_reduce.module_ms(tr, is_decode)
    if touched is None or not runs or not ctx["steps"]:
        return None
    inside, n = trace_reduce.inside_whole_runs(tr, is_decode)
    kernel_ms = 1e3 * trace_reduce.op_seconds(inside,
                                              trace_reduce.is_kernel) / n
    rest_ms = ctx["median"](runs) - kernel_ms
    if rest_ms <= 0:
        return None
    weight_itemsize = 2                   # bf16 weights, as the config states
    least_ms = 1e3 * opcount_mla.decode_weight_bytes(
        ctx["config"], weight_itemsize, touched / len(ctx["steps"])
    ) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_ms / rest_ms
