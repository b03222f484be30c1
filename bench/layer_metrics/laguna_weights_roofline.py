"""Everything of a decode step but its attention kernel against the weights
it must read: opcount_laguna.decode_weight_bytes (all that is dense, plus
the window's `moe_decode_experts_touched` per engine step times one expert's
6.29 MB) at the HBM peak, over the decode program's median busy time less
the RAGGED ATTENTION kernel's time per whole run of it. The attention's
calls are told by their place in the run (laguna_trace.attention_calls), not
by being Mosaic calls: where the expert layer's walk runs inside the grouped
product its three Mosaic calls a layer STAY in the denominator, so the share
reads the same work whatever implements it. Sources: the device trace and
the program's counters. Nothing to read where the program keeps no such
counter."""
import laguna_trace
import opcount_laguna
import trace_reduce


def read(ctx):
    touched = ctx["counters"].get("moe_decode_experts_touched")
    found = laguna_trace.decode_attention(ctx)
    if touched is None or not found or not found[2] or not ctx["steps"]:
        return None
    runs = trace_reduce.module_ms(ctx["trace"], laguna_trace.is_decode)
    _, seconds, n_runs = found
    rest_ms = ctx["median"](runs) - 1e3 * sum(seconds.values()) / n_runs
    if rest_ms <= 0:
        return None
    weight_itemsize = 2                   # bf16 weights, as the config states
    least_ms = 1e3 * opcount_laguna.decode_weight_bytes(
        ctx["config"], weight_itemsize, touched / len(ctx["steps"])
    ) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_ms / rest_ms
