"""Median device-busy time of one run of the runner's decode program
(`_decode_step`), from the device trace."""
import trace_reduce


def read(ctx):
    ms = trace_reduce.module_ms(ctx["trace"], lambda n: "decode" in n)
    return ctx["median"](ms) if ms else None
