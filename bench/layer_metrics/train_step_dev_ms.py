"""Median device-busy time of one run of TrainStep's program (`jit(step)`),
from the device trace: the union of its operations on the first device."""
import trace_reduce


def read(ctx):
    ms = trace_reduce.module_ms(ctx["trace"], lambda n: n == "step")
    return ctx["median"](ms) if ms else None
