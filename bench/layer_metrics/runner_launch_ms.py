"""Median host time of one engine step inside the runner's entry points:
the program's `runner.launch` spans (byte accounting, staging the host
operands, the jitted call's dispatch)."""
import program_spans


def read(ctx):
    return program_spans.median_ms(ctx, "engine.step", ("runner.launch",))
