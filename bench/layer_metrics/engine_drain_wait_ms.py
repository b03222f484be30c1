"""Median time of one engine step in which the host waits for the device:
the program's `engine.drain` spans (the blocking device->host reads). Also
prints the traced run's consistency line."""
import program_spans


def read(ctx):
    program_spans.report(ctx, "engine.step")
    return program_spans.median_ms(ctx, "engine.step", ("engine.drain",))
