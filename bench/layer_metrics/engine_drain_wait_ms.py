"""Median time of one engine step in which the host waits for the device:
the program's `engine.drain` spans (the blocking device->host reads)."""
import program_spans


def read(ctx):
    return program_spans.median_ms(ctx, "engine.step", ("engine.drain",))
