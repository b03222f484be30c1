"""Median idle time of the device in one decode-only engine step: from the
start of the step's program to the start of the next step's, less the union
of the device's events in between (device trace alone; no clock but the
trace's). README-idle.md; the run's table is printed before the result line."""
import idle_attribution


def read(ctx):
    return idle_attribution.metric(ctx, "device_idle_per_step_ms")
