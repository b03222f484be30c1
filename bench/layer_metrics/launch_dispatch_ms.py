"""Median time from the host entering a decode-only step's `runner.dispatch`
to the step's program beginning on the device, with the device's clock set
at the midpoint of the two causal fences (so +/- half `clock_fence_width_us`).
README-idle.md."""
import idle_attribution


def read(ctx):
    return idle_attribution.metric(ctx, "launch_dispatch_ms")
