"""Median time from the end of a decode-only step's last device event to the
return of its token fetch (`drain.fetch`), with the device's clock set at the
midpoint of the two causal fences (so +/- half `clock_fence_width_us`).
README-idle.md."""
import idle_attribution


def read(ctx):
    return idle_attribution.metric(ctx, "drain_fetch_ms")
