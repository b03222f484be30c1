"""Width of the interval the two causal fences leave for the offset between
the bench's clock and the trace's device plane: the least dispatch latency
plus the least fetch latency over the traced decode-only steps. None where
the interval is empty. README-idle.md."""
import idle_attribution


def read(ctx):
    return idle_attribution.metric(ctx, "clock_fence_width_us")
