"""Median host time of one engine step committing what was drained: the
self time of the program's `engine.commit` spans (token resolution,
appends, prefix registration, finishing), i.e. less the drains inside them."""
import program_spans


def read(ctx):
    return program_spans.median_ms(ctx, "engine.step", ("engine.commit",),
                                   own=True)
