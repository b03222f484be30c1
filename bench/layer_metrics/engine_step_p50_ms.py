"""Median duration of `engine.step()` on the bench's clock; each step ends
in the engine's own drain, so it covers the device step plus the host's part."""


def read(ctx):
    steps = ctx["steps"]
    return 1e3 * ctx["median"](t1 - t0 for t0, t1, *_ in steps) if steps else None
