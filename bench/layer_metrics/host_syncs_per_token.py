"""Blocking device->host transfers the engine made per output token, from
EngineMetrics' counters (counted where the drain happens), over the window."""


def read(ctx):
    c = ctx["counters"]
    return c["host_syncs"] / c["tokens_generated"] if c["tokens_generated"] else None
