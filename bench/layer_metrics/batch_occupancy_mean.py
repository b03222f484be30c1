"""Mean number of sequences in a decode launch, from EngineMetrics'
batch_occupancy histogram (observed where the batch is formed)."""


def read(ctx):
    c = ctx["counters"]
    n = c["batch_occupancy_count"]
    return c["batch_occupancy_sum"] / n if n else None
