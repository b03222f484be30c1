"""Share of the positions the chunked gated delta rule computed that were
real prompt tokens, over the window: EngineMetrics' `delta_prefill_tokens` /
`delta_prefill_positions` (counted in each prefill's program from its write
indices: real tokens, and the bucket it was padded to). Nothing to read
where the program keeps no such counters."""


def read(ctx):
    c = ctx["counters"]
    positions = c.get("delta_prefill_positions")
    return 100.0 * c["delta_prefill_tokens"] / positions if positions \
        else None
