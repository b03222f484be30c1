"""Token-expert pairs computed on this chip per held expert that got at
least one token, over the window: EngineMetrics' `moe_local_pairs` /
`moe_experts_touched` (counted on the device inside the expert layer).
How many rows each read of an expert's matrices serves."""


def read(ctx):
    c = ctx["counters"]
    touched = c.get("moe_experts_touched")
    return c["moe_local_pairs"] / touched if touched else None
