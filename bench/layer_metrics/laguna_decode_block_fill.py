"""Token-expert pairs over the rows the expert layer multiplied, in the
window's decode launches: EngineMetrics' `moe_decode_pairs` /
`moe_decode_rows_multiplied` (counted on the device: a layout's whole row
blocks, an expert's group padded to one). At 64 rows a step on 256 experts a
touched expert gets two or three pairs in a block of 16. Nothing to read
where the program keeps no such counter."""


def read(ctx):
    c = ctx["counters"]
    rows = c.get("moe_decode_rows_multiplied")
    return 100.0 * c["moe_decode_pairs"] / rows if rows else None
