"""Share of the rows the grouped products multiplied that held a
token-expert pair: the step's counters `moe_train_pairs` /
`moe_train_rows_padded` (every expert's group is padded to whole row
blocks)."""
import zaya_trace


def read(ctx):
    c = zaya_trace.counters(ctx)
    rows = c.get("moe_train_rows_padded")
    return 100.0 * c["moe_train_pairs"] / rows if rows else None
