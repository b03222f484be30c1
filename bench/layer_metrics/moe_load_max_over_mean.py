"""How uneven the routing is: a layer-step's fullest expert over its mean
load, over ALL the router's experts, as the ratio of the counters' sums
(`moe_train_load_max` / `moe_train_load_mean`). 1 is perfectly even."""
import zaya_trace


def read(ctx):
    c = zaya_trace.counters(ctx)
    mean = c.get("moe_train_load_mean")
    return c["moe_train_load_max"] / mean if mean else None
