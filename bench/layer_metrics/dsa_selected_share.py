"""Share of the index keys a window's query rows scored that their
selections kept: EngineMetrics' `dsa_keys_selected` / `dsa_keys_scored`
(counted in each step's program from its rows' contexts, all layers): about
index_topk over the mean context. What a sparse step may skip of a dense
one's reads. Nothing to read where the program keeps no such counters."""


def read(ctx):
    c = ctx["counters"]
    scored = c.get("dsa_keys_scored")
    return 100.0 * c["dsa_keys_selected"] / scored if scored else None
