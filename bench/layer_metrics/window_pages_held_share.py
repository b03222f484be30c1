"""Pages a layer of the pool's WINDOW GROUP held for the rows of the window's
decode launches, over the pages a cache of each row's whole context would
have held for them: EngineMetrics' `window_pages_held` /
`window_pages_whole_context` (summed by the pool as it builds each launch's
block tables). About (sliding_window / page + 1) / (mean context / page).
Nothing to read where the program has no window group."""


def read(ctx):
    c = ctx["counters"]
    whole = c.get("window_pages_whole_context")
    return 100.0 * c["window_pages_held"] / whole if whole else None
