"""Share of the latent decode kernel's page groups that it copied as ONE
copy, their pages being consecutive in the pool, over the window:
EngineMetrics' `latent_run_groups` / `latent_copy_groups` (counted in the
decode step's program from its block table, all layers). Nothing to read
where the program keeps no such counters."""


def read(ctx):
    c = ctx["counters"]
    groups = c.get("latent_copy_groups")
    return 100.0 * c["latent_run_groups"] / groups if groups else None
