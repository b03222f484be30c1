"""Median host time from the return of a decode-only step's token fetch
(`drain.fetch`) to the next step's dispatch (`runner.dispatch`): the rest of
`engine.commit`, the client's code between two `engine.step()` calls,
`engine.plan`, `engine.build_batch`, `runner.account`, `runner.stage`. The
program's spans alone; in a loop that drains before it launches the device is
idle for all of it. README-idle.md."""
import idle_attribution


def read(ctx):
    return idle_attribution.metric(ctx, "host_turnaround_ms")
