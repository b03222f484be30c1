"""Median host time of one engine step spent planning and building its
batch: the program's `engine.plan` spans (deadlines, admission, page-in
fence, prefill plan, decode-page reservation) plus `engine.build_batch`
(copy-on-write forks and the numpy token / table / position fill)."""
import program_spans


def read(ctx):
    return program_spans.median_ms(ctx, "engine.step",
                                   ("engine.plan", "engine.build_batch"))
