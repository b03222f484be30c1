"""Median host time of one `TrainStep.__call__`: the program's `train.step`
spans (staging the inputs and dispatching the compiled step; the device runs
behind it). Also prints the traced run's consistency line."""
import program_spans


def read(ctx):
    program_spans.report(ctx, "train.step")
    return program_spans.median_ms(ctx, "train.step", ("train.step",))
