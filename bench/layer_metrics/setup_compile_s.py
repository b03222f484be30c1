"""Seconds of set-up in the first call of each compiled program: every
`runner.compile` / `train.compile` span (trace, lower, compile or the
compile cache's load, and that call's dispatch) ended before the window's
first step."""
import program_spans


def read(ctx):
    return program_spans.of(ctx).before_window_s(program_spans.COMPILE)
