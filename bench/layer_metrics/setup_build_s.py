"""Seconds of set-up building the program's objects: `model.build`,
`model.set_state_dict`, and `engine.build` (runner, casts, KV pool) or
`train.init` (copies, optimizer state, sharding), ended before the window's
first step; none counted inside another."""
import program_spans


def read(ctx):
    return program_spans.of(ctx).before_window_s(program_spans.BUILD)
