"""Seconds importing the program: its `paddle_tpu.import` span, top to
bottom of `paddle_tpu/__init__.py` (JAX itself is imported before, by the
bench's device check)."""
import program_spans


def read(ctx):
    return program_spans.of(ctx).before_window_s(("paddle_tpu.import",))
