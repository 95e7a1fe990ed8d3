"""Median ``engine.step`` span of the window's steps with decode rows, on
the profiler's clock (ms)."""
from lib import spans


def read(ctx):
    return spans.step_ms(ctx.spans)
