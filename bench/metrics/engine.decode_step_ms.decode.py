"""Median span of the engine.step() calls in the window that produced
tokens (the harness's own span), in ms."""
from lib import readers


def read(ctx):
    return readers.step_ms_median(ctx)
