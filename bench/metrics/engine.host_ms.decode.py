"""Median per decode step of ``engine.step`` less its ``engine.sync``:
the host's own work in the step (ms)."""
from lib import spans


def read(ctx):
    return spans.host_ms(ctx.spans)
