"""Median per decode step of ``engine.sync``, the host blocked on the
step's device work (ms)."""
from lib import spans


def read(ctx):
    return spans.device_wait_ms(ctx.spans)
