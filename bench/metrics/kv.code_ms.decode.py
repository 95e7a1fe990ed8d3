"""Host time in ``kv.code_pages`` (cold-page coding) per window step
(ms)."""
from lib import spans


def read(ctx):
    return spans.code_ms(ctx.spans)
