"""Device self time of the ops under the scope ``kv.cold_permute`` over
the device's busy time (%)."""
from lib import spans


def read(ctx):
    return spans.scope_share(ctx.trace, ctx.spans, "kv.cold_permute")
