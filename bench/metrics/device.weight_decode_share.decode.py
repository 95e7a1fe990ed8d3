"""Device self time of the ops under the scope ``weights.decode`` (the
ECF8 weight decode) over the device's busy time (%)."""
from lib import spans


def read(ctx):
    return spans.scope_share(ctx.trace, ctx.spans, "weights.decode")
