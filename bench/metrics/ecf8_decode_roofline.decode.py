"""ECF8 weight-decode kernel: least time by bytes over its device time
in the trace (%)."""
from lib import readers


def read(ctx):
    return readers.kernel_roofline(ctx, "ecf8_decode")
