"""1 - the union of device-op intervals over the traced window (%)."""
from lib import readers


def read(ctx):
    return readers.idle_share(ctx)
