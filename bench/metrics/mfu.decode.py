"""Required model FLOPs of the window's work over its length, as a share
of the chip's bf16 peak (%)."""
from lib import readers


def read(ctx):
    return readers.mfu(ctx)
