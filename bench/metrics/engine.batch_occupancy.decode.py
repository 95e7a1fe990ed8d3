"""Mean decode slots that produced a token per step in the window, as a
share of max_batch (%)."""
from lib import readers


def read(ctx):
    return readers.batch_occupancy(ctx)
