"""95th percentile of every inter-token gap in the window, over all
requests, on the client's clock."""
from lib import readers


def read(ctx):
    return readers.p95(readers.token_gaps(ctx))
