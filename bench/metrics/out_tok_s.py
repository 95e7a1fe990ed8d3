"""Output tokens that arrived in the window over the window's length."""
from lib import readers


def read(ctx):
    return readers.out_tokens(ctx) / (ctx.w1 - ctx.w0)
