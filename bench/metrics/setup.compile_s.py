"""Seconds set-up spent tracing, lowering and compiling (jax.monitoring)."""


def read(ctx):
    return ctx.setup_compile_s
