"""Peak device memory in use (``memory_stats()["peak_bytes_in_use"]``,
fullest chip), read when the window closes, in GB (1e9 bytes)."""


def read(ctx):
    return ctx.hbm_peak_bytes / 1e9
