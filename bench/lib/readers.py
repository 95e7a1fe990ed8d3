"""What the metric files (``bench/metrics/<name>.py``) compute from a
run's context (``lib/harness.Ctx``).  A reader that finds nothing to read
returns None and the metric is left out of the line."""
from __future__ import annotations

import statistics

from . import flops, trace
from .stats import percentile, rate


def window_steps(ctx) -> list:
    return [s for s in ctx.loop.steps if s.t0 >= ctx.w0]


def token_gaps(ctx) -> list:
    """Every gap between consecutive tokens of a request, both in the
    window."""
    out = []
    for r in ctx.loop.records:
        ts = [t for t in r.times if ctx.w0 < t <= ctx.w1]
        out += [b - a for a, b in zip(ts, ts[1:])]
    return out


def out_tokens(ctx) -> int:
    return sum(1 for r in ctx.loop.records for t in r.times
               if ctx.w0 < t <= ctx.w1)


def p95(samples):
    return percentile(samples, 95) if samples else None


def required_flops(ctx) -> int:
    m = ctx.model
    mm = flops.layer_matmul(ctx.reference, m)
    total = 0
    for s in window_steps(ctx):
        total += sum(flops.tokens(m, mm, p, 1, logits=1)
                     for p in s.decode_tokens)
        total += sum(flops.tokens(m, mm, a, n) for a, n in s.prefill)
        total += s.finished_prompts * flops.unembed(m)
    return total


def mfu(ctx):
    """The window's required model FLOPs over its length, as a share (%)
    of the chip's bf16 peak."""
    steps = window_steps(ctx)
    if not steps:
        return None
    f = required_flops(ctx)
    if f <= 0:
        return None
    return 100.0 * rate(f, ctx.w0, steps[-1].t1) / (
        ctx.peaks["bf16_flops_per_s"] * ctx.chips)


def kernel_roofline(ctx, kernel: str):
    """Least time by bytes (or FLOPs) of the kernel's calls in the traced
    window over the kernel's device time, in %."""
    if ctx.trace is None:
        return None
    mod = ctx.kernel(kernel)
    times = trace.kernel_events(ctx.trace["ops"], mod.PATTERN)
    cost = mod.cost(ctx)
    if not times or cost is None:
        return None
    flops_ev, bytes_ev = cost
    least = max(flops_ev / ctx.peaks["bf16_flops_per_s"],
                bytes_ev / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(times) / sum(times)


def idle_share(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def step_ms_median(ctx):
    d = [1e3 * (s.t1 - s.t0) for s in window_steps(ctx)
         if s.decode_tokens or s.first_tokens]
    return statistics.median(d) if d else None


def batch_occupancy(ctx):
    steps = window_steps(ctx)
    if not steps:
        return None
    return 100.0 * statistics.fmean(len(s.decode_tokens) for s in steps) / (
        ctx.max_batch)
