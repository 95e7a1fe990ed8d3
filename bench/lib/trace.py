"""Reduction of a ``jax.profiler`` trace to the numbers the benchmark
reports: device busy time, per-op and per-kernel device time, and the
idle gaps labelled by what the host was doing.

``read`` takes the ``.xplane.pb`` apart (``jax.profiler.ProfileData``);
``reduce`` works on plain ``(name, start_ns, end_ns)`` lists, so it can be
checked on a synthetic trace.
"""
from __future__ import annotations

import glob
import heapq
import os
from collections import defaultdict

from .stats import union_seconds

WINDOW_SPAN = "bench.window"
DEVICE_OP_LINES = ("XLA Ops",)


def read(logdir: str) -> dict:
    """(device op events per device plane, host events per thread), on
    the profiler's one clock, from the newest trace under ``logdir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host = {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = []
            for line in plane.lines:
                if line.name in DEVICE_OP_LINES:
                    ops += [(_label(ev), ev.start_ns, ev.end_ns)
                            for ev in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host[line.name] = [(ev.name, ev.start_ns, ev.end_ns)
                                   for ev in line.events]
    return devices, host


def _label(ev) -> str:
    """An op's name, with the kernel or program name from its stats where
    the trace gives one (a Pallas kernel shows as a custom call)."""
    extra = [str(v) for k, v in ev.stats
             if k in ("long_name", "hlo_op", "tf_op", "kernel_details")
             and v]
    return " | ".join([ev.name] + extra)


def window(host: dict) -> tuple:
    """(thread, start_ns, end_ns) of the harness's window span."""
    for thread, evs in host.items():
        for name, s, e in evs:
            if name == WINDOW_SPAN:
                return thread, s, e
    raise ValueError(f"no {WINDOW_SPAN!r} span in the host trace")


def _clip(evs, w0, w1):
    return [(n, max(s, w0), min(e, w1)) for n, s, e in evs
            if e > w0 and s < w1]


def _timeline(host_evs, w0, w1) -> list:
    """What the host was doing over ``[w0, w1]``: consecutive segments
    ``(start, end, label)``, each labelled by the innermost (shortest)
    host event open over it."""
    evs = sorted((max(s, w0), min(e, w1), n) for n, s, e in host_evs
                 if e > w0 and s < w1 and e > s)
    points = sorted({w0, w1} | {t for s, e, _ in evs for t in (s, e)})
    out, active, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(evs) and evs[i][0] <= a:
            s, e, n = evs[i]
            heapq.heappush(active, (e - s, e, n))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        # a longer event that ended is buried under a shorter open one
        live = [x for x in active if x[1] > a] if active else []
        label = min(live)[2] if live else "(no host span)"
        out.append((a, b, label))
    return out


def _gap_labels(gaps, timeline) -> dict:
    """Seconds of the sorted ``gaps`` [(a, b)] under each timeline label."""
    out, j = defaultdict(float), 0
    for a, b in gaps:
        while j < len(timeline) and timeline[j][1] <= a:
            j += 1
        k = j
        while k < len(timeline) and timeline[k][0] < b:
            s, e, label = timeline[k]
            out[label] += (min(e, b) - max(s, a)) / 1e9
            k += 1
    return out


def _short(label: str) -> str:
    """An op's HLO text cut to its name and result type."""
    return label.split(" | ")[0].split("{")[0].strip()


def _self_times(ops) -> list:
    """(name, seconds) of each op less the ops nested in it (a while
    loop's op spans its body's ops on the same line)."""
    out, stack = [], []          # stack: [name, end, child seconds, dur]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            n, _, child, dur = stack.pop()
            out.append((n, (dur - child) / 1e9))
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0, e - s])
    out += [(n, (dur - child) / 1e9) for n, _, child, dur in stack]
    return out


def reduce(devices: dict, host: dict, top: int = 10) -> dict:
    """Busy and idle time of the traced window, per-op totals and idle
    gaps by host activity.  Times in the result are seconds."""
    thread, w0, w1 = window(host)
    win = (w1 - w0) / 1e9
    busy, per_op, gaps = [], defaultdict(float), defaultdict(float)
    timeline = _timeline(host[thread], w0, w1)
    for ops in devices.values():
        ops = _clip(ops, w0, w1)
        busy.append(union_seconds((s, e) for _, s, e in ops) / 1e9)
        for name, sec in _self_times(ops):
            per_op[_short(name)] += sec / len(devices)
        idle, t = [], w0
        for s, e in sorted((s, e) for _, s, e in ops) + [(w1, w1)]:
            if s > t:
                idle.append((t, s))
            t = max(t, e)
        for label, sec in _gap_labels(idle, timeline).items():
            gaps[label] += sec / len(devices)
    ops_all = [op for evs in devices.values() for op in _clip(evs, w0, w1)]
    return {
        "window_s": win,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "ops": ops_all,
        "device_ops": sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
    }


def kernel_events(ops, pattern: str) -> list:
    """Durations (seconds) of the device ops whose label holds
    ``pattern``."""
    return [(e - s) / 1e9 for name, s, e in ops if pattern in name]
