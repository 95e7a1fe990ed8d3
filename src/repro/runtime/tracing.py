"""Host spans of the serving engine, on the profiler's clock.

The tracing half of the telemetry subsystem (metrics live in
``serving.telemetry``; the Chrome-trace/Perfetto JSON exporter in
``runtime.trace_export``).

:class:`span` is the one span primitive.  It always enters a
``jax.profiler.TraceAnnotation`` named ``<cat>.<name>`` (``engine.step``,
``kv.code_pages``, ``swap.fault``), so a ``jax.profiler`` trace holds the
engine's phases on the same clock as the device ops and an idle gap of
the device reads as the phase the host was in.  With no profiler session
running an annotation costs about a microsecond, so the spans are always
on; they are kept at step, phase and page granularity, never per token
or per slot.  Arguments given on entry read back as the event's stats
(``jax.profiler.ProfileData``); counts known only at the end go on a
zero-length ``<cat>.<name>.done`` child (:meth:`span.done`).

When a :class:`SpanTracer` is attached (``Telemetry(trace=True)``), each
span is also appended to its buffer as ``(cat, name)``, which feeds the
Chrome-trace export (``launch/serve.py --trace-out``), together with the
per-request **state spans** of :class:`RequestStateTracker` (queued ->
prefilling -> decoding -> preempted -> finished) and per-step counter
samples (queue depth, pages in use).

The buffer is **bounded**: capped at ``capacity``; overflow bumps
``n_dropped`` instead of growing.  An event is one small tuple append,
``(ph, cat, name, track, ts, dur, args)`` with ``ph`` one of ``"X"``
(complete span), ``"I"`` (instant), ``"C"`` (counter sample; ``args`` is
the numeric value); timestamps are raw ``perf_counter`` floats.
``track`` is ``"engine"`` (engine-phase rows) or ``"req:<id>"`` (one row
per request); ``runtime.trace_export`` maps tracks to Chrome-trace
pid/tid pairs.
"""
from __future__ import annotations

import time
import warnings

import jax

ENGINE_TRACK = "engine"


def request_track(rid) -> str:
    return f"req:{rid}"


class SpanTracer:
    """Bounded host-side event buffer (see module docstring)."""

    def __init__(self, capacity: int = 200_000, clock=time.perf_counter):
        self._clock = clock
        self.capacity = capacity
        self.events: list[tuple] = []
        self.n_dropped = 0
        self.t0 = clock()

    def __len__(self) -> int:
        return len(self.events)

    def now(self) -> float:
        return self._clock()

    def _push(self, ev: tuple) -> None:
        if len(self.events) >= self.capacity:
            self.n_dropped += 1
            return
        self.events.append(ev)

    def complete(self, cat: str, name: str, track: str, t_start: float,
                 t_end: float | None = None, args: dict | None = None):
        """Record a finished span [t_start, t_end] (end defaults to now)."""
        if t_end is None:
            t_end = self._clock()
        self._push(("X", cat, name, track, t_start,
                    max(t_end - t_start, 0.0), args))

    def instant(self, cat: str, name: str, track: str = ENGINE_TRACK,
                args: dict | None = None):
        self._push(("I", cat, name, track, self._clock(), 0.0, args))

    def counter(self, name: str, value: float,
                track: str = ENGINE_TRACK):
        """One sample of a counter track (queue depth, pages in use)."""
        self._push(("C", "metric", name, track, self._clock(), 0.0,
                    float(value)))

    def span(self, cat: str, name: str, track: str = ENGINE_TRACK,
             args: dict | None = None) -> "span":
        return span(f"{cat}.{name}", self, track, **(args or {}))


class span:
    """``with span("engine.decode_step", tracer, rows=16): ...`` — one
    host span (module docstring).  ``tracer`` may be None (profiler
    only).  The span closes when its body raises too, so aborted work
    (``swap.fault`` on ``OutOfPages``) still shows.  ``seconds`` holds
    the span's length once it has closed."""

    __slots__ = ("name", "tracer", "track", "args", "t0", "seconds",
                 "_ann")

    def __init__(self, name: str, tracer: SpanTracer | None = None,
                 track: str = ENGINE_TRACK, **args):
        self.name, self.tracer, self.track, self.args = (name, tracer,
                                                         track, args)

    def _now(self) -> float:
        return (time.perf_counter() if self.tracer is None
                else self.tracer.now())

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self.t0 = self._now()
        return self

    def done(self, **args) -> None:
        """Counts known only at the end: a zero-length
        ``<name>.done`` child in the profiler, the span's own args in
        the buffer."""
        with jax.profiler.TraceAnnotation(self.name + ".done", **args):
            pass
        self.args.update(args)

    def __exit__(self, *exc) -> bool:
        t1 = self._now()
        self.seconds = t1 - self.t0
        if self.tracer is not None:
            cat, _, name = self.name.partition(".")
            self.tracer.complete(cat, name, self.track, self.t0, t1,
                                 self.args or None)
        self._ann.__exit__(*exc)
        return False


class RequestStateTracker:
    """Per-request lifecycle state machine -> non-overlapping state spans.

    Each request's track carries back-to-back spans named after its
    scheduler state (``queued`` / ``prefilling`` / ``decoding`` /
    ``preempted``): :meth:`transition` closes the open state span and
    opens the next, :meth:`finish` closes the last one and stamps an
    instant ``finished`` marker.  Invariants the telemetry tests pin:
    every submitted request's spans close by the time the engine drains
    (``open_states`` is empty), and spans on one track never overlap
    (they share single open-state bookkeeping by construction)."""

    CAT = "request"

    def __init__(self, tracer: SpanTracer):
        self.tracer = tracer
        self._open: dict = {}       # rid -> (state, t_since, args)

    def transition(self, rid, state: str, args: dict | None = None):
        now = self.tracer.now()
        prev = self._open.get(rid)
        if prev is not None:
            pstate, pt, pargs = prev
            self.tracer.complete(self.CAT, pstate, request_track(rid),
                                 pt, now, pargs)
        self._open[rid] = (state, now, args)

    def finish(self, rid, args: dict | None = None):
        prev = self._open.pop(rid, None)
        if prev is not None:
            pstate, pt, pargs = prev
            self.tracer.complete(self.CAT, pstate, request_track(rid),
                                 pt, args=pargs)
        self.tracer.instant(self.CAT, "finished", request_track(rid), args)

    @property
    def open_states(self) -> dict:
        """rid -> current state name (empty once the engine drains)."""
        return {rid: st for rid, (st, _, _) in self._open.items()}


class JaxProfilerHook:
    """Opt-in ``jax.profiler`` capture over an engine-step range.

    Drives ``jax.profiler.start_trace``/``stop_trace`` over those steps
    (``launch/serve.py --jax-profile DIR --profile-steps A:B``): the
    profile holds the device's ops and the engine's spans on one clock.
    Failures to start/stop are downgraded to warnings — profiling must
    never take down a serving run."""

    def __init__(self, logdir: str, start_step: int = 0,
                 stop_step: int | None = None):
        self.logdir = logdir
        self.start_step = start_step
        # default: a one-step capture window
        self.stop_step = (start_step + 1 if stop_step is None
                          else stop_step)
        self.active = False
        self.done = False

    def on_step(self, step: int) -> None:
        if not self.done and not self.active and step >= self.start_step:
            try:
                jax.profiler.start_trace(self.logdir)
                self.active = True
            except Exception as e:                  # pragma: no cover
                warnings.warn(f"jax.profiler start failed: {e}")
                self.done = True
        elif self.active and step >= self.stop_step:
            self.close()

    def close(self) -> None:
        if self.active:
            try:
                jax.profiler.stop_trace()
            except Exception as e:                  # pragma: no cover
                warnings.warn(f"jax.profiler stop failed: {e}")
            self.active = False
        self.done = True
