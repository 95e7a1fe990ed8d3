"""The program's own readings in a ``jax.profiler`` trace: its host spans
(``engine.*``, ``kv.*``, ``swap.*``, from ``repro.runtime.tracing.span``)
with their arguments, and the named scope (``jax.named_scope``) of each
device op.

``read`` takes both from the newest ``.xplane.pb`` under a trace
directory; the readers below work on plain lists, so they can be checked
on a synthetic trace.  A trace of a program without these spans or scopes
reads as empty, and every reader then returns None.

The scope of a device op is the ``tf_op`` stat of its event metadata
(``jit(step)/kv.cold_permute/...``).  ``jax.profiler.ProfileData`` gives
an event's own stats but not its metadata's, so ``op_scopes`` reads the
metadata from the protobuf wire format directly (``XSpace`` of
``tsl/profiler/protobuf/xplane.proto``).
"""
from __future__ import annotations

import glob
import os
import statistics

from .trace import WINDOW_SPAN, _self_times

PREFIXES = ("engine.", "kv.", "swap.")
STEP, DECODE, SYNC, CODE = ("engine.step", "engine.decode_step",
                            "engine.sync", "kv.code_pages")


def _newest(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def read(logdir: str) -> dict:
    """``spans``: the program's host spans on the thread of the harness's
    window span, ``(name, start_ns, end_ns, args)``; ``window``: that
    span's ``(start_ns, end_ns)``; ``scopes``: device op name -> its
    ``tf_op`` scope path."""
    from jax.profiler import ProfileData
    path = _newest(logdir)
    pd = ProfileData.from_file(path)
    out = {"spans": [], "window": None}
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                   for ev in line.events
                   if ev.name == WINDOW_SPAN or ev.name.startswith(PREFIXES)]
            win = [(s, e) for n, s, e, _ in evs if n == WINDOW_SPAN]
            if win:
                out["window"] = win[0]
                out["spans"] = [x for x in evs if x[0] != WINDOW_SPAN]
    with open(path, "rb") as fh:
        out["scopes"] = op_scopes(fh.read())
    return out


# -- host spans ---------------------------------------------------------------
# Each reader takes what ``read`` returns and gives None where it finds
# nothing to read.

def steps(sp) -> list:
    """``(start, end, children)`` of every ``engine.step`` inside the
    window; ``children`` are the spans nested in it."""
    if not sp or sp["window"] is None:
        return []
    w0, w1 = sp["window"]
    evs = sorted((s, -e, n, a) for n, s, e, a in sp["spans"]
                 if s >= w0 and e <= w1)
    out = []
    for s, ne, n, a in evs:
        if n == STEP:
            out.append((s, -ne, []))
        elif out and -ne <= out[-1][1]:
            out[-1][2].append((n, s, -ne, a))
    return out


def _decode_steps(sp) -> list:
    return [st for st in steps(sp) if any(c[0] == DECODE for c in st[2])]


def _median_ms(values):
    return statistics.median(values) / 1e6 if values else None


def _sync_ns(children) -> int:
    return sum(e - s for n, s, e, _ in children if n == SYNC)


def step_ms(sp):
    """Median ``engine.step`` of the window's steps with decode rows (ms)."""
    return _median_ms([e - s for s, e, _ in _decode_steps(sp)])


def device_wait_ms(sp):
    """Median per decode step of ``engine.sync``: the host waiting for
    the device (ms)."""
    return _median_ms([_sync_ns(c) for _, _, c in _decode_steps(sp)])


def host_ms(sp):
    """Median per decode step of ``engine.step`` less its
    ``engine.sync`` (ms)."""
    return _median_ms([e - s - _sync_ns(c) for s, e, c in _decode_steps(sp)])


def code_ms(sp):
    """``kv.code_pages`` time in the window's steps per step (ms)."""
    st = steps(sp)
    if not st:
        return None
    return sum(e - s for _, _, c in st for n, s, e, _ in c
               if n == CODE) / len(st) / 1e6


# -- device scopes ------------------------------------------------------------

def scope_share(trace: dict, sp, scope: str):
    """Device self time of the ops under ``scope`` over the busy time
    (%), from ``trace.reduce``'s ``ops`` and ``busy_s`` of a one-chip
    trace: an op is under ``scope`` where its label or its ``tf_op`` path
    holds it."""
    scopes = sp["scopes"] if sp else {}

    def under(label):
        return scope in label or scope in scopes.get(
            label.split(" | ")[0], "")

    hit = [sec for label, sec in _self_times(trace["ops"]) if under(label)]
    if not hit or trace["busy_s"] <= 0:
        return None
    return 100.0 * sum(hit) / trace["busy_s"]


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i=0, end=None):
    """(field number, value) of one protobuf message: an int for varint
    and fixed fields, a (start, end) slice for length-delimited ones."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} not read")
        yield num, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


# XSpace.planes; XPlane.name, .event_metadata, .stat_metadata; map entry
# key, value; XEventMetadata.name, .stats; XStatMetadata.name;
# XStat.metadata_id, .str_value, .ref_value
_PLANES, _P_NAME, _P_EVMETA, _P_STATMETA = 1, 2, 4, 5
_KEY, _VALUE = 1, 2
_EM_NAME, _EM_STATS = 2, 5
_SM_NAME = 2
_ST_META, _ST_STR, _ST_REF = 1, 5, 7


def op_scopes(data: bytes) -> dict:
    """Device op name -> ``tf_op`` scope path, from the event metadata of
    every ``/device:`` plane of a serialized ``XSpace``."""
    buf = memoryview(data)
    out = {}
    for num, pl in _fields(buf):
        if num != _PLANES:
            continue
        name, evmeta, statmeta = "", [], {}
        for f, v in _fields(buf, *pl):
            if f == _P_NAME:
                name = _text(buf, v)
            elif f == _P_EVMETA:
                evmeta.append(v)
            elif f == _P_STATMETA:
                entry = dict(_fields(buf, *v))
                if _VALUE in entry:
                    sm = dict(_fields(buf, *entry[_VALUE]))
                    if _SM_NAME in sm:
                        statmeta[entry[_KEY]] = _text(buf, sm[_SM_NAME])
        if not name.startswith("/device:"):
            continue
        tf_op = [k for k, v in statmeta.items() if v == "tf_op"]
        if not tf_op:
            continue
        for entry in evmeta:
            em = dict(_fields(buf, *entry))
            if _VALUE not in em:
                continue
            op, scope = None, None
            for f, v in _fields(buf, *em[_VALUE]):
                if f == _EM_NAME:
                    op = _text(buf, v)
                elif f == _EM_STATS:
                    st = dict(_fields(buf, *v))
                    if st.get(_ST_META) == tf_op[0]:
                        scope = (_text(buf, st[_ST_STR]) if _ST_STR in st
                                 else statmeta.get(st.get(_ST_REF), ""))
            if op is not None and scope:
                out[op] = scope
    return out
