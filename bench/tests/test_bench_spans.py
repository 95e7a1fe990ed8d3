"""The program's span and scope readers on a synthetic trace."""
import os
import sys

import pytest

# bench/run.py's import paths: the program's src/ and bench/ itself
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import spans

MS = 1_000_000


def _step(t, total, sync, code, rows=16):
    """One engine step at ``t`` ms: dispatch, sync, commit, coding."""
    d0 = t + 1
    return [("engine.step", t * MS, (t + total) * MS, {"step": t}),
            ("engine.decode_step", d0 * MS, (d0 + 1 + sync) * MS,
             {"rows": rows}),
            ("engine.dispatch", d0 * MS, (d0 + 1) * MS, {}),
            ("engine.sync", (d0 + 1) * MS, (d0 + 1 + sync) * MS, {}),
            ("kv.code_pages", (t + total - code) * MS, (t + total) * MS,
             {"slots": 16}),
            ("kv.code_pages.done", (t + total) * MS, (t + total) * MS,
             {"pages": 3, "raw": 0, "bytes": 900})]


def _spans():
    evs = (_step(0, 50, 30, 5)            # before the window: left out
           + _step(100, 40, 30, 4)
           + _step(200, 60, 40, 10)
           + _step(300, 50, 35, 6)
           + [("engine.step", 400 * MS, 402 * MS, {}),   # no decode rows
              ("engine.admit", 400 * MS, 401 * MS, {"waiting": 0})]
           + _step(500, 45, 30, 5))        # past the window: left out
    return {"spans": evs, "window": (90 * MS, 450 * MS), "scopes": {}}


def test_steps_clip_to_the_window_and_nest():
    st = spans.steps(_spans())
    assert [s // MS for s, _, _ in st] == [100, 200, 300, 400]
    names = [n for n, _, _, _ in st[0][2]]
    assert names == ["engine.decode_step", "engine.dispatch", "engine.sync",
                     "kv.code_pages", "kv.code_pages.done"]
    assert st[3][2] == [("engine.admit", 400 * MS, 401 * MS,
                         {"waiting": 0})]


def test_step_wait_and_host_are_medians_over_decode_steps():
    sp = _spans()
    assert spans.step_ms(sp) == pytest.approx(50.0)       # 40, 50, 60
    assert spans.device_wait_ms(sp) == pytest.approx(35.0)  # 30, 35, 40
    assert spans.host_ms(sp) == pytest.approx(15.0)       # 10, 15, 20


def test_code_ms_is_per_window_step():
    # 4 + 10 + 6 ms of coding over the window's 4 steps
    assert spans.code_ms(_spans()) == pytest.approx(5.0)


def test_readers_find_nothing_in_a_trace_without_spans():
    empty = {"spans": [], "window": (0, 100 * MS), "scopes": {}}
    for reader in (spans.step_ms, spans.device_wait_ms, spans.host_ms,
                   spans.code_ms):
        assert reader(empty) is None
        assert reader(None) is None


def _ops():
    """A while loop whose body holds a scoped fusion, a scoped gather and
    an unscoped op (self time counts each once)."""
    return [("%while.1 = (s32[]) while()", 0, 40 * MS),
            ("%fusion.7 = f32[8] fusion()", 0, 10 * MS),
            ("%fusion.9 = s32[8] fusion()", 10 * MS, 30 * MS),
            ("%decode_pallas.3 = u8[2] custom-call() | "
             "jit(step)/weights.decode/pallas_call", 50 * MS, 60 * MS),
            ("%add.1 = f32[8] add()", 60 * MS, 100 * MS)]


def test_scope_share_uses_self_time_and_the_scope_map():
    trace = {"ops": _ops(), "busy_s": 0.1}
    sp = {"spans": [], "window": None,
          "scopes": {"%fusion.9 = s32[8] fusion()":
                     "jit(step)/kv.cold_permute/gather:",
                     "%while.1 = (s32[]) while()":
                     "jit(step)/kv.cold_permute/while:"}}
    # the while's own 10 ms and its gather's 20 ms, not its 40 ms again
    assert spans.scope_share(trace, sp, "kv.cold_permute") == \
        pytest.approx(30.0)
    # from the op's label alone
    assert spans.scope_share(trace, None, "weights.decode") == \
        pytest.approx(10.0)
    assert spans.scope_share(trace, sp, "kv.cold_decode") is None


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(num, value):
    return _varint(num << 3) + _varint(value)


def _bytes(num, value):
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _xspace():
    """An XSpace with a device plane (a scoped and an unscoped op, one
    scope given by reference) and a host plane that is not read."""
    def entry(key, value):
        return _int(1, key) + _bytes(2, value)

    stat_meta = [entry(1, _int(1, 1) + _bytes(2, "tf_op")),
                 entry(2, _int(1, 2) + _bytes(2, "flops")),
                 entry(3, _int(1, 3) + _bytes(2,
                                              "jit(s)/kv.cold_decode/call:"))]
    ev_meta = [
        entry(10, _int(1, 10) + _bytes(2, "%fusion.301 = s32[33554432]")
              + _bytes(5, _int(1, 2) + _int(3, 7))
              + _bytes(5, _int(1, 1)
                       + _bytes(5, "jit(s)/kv.cold_permute/gather:"))),
        entry(11, _int(1, 11) + _bytes(2, "%dec.19 = s32[2048]")
              + _bytes(5, _int(1, 1) + _int(7, 3))),
        entry(12, _int(1, 12) + _bytes(2, "%copy.1 = f32[8]"))]
    device = (_int(1, 1) + _bytes(2, "/device:TPU:0")
              + _bytes(3, _bytes(2, "XLA Ops"))
              + b"".join(_bytes(4, e) for e in ev_meta)
              + b"".join(_bytes(5, e) for e in stat_meta))
    host = (_int(1, 2) + _bytes(2, "/host:CPU")
            + _bytes(4, entry(10, _bytes(2, "engine.step")))
            + _bytes(5, entry(1, _bytes(2, "tf_op"))))
    return _bytes(1, device) + _bytes(1, host) + _bytes(4, "host0")


def test_op_scopes_read_the_event_metadata():
    assert spans.op_scopes(_xspace()) == {
        "%fusion.301 = s32[33554432]": "jit(s)/kv.cold_permute/gather:",
        "%dec.19 = s32[2048]": "jit(s)/kv.cold_decode/call:"}
    assert spans.op_scopes(b"") == {}


def test_read_a_recorded_trace(tmp_path):
    """Spans recorded here through the program's primitive read back
    with their arguments, nested under the window (CPU: no device
    plane, so no scopes)."""
    import jax
    from repro.runtime.tracing import span
    from lib import trace
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for i in range(2):
            with span("engine.step", step=i):
                with span("engine.decode_step", rows=4):
                    with span("engine.sync"):
                        pass
                with span("kv.code_pages") as sp:
                    sp.done(pages=2, raw=1, bytes=77)
    jax.profiler.stop_trace()
    sp = spans.read(str(tmp_path))
    assert sp["scopes"] == {}
    st = spans.steps(sp)
    assert len(st) == 2
    done = [a for n, _, _, a in st[1][2] if n == "kv.code_pages.done"]
    assert done == [{"pages": 2, "raw": 1, "bytes": 77}]
    assert spans.step_ms(sp) > 0 and spans.code_ms(sp) >= 0
    assert spans.device_wait_ms(sp) + spans.host_ms(sp) == \
        pytest.approx(spans.step_ms(sp))
