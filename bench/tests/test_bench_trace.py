"""The trace reduction on a synthetic trace."""
import os
import sys

import pytest

# bench/run.py's import paths: the program's src/ and bench/ itself
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import trace
from lib.peaks import UnknownDevice, peaks

MS = 1_000_000


def _trace():
    host = {"python3": [("bench.window", 0, 100 * MS),
                        ("bench.step", 0, 60 * MS),
                        ("compress", 40 * MS, 55 * MS),
                        ("bench.step", 60 * MS, 100 * MS)]}
    ops = [("%while.1 = (s32[]{:T(128)}) while()", 10 * MS, 30 * MS),
           ("%fusion.1 = s32[8]{0} fusion()", 10 * MS, 20 * MS),
           ("%decode_pallas.3 = u8[2]{0} custom-call()", 20 * MS, 30 * MS),
           ("%fusion.2 = s32[8]{0} fusion()", 70 * MS, 80 * MS),
           ("%decode_pallas.3 = u8[2]{0} custom-call()", 110 * MS,
            120 * MS)]
    return {"/device:TPU:0": ops}, host


def test_busy_union_idle_and_gaps():
    devices, host = _trace()
    r = trace.reduce(devices, host)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.03)        # 10-30 and 70-80 ms
    gaps = dict(r["idle_gaps"])
    # 0-10 and 30-40 in the first step, 40-55 in compress, 55-60, 60-70 and
    # 80-100 in the second step
    assert gaps["bench.step"] == pytest.approx(0.055)
    assert gaps["compress"] == pytest.approx(0.015)
    assert sum(gaps.values()) == pytest.approx(0.07)


def test_kernel_time_and_ops_clip_to_the_window():
    devices, host = _trace()
    r = trace.reduce(devices, host)
    assert sum(trace.kernel_events(r["ops"], "%decode_pallas.")) == \
        pytest.approx(0.01)
    # self times: the while loop's body ops are its children
    ops = dict(r["device_ops"])
    assert ops["%decode_pallas.3 = u8[2]"] == pytest.approx(0.01)
    assert ops["%fusion.1 = s32[8]"] == pytest.approx(0.01)
    assert ops.get("%while.1 = (s32[]", 0.0) == pytest.approx(0.0)


def test_missing_window_is_an_error():
    devices, host = _trace()
    host["python3"] = host["python3"][1:]
    with pytest.raises(ValueError):
        trace.reduce(devices, host)


def test_peaks_unknown_device_is_an_error():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(UnknownDevice):
        peaks("TPU v99")


def test_read_finds_the_window_span_in_a_recorded_trace(tmp_path):
    """A profile recorded here (CPU: no device plane) reads back with the
    harness's window span on the host clock."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    devices, host = trace.read(str(tmp_path))
    assert devices == {}
    _, w0, w1 = trace.window(host)
    assert w1 > w0
