"""``correct`` end to end on the CPU at test widths: a sound run passes,
also of a layer that only its reference file declares; the run fails
with a token altered where it is produced, with a decode step that
returns its state unchanged and with a weight decode that errs in one
bit, and the control (the reference in fp8) is judged not correct.  A
traced run reports the program's span readings."""
import os
import sys
import time
from pathlib import Path

import pytest

# bench/run.py's import paths: the program's src/ and bench/ itself
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import checkpoint, harness, spec

DATA = Path(os.path.dirname(__file__)) / "data"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(autouse=True)
def _fresh_caches(tmp_path, monkeypatch):
    from repro.serving import engine as E
    monkeypatch.setattr(checkpoint, "CACHE_DIR", tmp_path / "ckpt")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(E, "_STEP_CACHE", {})
    monkeypatch.setattr(E, "_CHUNK_CACHE", {})


def _run(seed=11, trace=False, control=False, workload="tiny.mix"):
    import jax
    bm = spec.load_benchmark(DATA)
    return harness.run(bm, workload, seed=seed, seconds=1.0, trace=trace,
                       t_start=time.perf_counter(), device=jax.devices()[0],
                       peaks=PEAKS, root=DATA, bench=DATA, control=control)


def test_sound_run_is_correct_and_reports_its_metrics():
    r = _run(control=True)
    assert r["correct"], r["check"]
    gap = r["check"]["logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]
    assert r["check"]["weight_bit_mismatches"] == {"value": 0, "limit": 0}
    assert list(r)[-1] == "check"
    m = r["metrics"]
    assert {"setup_s", "out_tok_s", "itl_p95_s", "hbm_peak_gb"} <= set(m)
    assert m["out_tok_s"]["value"] > 0
    # the control, on the same served sequences, is not correct
    assert r["control"]["correct"] is False, r["control"]
    assert r["control"]["logit_gap"] > gap["limit"]


def test_a_layer_bench_lib_does_not_know_is_correct():
    """A squared-ReLU MLP (``mlp.wi``, ``mlp.wo``), declared only in the
    fixture's reference file, runs through the harness and is correct;
    its control is not."""
    r = _run(seed=12, control=True, workload="tiny-sqrelu.mix")
    assert r["correct"], r["check"]
    assert r["check"]["weight_bit_mismatches"] == {"value": 0, "limit": 0}
    assert r["metrics"]["out_tok_s"]["value"] > 0
    assert r["control"]["correct"] is False, r["control"]


def test_traced_run_reads_the_program_spans():
    r = _run(seed=13, trace=True)
    assert r["correct"], r["check"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for name in ("engine.step_ms.decode", "engine.device_wait_ms.decode",
                 "engine.host_ms.decode", "kv.code_ms.decode"):
        assert m[name] >= 0, name
    assert 0 < m["engine.step_ms.decode"]


def test_altered_token_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from repro.serving import engine as E
    monkeypatch.setattr(
        E, "greedy",
        lambda logits: (jnp.argmax(logits, -1) + 1) % logits.shape[-1])
    r = _run()
    assert not r["correct"], r["check"]


def test_unchanged_decode_state_is_not_correct(monkeypatch):
    from repro.models import model as M
    step = M.decode_step

    def stale(params, cfg, token, cache, mesh=None):
        logits, _ = step(params, cfg, token, cache, mesh=mesh)
        return logits, cache

    monkeypatch.setattr(M, "decode_step", stale)
    r = _run()
    assert not r["correct"], r["check"]


def test_lossy_weight_decode_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from repro.core import tpu_format
    decode = tpu_format._decode_jnp_impl

    def lossy(*args, **kw):     # the last mantissa bit of one element
        bits = decode(*args, **kw)
        return bits.at[0].set(bits[0] ^ jnp.asarray(1, bits.dtype))

    monkeypatch.setattr(tpu_format, "_decode_jnp_impl", lossy)
    r = _run()
    assert not r["correct"], r["check"]
    assert r["check"]["weight_bit_mismatches"]["value"] > 0
