"""Serving demo: paged, ECF8-compressed KV cache under mixed-length load.

The paper's deployment story, cache edition.  Weights are entropy-coded
fp8 (decode-on-use in the jitted step); the KV cache is **paged**
(``repro.kvcache``): short requests hold only the pages they wrote, and
pages that fill up go cold and live entropy-coded — the same exponent
concentration the paper measures for weights holds for K/V activations
(Heilper & Singer 2025), so the cold pool is losslessly smaller.

The demo queues a mixed-length request stream through a small batch,
proves the paged+compressed path emits the exact tokens of the
monolithic baseline, and prints raw-vs-compressed cache bytes and
throughput.  On the CPU (``JAX_PLATFORMS=cpu``) the Pallas kernels run in
interpret mode; on a TPU the same script runs them compiled (its
wall-clock figures are host timings either way, not device metrics).
``python chip_smoke.py`` is the full-width run on a chip.

Usage:  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import numpy as np
import jax

from repro.configs import get, smoke_variant
from repro.core.store import compress_tree
from repro.models import model as M
from repro.runtime.monitor import KVCacheMonitor
from repro.serving import EngineConfig, GenerationEngine, Request

MAX_BATCH, MAX_LEN, PAGE = 4, 96, 16


def make_requests(vocab_size: int, seed: int = 0):
    """A mixed-length stream: chatty short prompts next to long ones."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(10):
        plen = int(rng.integers(2, 40))
        new = int(rng.integers(4, 40))
        prompt = rng.integers(0, vocab_size, size=plen).tolist()
        reqs.append(Request(prompt=prompt, max_new_tokens=new))
    return reqs


def run_stream(params, cfg, reqs, **cache_kw):
    mon = KVCacheMonitor()
    eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=MAX_BATCH, max_len=MAX_LEN,
                           kv_monitor=mon, **cache_kw))
    for r in reqs:
        eng.submit(r)
    t0 = time.time()
    eng.run()
    dt = time.time() - t0
    n_tok = sum(len(r.out_tokens) for r in reqs)
    return [r.out_tokens for r in reqs], eng, mon, n_tok, dt


def main():
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    # the paper's weight story rides along: fp8 weights, entropy-coded,
    # decoded on use inside the jitted step (both streams serve them)
    params_c, rep = compress_tree(params, fmt="tpu", min_elems=4096,
                                  out_dtype="float32")
    print(f"== {cfg.name}: ECF8 weights "
          f"{rep['fp8_bytes'] / 1e6:.2f}MB fp8 -> "
          f"{rep['compressed_bytes'] / 1e6:.2f}MB | "
          f"{len(make_requests(cfg.vocab_size))} mixed-length requests, "
          f"batch {MAX_BATCH}, window {MAX_LEN}, page {PAGE}")

    base, _, _, _, _ = run_stream(params_c, cfg,
                                  make_requests(cfg.vocab_size),
                                  cache_mode="monolithic")
    toks, eng, mon, n_tok, dt = run_stream(
        params_c, cfg, make_requests(cfg.vocab_size), cache_mode="paged",
        page_size=PAGE, compress_cold=True)

    lossless = toks == base
    print(f"paged+compressed vs monolithic tokens: "
          f"{'IDENTICAL' if lossless else 'MISMATCH'}")

    s = mon.summary()
    print(f"{n_tok} tokens in {dt:.1f}s ({n_tok / max(dt, 1e-9):.1f} tok/s "
          f"host wall-clock, {eng.steps} decode steps, occupancy "
          f"{n_tok / max(eng.steps, 1):.2f})")
    print(f"cache bytes: monolithic {s['monolithic_bytes'] / 1e6:.3f}MB | "
          f"paged peak {s['peak_paged_bytes'] / 1e6:.3f}MB "
          f"({100 * (1 - s['paged_vs_monolithic']):.1f}% saved) | "
          f"peak pages in use {s['peak_pages_in_use']}")
    print(f"cold pages: raw-equivalent peak "
          f"{s['peak_raw_equiv_bytes'] / 1e6:.3f}MB, entropy-coded at "
          f"{s['cold_compression_ratio']:.3f}x raw bytes")
    if not lossless:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
