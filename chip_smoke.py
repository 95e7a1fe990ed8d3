#!/usr/bin/env python3
"""Chip smoke: the ECF8 serving path on a TPU at qwen3-8b's published widths.

Drives the serving main path once through its own entry points
(``repro.launch.serve``: build weights, build engine, serve a request list)
on the model's full widths — d_model 4096, d_ff 12288, 32/8 heads, the full
151,936-token vocabulary, bf16 activations — with the depth cut to
``--layers`` (default 4; ``--layers 36`` is the whole model).  Weights are
random, drawn on the host from ``--seed``, cast to fp8 and ECF8-encoded
there (the stand-in for loading a checkpoint); all serving runs on the chip.

  Phase A  compressed weights, paged cache with entropy-coded cold pages,
           chunked prefill (256), 8 greedy requests of 64-512 prompt tokens
           and 32 new tokens each.
  Phase B  the same stream on a page pool below its worst case with an
           unbounded host swap tier: requests are preempted, swapped out and
           faulted back through the compiled Pallas page-decode kernel.

Checks (any failure exits non-zero): every compressed leaf decoded on the
chip equals its fp8 cast bit for bit; every parameter and cache leaf lives
on a TPU; Phase A's first-step logits and all its tokens equal an FP8-weight
engine's bit for bit; Phase B preempts, swaps in, and reproduces Phase A's
tokens.

``--chips 4`` runs only the mesh path and its comparison: the Phase A
stream on a data=4 mesh with replicated weights against a single-chip
engine in the same process, tokens bit for bit, and per-device memory.

Run on a TPU host from the repository root:

    python chip_smoke.py [--layers N] [--chips 4]

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU it exits non-zero at once and prints the platform it found.
"""
from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_REQUESTS = 8
PROMPT_LEN = (64, 512)      # inclusive range of the seeded prompt lengths
MAX_NEW = 32
# past this many seconds the run dumps every thread's stack to stderr and
# exits 1, so an overrun says where it was instead of being killed blind
DEADLINE_S = 1140
PROGRESS_EVERY = 16         # engine steps between progress lines


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CheckFailed(Exception):
    pass


def check(name: str, ok: bool, detail: str = "") -> None:
    log(f"check {name}: {'PASS' if ok else 'FAIL'}"
        + (f" ({detail})" if detail else ""))
    if not ok:
        raise CheckFailed(name)


class CompileClock:
    """Sums JAX's compile-phase durations (trace, lower, backend compile)
    reported through ``jax.monitoring`` while it is registered."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def make_prompts(cfg, seed: int) -> list:
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, size=N_REQUESTS)
    return [rng.integers(0, cfg.vocab_size, size=int(n)).tolist()
            for n in lens]


def engine_config(**kw):
    """Phase A's engine options; ``kw`` overrides (Phase B's pool)."""
    from repro.serving import EngineConfig
    base = dict(max_batch=8, max_len=1024, page_size=16, cache_mode="paged",
                compress_cold=True, prefill_chunk=256)
    return EngineConfig(**{**base, **kw})


def serve(params, cfg, config, prompts):
    """Serve ``prompts`` greedily; returns (engine, requests, first-step
    logits on the host, seconds)."""
    import numpy as np
    from repro.launch.serve import serve_requests
    from repro.serving import GenerationEngine
    eng = GenerationEngine(params, cfg, config=config)
    first = []
    t0 = time.perf_counter()

    def on_step(i):
        if i == 0:
            first.append(np.asarray(eng.last_logits))
        if i % PROGRESS_EVERY == PROGRESS_EVERY - 1:
            log(f"  step {i + 1}: {eng.steps} decode steps, {eng.n_chunks} "
                f"prefill chunks, {time.perf_counter() - t0:.2f} s")

    reqs, dt = serve_requests(eng, prompts, MAX_NEW, on_step=on_step)
    if not all(r.done for r in reqs):
        raise CheckFailed("all requests finished")
    return eng, reqs, first[0], dt


def memory(devs, key: str) -> list:
    """``memory_stats()[key]`` of each device."""
    return [d.memory_stats()[key] for d in devs]


def host_peak_bytes() -> int:
    """This process's peak resident host memory."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def leaves_on(tree, platform: str) -> bool:
    import jax
    return all(d.platform == platform
               for x in jax.tree_util.tree_leaves(tree)
               for d in x.devices())


def check_decode(weights) -> None:
    """Every compressed leaf, decoded on the chip by ``materialize``,
    equals the host fp8 cast bit for bit (compared on the chip)."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.core import fp8
    from repro.core.store import CompressedTensor, is_compressed, materialize

    @functools.partial(jax.jit, static_argnames="meta")
    def equal(arrays, ref, meta):
        ct = CompressedTensor(arrays=arrays, meta=meta)
        bits = materialize(ct, dtype=fp8.FP8_DTYPE).view(jnp.uint8)
        return jnp.array_equal(bits, ref.view(jnp.uint8))

    pairs = zip(jax.tree_util.tree_leaves(weights.params,
                                          is_leaf=is_compressed),
                jax.tree_util.tree_leaves(weights.host))
    n = bad = 0
    for ct, ref in pairs:
        if not is_compressed(ct):
            continue
        ref_d = jax.device_put(ref, list(ct.arrays["payload"].devices())[0])
        stacked = ct.arrays["payload"].ndim == 4
        for i in range(ref.shape[0] if stacked else 1):
            arrays = ({k: v[i] for k, v in ct.arrays.items()} if stacked
                      else ct.arrays)
            n += 1
            bad += not bool(equal(arrays, ref_d[i] if stacked else ref_d,
                                  meta=ct.meta))
        del ref_d
    check("ECF8 decode on chip == fp8 cast", bad == 0,
          f"{n - bad}/{n} tensors bit-identical")


def one_chip(cfg, w, dev, seed: int) -> None:
    import numpy as np
    import jax
    from repro.launch.serve import release, tree_bytes
    from repro.runtime.monitor import KVCacheMonitor

    clock = CompileClock()
    w.params = jax.device_put(w.params, dev)
    fp8_b, ecf8_b = w.report["fp8_bytes"], w.report["compressed_bytes"]
    log(f"weights: {w.report['n_compressed']} compressed tensors, fp8 "
        f"{fp8_b} B -> ECF8 {ecf8_b} B ({ecf8_b / fp8_b:.4f} of fp8); "
        f"whole tree on chip {tree_bytes(w.params)} B")
    check("params on TPU", leaves_on(w.params, "tpu"))
    t0 = time.perf_counter()
    check_decode(w)
    log(f"decode check {time.perf_counter() - t0:.2f} s")

    prompts = make_prompts(cfg, seed)
    log(f"stream: {len(prompts)} requests, prompt lengths "
        f"{[len(p) for p in prompts]}, {MAX_NEW} new tokens each")

    # Phase A: compressed weights
    c0 = clock.seconds
    eng, reqs_a, logits_a, dt = serve(w.params, cfg, engine_config(), prompts)
    check("cache on TPU", leaves_on(eng.cache, "tpu"))
    toks_a = [r.out_tokens for r in reqs_a]
    comp = clock.seconds - c0
    log(f"phase A: {sum(map(len, toks_a))} tokens, {eng.steps} steps, "
        f"{dt:.2f} s wall of which compile {comp:.2f} s, serve "
        f"{dt - comp:.2f} s")
    eng = None

    # Phase B: the same stream, oversubscribed, through swap + preemption
    mon = KVCacheMonitor()
    cfg_b = engine_config(n_pages=65, n_cold_slots=32, swap_bytes=-1,
                          kv_monitor=mon)
    c0 = clock.seconds
    eng, reqs_b, _, dt = serve(w.params, cfg, cfg_b, prompts)
    comp = clock.seconds - c0
    s = mon.summary()
    demand = sum(eng.paged.pages_worst_case(len(p), MAX_NEW)
                 for p in prompts)
    log(f"phase B: pool {eng.paged.n_pages} raw pages + {eng.paged.n_cold} "
        f"cold slots vs worst-case demand {demand} pages; "
        f"{s['n_preempted']} preemptions, {s['n_resumed']} resumed, swap "
        f"out {s['swap_out_bytes_total']} B / in {s['swap_in_bytes_total']}"
        f" B; {dt:.2f} s wall of which compile {comp:.2f} s")
    check("phase B preempted and swapped in",
          s["n_preempted"] >= 1 and s["swap_in_bytes_total"] > 0)
    check("tokens phase B == phase A",
          [r.out_tokens for r in reqs_b] == toks_a)
    # the FP8-weight engine starts once the ECF8 buffers are free
    release(eng)
    eng = None

    c0 = clock.seconds
    eng, reqs_f, logits_f, dt = serve(w.host, cfg, engine_config(), prompts)
    comp = clock.seconds - c0
    log(f"fp8 engine: {dt:.2f} s wall of which compile {comp:.2f} s")
    check("first-step logits ECF8 == FP8",
          logits_a.shape == logits_f.shape
          and np.array_equal(logits_a.view(np.uint32),
                             logits_f.view(np.uint32)),
          f"shape {logits_a.shape}")
    check("tokens ECF8 == FP8",
          toks_a == [r.out_tokens for r in reqs_f])
    release(eng)
    log(f"tokens served: {sum(map(len, toks_a))} per run, 3 runs")
    log(f"compile total {clock.seconds:.2f} s")


def four_chips(cfg, w, devs, seed: int) -> None:
    import numpy as np
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devs), ("data",))
    w.params = jax.device_put(w.params, NamedSharding(mesh, P()))
    prompts = make_prompts(cfg, seed)

    eng, reqs_m, _, dt = serve(w.params, cfg, engine_config(mesh=mesh),
                               prompts)
    check("params replicated on the mesh",
          all(x.sharding.is_fully_replicated
              and len(x.devices()) == len(devs)
              for x in jax.tree_util.tree_leaves(eng.params)))
    used, peaks = memory(devs, "bytes_in_use"), memory(devs,
                                                       "peak_bytes_in_use")
    log(f"mesh data={len(devs)}: {dt:.2f} s wall; bytes in use per device "
        f"{used}, peak {peaks}")
    check("no pile-up on device 0", used[0] <= 1.1 * min(used[1:]),
          f"device 0 {used[0]} B vs min other {min(used[1:])} B")

    single = jax.device_put(w.params, devs[0])
    eng1, reqs_1, _, dt = serve(single, cfg, engine_config(), prompts)
    log(f"single chip: {dt:.2f} s wall")
    check("tokens mesh == single chip",
          [r.out_tokens for r in reqs_m] == [r.out_tokens for r in reqs_1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=4,
                    help="depth cut of qwen3-8b (36 = the whole model)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the data=4 mesh path and its "
                         "single-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r} "
              f"({len(devs)} device(s))", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2

    from repro.configs import get
    from repro.launch.serve import build_weights
    from repro.runtime.compile_cache import enable_compile_cache
    log(f"device_kind {devs[0].device_kind}, {len(devs)} device(s); "
        f"compile cache {enable_compile_cache()}")
    cfg = dataclasses.replace(get("qwen3-8b"), n_layers=args.layers)
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab "
        f"{cfg.vocab_size}, dtype {cfg.dtype}")
    w = build_weights(cfg, seed=args.seed, fmt="tpu")
    log(f"setup: synthesis {w.synth_s:.2f} s, encode {w.encode_s:.2f} s "
        f"(host), host peak RSS {host_peak_bytes()} B")
    used = devs[:args.chips]
    try:
        if args.chips == 1:
            one_chip(cfg, w, used[0], args.seed)
        else:
            four_chips(cfg, w, used, args.seed)
    except CheckFailed as e:
        log(f"FAILED: {e}")
        return 1
    for d, peak in zip(used, memory(used, "peak_bytes_in_use")):
        log(f"{d}: peak_bytes_in_use {peak}")
    log(f"host peak RSS {host_peak_bytes()} B")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
