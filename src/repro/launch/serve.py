"""Batched serving driver: the paper's RQ2 experiment shape.

Synthesizes weights on the host from a seed (standing in for loading an FP8
checkpoint), optionally compresses them to ECF8, puts them on the device and
serves a batch of requests through the continuous-batching engine, printing
the memory footprint of both weight representations and the achieved
tokens/step.  The pieces — :func:`synth_weights`, :func:`build_weights`,
:func:`serve_requests`, :func:`release` — are what ``chip_smoke.py`` drives
at full width on a TPU.

On the CPU (tests, ``JAX_PLATFORMS=cpu``) the Pallas kernels run in
interpret mode and ``--smoke`` configs keep the run small; on a TPU the same
command runs the compiled kernels.  No timing printed here is a device
measurement.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --compress tpu --requests 8

  # sharded serving on a 2-way data mesh (CPU: export
  # XLA_FLAGS=--xla_force_host_platform_device_count=2 first)
  PYTHONPATH=src python -m repro.launch.serve --smoke --mesh 2 \
      --cache paged-compressed --requests 8
"""
from __future__ import annotations

import argparse
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, replace

import numpy as np
import jax
from jax.sharding import Mesh

from repro.configs import get, smoke_variant
from repro.core import fp8
from repro.core.store import compress_tree, weight_leaf
from repro.models import model as M
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.monitor import KVCacheMonitor
from repro.runtime.trace_export import export_chrome_trace
from repro.runtime.tracing import JaxProfilerHook
from repro.serving import EngineConfig, EngineConfigError, \
    GenerationEngine, Request
from repro.serving.telemetry import Telemetry, serving_report_line


def tree_bytes(tree) -> int:
    return sum(
        x.nbytes_compressed() if hasattr(x, "nbytes_compressed")
        else (int(np.prod(x.shape)) * x.dtype.itemsize
              if hasattr(x, "shape") else 0)
        for x in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda t: hasattr(t, "nbytes_compressed")))


# leaves with fewer elements per layer stay uncompressed (norms, biases)
MIN_ELEMS = 4096
# host synthesis block: one generator per block, so the weights depend on
# the seed and the shapes only, not on the thread count
_SYNTH_BLOCK = 1 << 22


def _synth_block(out2d, r0, r1, std, seed):
    g = np.random.default_rng(seed)
    x = g.standard_normal((r1 - r0, out2d.shape[1]), dtype=np.float32)
    x *= np.float32(std)
    out2d[r0:r1] = x.astype(out2d.dtype)


def synth_weights(cfg, seed: int = 0, min_elems: int = MIN_ELEMS) -> dict:
    """Random weights for ``cfg`` on the host, from ``seed``: the stand-in
    for loading an FP8 checkpoint.

    The tree has ``models.model.init_params``' layout (shapes from
    ``jax.eval_shape``; nothing is computed on a device).  Every weight
    matrix (:func:`repro.core.store.weight_leaf`) is drawn normal with
    1/sqrt(fan-in) scale (1/sqrt(d_model) for the embedding) in blocks and
    cast to fp8 at once, so no float32 copy of a stacked leaf is ever
    held; smaller matrices stay float32 and vectors (norm scales, biases)
    are zeros.  Blocks are drawn on a thread pool."""
    shapes = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves, jobs = [], []
    for i, (path, sds) in enumerate(flat):
        is_weight, stacked = weight_leaf(path, sds.shape, min_elems)
        if len(sds.shape) - stacked < 2:
            leaves.append(np.zeros(sds.shape, np.float32))
            continue
        out = np.empty(sds.shape, fp8.FP8_DTYPE if is_weight
                       else np.float32)
        name = str(getattr(path[-1], "key", path[-1]))
        std = (cfg.d_model if name == "embed" else sds.shape[-2]) ** -0.5
        out2d = out.reshape(-1, sds.shape[-1])
        rows = max(1, _SYNTH_BLOCK // sds.shape[-1])
        jobs += [(out2d, r0, min(r0 + rows, out2d.shape[0]), std,
                  (seed, i, r0)) for r0 in range(0, out2d.shape[0], rows)]
        leaves.append(out)
    with ThreadPoolExecutor(8) as ex:
        list(ex.map(lambda j: _synth_block(*j), jobs))
    return jax.tree_util.tree_unflatten(treedef, leaves)


@dataclass
class Weights:
    """Serving weights built by :func:`build_weights` (host arrays)."""

    host: dict      # host fp8 tree: the FP8 checkpoint stand-in/baseline
    params: dict    # the serving tree (ECF8 containers, or ``host``)
    report: dict    # compress_tree's report ({} when not compressed)
    synth_s: float  # host synthesis seconds
    encode_s: float  # host ECF8 encode seconds (0 when not compressed)


def build_weights(cfg, *, seed: int = 0, fmt: str = "tpu") -> Weights:
    """Synthesize on the host and encode (``fmt`` "tpu"/"fixedrate";
    "none" serves the fp8 tree itself): the stand-in for loading a
    checkpoint.  No device is touched; ``jax.device_put`` of ``params``
    (the engine does it) moves the tree with one transfer per leaf, and
    the float32 weights never reach a device."""
    t0 = time.perf_counter()
    host = synth_weights(cfg, seed)
    t1 = time.perf_counter()
    tree, report = host, {}
    if fmt != "none":
        tree, report = compress_tree(host, fmt=fmt, min_elems=MIN_ELEMS,
                                     out_dtype=cfg.dtype)
    t2 = time.perf_counter()
    return Weights(host=host, params=tree, report=report, synth_s=t1 - t0,
                   encode_s=t2 - t1)


def serve_requests(eng: GenerationEngine, prompts, max_new: int,
                   on_step=None) -> tuple[list, float]:
    """Submit one greedy request per prompt and run the engine until all
    finish; returns (finished requests in prompt order, wall seconds)."""
    reqs = [Request(prompt=list(p), max_new_tokens=max_new)
            for p in prompts]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run(on_step=on_step)
    return reqs, time.perf_counter() - t0


def release(eng: GenerationEngine) -> None:
    """Free the engine's device buffers (weights and cache) now, so the
    next engine built in this process finds the memory free."""
    for x in jax.tree_util.tree_leaves((eng.params, eng.cache)):
        if isinstance(x, jax.Array) and not x.is_deleted():
            x.delete()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--compress", default="tpu",
                    choices=["none", "tpu", "fixedrate"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--check-lossless", action="store_true",
                    help="compare logits vs the uncompressed fp8 baseline")
    ap.add_argument("--cache", default="paged",
                    choices=["monolithic", "paged", "paged-compressed"],
                    help="KV-cache layout (paged-compressed entropy-codes "
                         "cold pages in place, decode-on-use in-graph). "
                         "Combines with --mesh: the paged variants shard "
                         "the page pool/table over the mesh batch axes "
                         "(bit-identical to single-device on a pure data "
                         "mesh); monolithic relies on GSPMD cache "
                         "sharding instead.")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--n-pages", type=int, default=None,
                    help="raw page-pool size (default: worst case).  Set "
                         "it below the worst case to oversubscribe the "
                         "pool; with --swap-bytes the engine then swaps/"
                         "preempts instead of failing with OutOfPages.")
    ap.add_argument("--swap-bytes", type=int, default=0,
                    help="host swap-tier capacity in bytes for entropy-"
                         "coded evicted pages (-1 = unbounded, 0 = "
                         "disabled).  Enables serving workloads whose "
                         "aggregate page demand exceeds the device pool, "
                         "bit-identically.")
    ap.add_argument("--preemption", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="allow whole-request preemption (compress + swap "
                         "out a victim, requeue, resume later).  Requires "
                         "--swap-bytes; --no-preemption restores the "
                         "seed's stall-and-raise admission.")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked, decode-interleaved prefill: split each "
                         "prompt into fixed N-token chunks (one prefill "
                         "compilation for every prompt length) and "
                         "interleave them with decode steps.  0 = "
                         "whole-prompt prefill (one compile per prompt "
                         "length).  Needs --cache paged/paged-compressed "
                         "and an all-attention architecture.")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="prompt tokens spent on prefill per engine step "
                         "(bounds decode latency under long prompts); "
                         "default: one chunk.")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="cross-request prefix sharing: requests with a "
                         "common page-aligned prompt prefix reference one "
                         "physical copy of its KV pages (copy-on-write) "
                         "and skip recomputing the matched positions.  "
                         "Needs --prefill-chunk and a single batch shard.")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend the same N-token system prompt to every "
                         "request (chat-style workload; makes "
                         "--prefix-sharing hits visible in the report)")
    ap.add_argument("--draft", default=None, metavar="ARCH",
                    help="speculative decoding: draft-model architecture "
                         "from the registry (e.g. xlstm-350m drafting for "
                         "qwen3-8b; --smoke applies to it too).  The "
                         "draft proposes --spec-k tokens per round and "
                         "the target verifies all k+1 positions in one "
                         "batched forward with exact rejection sampling "
                         "— output is bit-identical to target-only "
                         "decoding under greedy and distribution-"
                         "identical when sampling.  Needs --cache paged/"
                         "paged-compressed, an all-attention target and "
                         "whole-prompt prefill.")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="drafted tokens per speculative round "
                         "(default 4; an error without --draft)")
    ap.add_argument("--draft-seed", type=int, default=None,
                    help="PRNG seed for the synthesized draft weights "
                         "(default 1; an error without --draft)")
    ap.add_argument("--mesh", default=None, metavar="D[xM]",
                    help="serve on a (data=D[, model=M]) device mesh, e.g. "
                         "'2' or '2x2'.  Needs D*M visible devices (on CPU "
                         "set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N).  --max-batch must be divisible by D or "
                         "the engine falls back to the monolithic cache.")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(per-request lifecycle spans + engine-phase "
                         "spans + counter tracks; open in "
                         "ui.perfetto.dev).  See docs/OBSERVABILITY.md.")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    metavar="STEPS",
                    help="print a one-line stats report every N engine "
                         "steps (tokens, queue depth, step/TTFT "
                         "percentiles)")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace into DIR "
                         "over the --profile-steps window")
    ap.add_argument("--profile-steps", default="0:1", metavar="A:B",
                    help="engine-step window for --jax-profile "
                         "(default 0:1)")
    args = ap.parse_args(argv)

    cfg = get(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)

    mesh = None
    if args.mesh:
        try:
            dims = [int(x) for x in args.mesh.lower().split("x")]
        except ValueError:
            dims = []
        if not 1 <= len(dims) <= 2 or any(d < 1 for d in dims):
            raise SystemExit(
                f"--mesh {args.mesh!r}: expected 'D' or 'DxM' with "
                f"positive integers (e.g. '2' or '2x2')")
        n_dev = int(np.prod(dims))
        if n_dev > len(jax.devices()):
            raise SystemExit(
                f"--mesh {args.mesh} needs {n_dev} devices, "
                f"{len(jax.devices())} visible (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n_dev})")
        axes = ("data", "model")[: len(dims)]
        mesh = Mesh(np.asarray(jax.devices()[:n_dev]).reshape(dims), axes)
        print(f"[serve] mesh {dict(zip(axes, dims))}")

    # the one CLI -> engine-config mapping: strict validation here
    # surfaces ignored flags (--spec-k without --draft) and incompatible
    # feature requests (--prefix-sharing with --draft, chunked prefill
    # on a model mesh axis, ...) *before* any weights are synthesized
    dcfg = None
    if args.draft:
        dcfg = smoke_variant(get(args.draft)) if args.smoke \
            else get(args.draft)
    try:
        ecfg = EngineConfig.from_args(args, cfg, mesh=mesh, draft_cfg=dcfg)
    except EngineConfigError as e:
        ap.error(str(e))

    enable_compile_cache()
    w = build_weights(cfg, seed=args.seed, fmt=args.compress)
    if args.compress != "none":
        rep = w.report
        fp8_b = max(rep["fp8_bytes"], 1)
        print(f"[serve] ECF8({args.compress}) encode {w.encode_s:.1f}s: "
              f"{rep['n_compressed']} tensors, fp8 {fp8_b / 1e6:.2f}MB ->"
              f" {rep['compressed_bytes'] / 1e6:.2f}MB "
              f"({100 * (1 - rep['compressed_bytes'] / fp8_b):.1f}% "
              f"saved)")

    rng = np.random.default_rng(args.seed)
    system = rng.integers(1, cfg.vocab_size,
                          size=args.shared_prefix).tolist()
    prompts = [system + rng.integers(0, cfg.vocab_size,
                                     size=rng.integers(4, 12)).tolist()
               for _ in range(args.requests)]

    if args.draft:
        draft_seed = 1 if args.draft_seed is None else args.draft_seed
        dparams = M.init_params(jax.random.PRNGKey(draft_seed), dcfg)
        ecfg = replace(ecfg, draft_params=dparams)
        print(f"[serve] speculative: draft {args.draft} "
              f"({sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(dparams)) / 1e6:.2f}M params), k={ecfg.spec_k}")
    tel = Telemetry(trace=args.trace_out is not None)
    mon = KVCacheMonitor(registry=tel.registry)
    eng = GenerationEngine(w.params, cfg,
                           config=replace(ecfg, telemetry=tel,
                                          kv_monitor=mon))

    profiler = None
    if args.jax_profile:
        try:
            a, b = (int(x) for x in args.profile_steps.split(":"))
        except ValueError:
            raise SystemExit(f"--profile-steps {args.profile_steps!r}: "
                             f"expected 'A:B' (engine-step window)")
        profiler = JaxProfilerHook(args.jax_profile, a, b)

    def on_step(i):
        if profiler is not None:
            profiler.on_step(i)
        if args.metrics_interval and (i + 1) % args.metrics_interval == 0:
            print(f"[serve] step {i + 1}: "
                  f"{serving_report_line(tel.registry)}")

    done, dt = serve_requests(eng, prompts, args.max_new, on_step=on_step)
    if profiler is not None:
        profiler.close()
        print(f"[serve] jax.profiler trace in {args.jax_profile}")
    if args.trace_out:
        trace = export_chrome_trace(tel.tracer, args.trace_out,
                                    registry=tel.registry)
        print(f"[serve] wrote {args.trace_out}: "
              f"{len(trace['traceEvents'])} trace events "
              f"({tel.tracer.n_dropped} dropped) — open in "
              f"ui.perfetto.dev")
    n_tok = sum(len(r.out_tokens) for r in done)
    print(f"[serve] {len(done)} requests, {n_tok} tokens in {dt:.1f}s "
          f"({n_tok / max(dt, 1e-9):.1f} tok/s host wall-clock, "
          f"{eng.steps} decode steps, batch occupancy "
          f"{n_tok / max(eng.steps, 1):.2f})")
    if eng.spec_on:
        sc = eng.spec_counters()
        print(f"[serve] speculative: {sc['spec_rounds']} verify rounds, "
              f"accept rate {sc['spec_accept_rate']:.3f} "
              f"({sc['spec_accepted']}/{sc['spec_drafted']} drafted), "
              f"{n_tok / max(eng.steps, 1):.2f} tokens/step")
    ttft = tel.registry.get("serving_ttft_seconds")
    lat = tel.registry.get("serving_request_latency_seconds")
    if ttft is not None and ttft.count:
        print(f"[serve] ttft p50/p95/p99 "
              f"{ttft.percentile(0.5) * 1e3:.0f}/"
              f"{ttft.percentile(0.95) * 1e3:.0f}/"
              f"{ttft.percentile(0.99) * 1e3:.0f}ms, request latency p50 "
              f"{lat.percentile(0.5):.2f}s p99 {lat.percentile(0.99):.2f}s")
    if eng.cache_mode == "paged" and mon.n_samples:
        s = mon.summary()
        ratio = s["cold_compression_ratio"]
        cold = (f"cold-page compression {ratio:.3f}x raw"
                if ratio == ratio else "no page went cold")
        print(f"[serve] kv-cache ({args.cache}, page={eng.paged.page_size}):"
              f" peak {s['peak_paged_bytes'] / 1e6:.3f}MB vs monolithic "
              f"{s['monolithic_bytes'] / 1e6:.3f}MB "
              f"({100 * (1 - s['paged_vs_monolithic']):.1f}% saved), {cold}")
        if eng.paged.n_shards > 1:
            print(f"[serve] pages-per-shard peak {mon.peak_per_shard()} "
                  f"(free now {eng.paged.free_pages_per_shard})")
        if eng.prefill_chunk:
            print(f"[serve] chunked prefill (chunk={eng.prefill_chunk}, "
                  f"budget={eng.prefill_budget}/step): {eng.n_chunks} "
                  f"chunks / {eng.n_chunk_tokens} prompt tokens, "
                  f"{eng.n_interleaved_steps} interleaved steps, "
                  f"{eng.prefill_compile_count()} prefill compilation(s) "
                  f"across all prompt lengths")
        if eng.prefix_sharing:
            sp = eng.paged.stats()
            hits = tel.registry.get("prefix_hit_total")
            miss = tel.registry.get("prefix_miss_total")
            print(f"[serve] prefix sharing: "
                  f"{hits.value if hits else 0} hits / "
                  f"{miss.value if miss else 0} misses, index "
                  f"{sp['prefix_index_blocks']} blocks "
                  f"({sp['prefix_resident_blocks']} resident), "
                  f"{sp['prefix_retired_total']} retired to swap, "
                  f"{sp['prefix_cow_splits_total']} CoW splits")
        if "peak_swap_bytes" in s:
            print(f"[serve] swap tier: peak host-resident "
                  f"{s['peak_swap_bytes'] / 1e6:.3f}MB, traffic out/in "
                  f"{s['swap_out_bytes_total'] / 1e6:.3f}/"
                  f"{s['swap_in_bytes_total'] / 1e6:.3f}MB, "
                  f"{s['n_preempted']} preemptions "
                  f"({s['n_resumed']} resumed)")

    if args.check_lossless and args.compress != "none":
        # the FP8 engine starts only once the ECF8 engine's buffers are
        # free: at full width the two would not fit on one device together
        release(eng)
        eng = GenerationEngine(w.host, cfg, config=ecfg)
        done2, _ = serve_requests(eng, prompts, args.max_new)
        same = all(a.out_tokens == b.out_tokens
                   for a, b in zip(done, done2))
        print(f"[serve] lossless check vs fp8 baseline: "
              f"{'IDENTICAL' if same else 'MISMATCH'}")
        if not same:
            raise SystemExit(1)
    return done


if __name__ == "__main__":
    main()
