"""KV-cache exponent entropy per layer (fig1-style) + memory savings.

The paper's Figure 1 measures exponent entropy of *weights*; this
benchmark measures the same statistic on K/V cache pages produced by
real prefills, validating the Heilper & Singer observation the kvcache
subsystem is built on: cache activations concentrate their exponents
just like trained weights, so the page codec's entropy coding wins.

Reports, per arch / layer / K-or-V:
  * Shannon entropy of the bf16 8-bit exponent field (bits/element);
  * the page codec's true compressed ratio vs raw bf16 bytes;
an engine-level savings table (paged pages-in-use vs the monolithic
``(max_batch, max_len)`` cache) from a short mixed-length stream; an
**oversubscription variant**: a workload whose aggregate page demand is
>= 2x the raw pool, served through the host swap tier + preemptive
scheduler (``--swap-bytes``), reporting swap-in/out bytes and preemption
counts and asserting the tokens stay bit-identical to the monolithic
reference; a **speculative variant** (``run_speculative``): a
zero-extended draft/target pair served at batch 1, reporting acceptance
rate vs tok/s speedup and asserting the spec tokens bit-identical to
target-only; and a **sharded variant** (subprocess with virtual devices,
like tests/test_sharding.py) that serves the same stream on a 2-way data
mesh and a 2-way model mesh, recording pages-per-shard and the
cross-shard gather cost of each layout (zero page bytes on the data mesh
by construction; the tiny per-layer (acc, m, l) stat-merge all-gather on
the model mesh), plus the oversubscribed workload on the 2-way data
mesh (per-shard free lists + per-shard swap ledgers, still
bit-identical).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get, smoke_variant
from repro.core import stats
from repro.kvcache import codec
from repro.models import model as M
from repro.runtime.monitor import KVCacheMonitor
from repro.serving import EngineConfig, GenerationEngine, Request

ARCHS = ("qwen3-8b", "gemma2-9b")
PREFILL_T = 64


def _attn_cache_leaves(cfg, cache):
    """Yield (layer_name, kind, k_or_v, (n_kv, T, hd) array)."""
    unit = cfg.unit
    n_units = cfg.n_layers // unit
    for j in range(unit):
        kind = cfg.pattern[j]
        if kind not in ("attn", "nope", "local"):
            continue
        leaf = cache["units"][f"pos{j}"]
        for u in range(n_units):
            for kn in ("k", "v"):
                yield f"L{u * unit + j}", kind, kn, np.asarray(leaf[kn][u, 0])
    for t in range(cfg.n_layers - n_units * unit):
        name = f"layer{t}"
        kind = cfg.layer_kind(n_units * unit + t)
        if kind not in ("attn", "nope", "local"):
            continue
        leaf = cache["tail"][name]
        for kn in ("k", "v"):
            yield f"L{n_units * unit + t}", kind, kn, np.asarray(leaf[kn][0])


def run(verbose: bool = True):
    rows = []
    for arch in ARCHS:
        cfg = smoke_variant(get(arch))
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, PREFILL_T), 0,
                                  cfg.vocab_size)
        _, cache = M.prefill(params, cfg, toks, max_len=PREFILL_T)
        for lname, kind, kn, kv in _attn_cache_leaves(cfg, cache):
            page = np.asarray(jnp.asarray(kv, jnp.bfloat16))
            exp, _, _ = codec.split_planes(page)
            H = stats.shannon_entropy(np.bincount(exp, minlength=256))
            cp = codec.encode_page(page)
            rows.append({"arch": arch, "layer": lname, "kind": kind,
                         "kv": kn, "H": H, "ratio": cp.ratio()})

    if verbose:
        print(f"{'arch':18s} {'layer':6s} {'kind':6s} {'kv':3s}"
              f" {'H(E8) bits':>10s} {'coded/raw':>10s}")
        for r in rows:
            print(f"{r['arch']:18s} {r['layer']:6s} {r['kind']:6s}"
                  f" {r['kv']:3s} {r['H']:10.3f} {r['ratio']:10.3f}")

    ents = [r["H"] for r in rows]
    ratios = [r["ratio"] for r in rows]
    assert 0.5 < min(ents) and max(ents) < 6.0, (min(ents), max(ents))
    assert max(ratios) < 1.0, max(ratios)   # every layer compresses

    # engine-level savings: mixed-length stream through the paged engine
    cfg = smoke_variant(get(ARCHS[0]))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    mon = KVCacheMonitor()
    eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=4, max_len=64,
                           page_size=16, compress_cold=True, kv_monitor=mon))
    rng = np.random.default_rng(0)
    for _ in range(8):
        eng.submit(Request(
            prompt=rng.integers(0, cfg.vocab_size,
                                size=rng.integers(2, 24)).tolist(),
            max_new_tokens=int(rng.integers(4, 24))))
    eng.run()
    s = mon.summary()
    if verbose:
        print(f"\nengine ({ARCHS[0]}, batch 4, window 64, page 16):")
        print(f"  monolithic cache      {s['monolithic_bytes']:>10d} B")
        print(f"  paged peak            {s['peak_paged_bytes']:>10d} B "
              f"({100 * (1 - s['paged_vs_monolithic']):.1f}% saved)")
        print(f"  cold-page compression {s['cold_compression_ratio']:.3f}x "
              f"raw")
    assert s["peak_paged_bytes"] < s["monolithic_bytes"]

    over = run_oversubscribed(verbose=verbose)
    mixed = run_mixed(verbose=verbose)
    speculative = run_speculative(verbose=verbose)
    prefix = run_prefix_shared(verbose=verbose)
    sharded = run_sharded(verbose=verbose)
    return {
        "layers": len(rows),
        "entropy_range": (min(ents), max(ents)),
        "worst_ratio": max(ratios),
        "paged_vs_monolithic": s["paged_vs_monolithic"],
        "cold_compression_ratio": s["cold_compression_ratio"],
        "oversubscribed": over,
        "mixed": mixed,
        "speculative": speculative,
        "prefix": prefix,
        "sharded": sharded,
    }


def _zero_extended_target(dparams, dcfg, tcfg, seed: int = 99):
    """Graft the draft's weights into a deeper target whose extra blocks
    are exact identities: the extra layers' output projections (attn
    ``wo`` and mlp ``wo``) are zeroed, so each contributes ``x + 0`` to
    the residual stream and the target's logits are **bit-equal** to the
    draft's — while a target step costs ``n_layers_t / n_layers_d`` x
    the draft step.  This turns the smoke-shape speculative bench into a
    real measurement: acceptance is 1.0 by construction (random smoke
    weights would accept ~1/V of proposals) and any speedup comes from
    the engine actually replacing k+1 target decode steps with cheap
    draft steps plus one k+1-wide verify forward."""
    tparams = M.init_params(jax.random.PRNGKey(seed), tcfg)
    n_d = dcfg.n_layers
    dflat = {jax.tree_util.keystr(p): v
             for p, v in jax.tree_util.tree_flatten_with_path(dparams)[0]}

    def graft(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", k)))
                 for k in path]
        if "units" not in names:
            return dflat[jax.tree_util.keystr(path)]    # embed/norm/unembed
        if names[-1] == "wo":
            leaf = jnp.zeros_like(leaf)
        return leaf.at[:n_d].set(dflat[jax.tree_util.keystr(path)])

    return jax.tree_util.tree_map_with_path(graft, tparams)


def run_speculative(verbose: bool = True, spec_k: int = 4,
                    target_layers: int = 16):
    """Speculative decoding headline: acceptance rate vs tok/s speedup.

    Serves the same greedy stream through the target-only engine and the
    speculative engine (draft proposes ``spec_k`` tokens/round, target
    verifies all k+1 positions in one batched forward with exact
    rejection sampling), asserting the spec output **bit-identical** to
    target-only and reporting acceptance rate, tokens/round and the
    tok/s speedup.  The draft/target pair is the zero-extended
    construction (``_zero_extended_target``), so acceptance is exactly
    1.0 and the speedup is a pure engine-efficiency figure.  The stream
    serves at batch 1 — the latency-bound regime speculative decoding
    targets (the verify forward runs per slot, so at high batch
    occupancy the saved decode steps are offset by per-slot verify
    dispatches; at smoke shapes the crossover is ~batch 2).  Feeds the
    ``speculative`` section of ``BENCH_serving.json`` (perf-smoke CI
    tier)."""
    from dataclasses import replace
    dcfg = smoke_variant(get(ARCHS[0]))
    tcfg = replace(dcfg, n_layers=target_layers)
    dparams = M.init_params(jax.random.PRNGKey(0), dcfg)
    tparams = _zero_extended_target(dparams, dcfg, tcfg)

    def stream():
        rng = np.random.default_rng(3)
        return [Request(prompt=rng.integers(1, dcfg.vocab_size,
                                            size=rng.integers(4, 12)).tolist(),
                        max_new_tokens=24, id=30_000 + i)
                for i in range(3)]

    def serve(**kw):
        def once():
            eng = GenerationEngine(tparams, tcfg, config=EngineConfig(max_batch=1, max_len=64,
                                   page_size=16, **kw))
            reqs = stream()
            for r in reqs:
                eng.submit(r)
            t0 = time.perf_counter()
            eng.run()
            dt = time.perf_counter() - t0
            toks = sum(len(r.out_tokens) for r in reqs)
            return [r.out_tokens for r in reqs], toks / max(dt, 1e-9), eng
        once()                      # warm the jit caches
        return once()

    base_toks, base_tps, _ = serve()
    spec_toks, spec_tps, eng = serve(draft_params=dparams, draft_cfg=dcfg,
                                     spec_k=spec_k)
    assert eng.spec_on, "speculative gating rejected the smoke pair"
    assert spec_toks == base_toks, \
        "speculative decoding deviated from target-only"
    sc = eng.spec_counters()
    n_tok = sum(len(t) for t in spec_toks)
    out = {
        "k": spec_k,
        "draft_layers": dcfg.n_layers,
        "target_layers": tcfg.n_layers,
        "accept_rate": sc["spec_accept_rate"],
        "rounds": sc["spec_rounds"],
        "drafted": sc["spec_drafted"],
        "accepted": sc["spec_accepted"],
        "tokens_per_round": n_tok / max(sc["spec_rounds"], 1),
        "target_tok_per_s": base_tps,
        "spec_tok_per_s": spec_tps,
        "speedup": spec_tps / max(base_tps, 1e-9),
        "bit_identical_to_target_only": True,
    }
    assert out["accept_rate"] == 1.0, out["accept_rate"]
    if out["speedup"] < 1.0:
        # correctness (bit-identity, acceptance) is asserted above; raw
        # speedup on the tiny smoke shapes is CPU-warmth-dependent, so
        # regressions are gated by perf_smoke's baseline comparison
        # (machine-probe normalised) rather than a hard assert here
        import warnings
        warnings.warn(f"speculative smoke speedup {out['speedup']:.2f}x "
                      f"< 1.0 on this run", stacklevel=2)
    if verbose:
        print(f"\nspeculative decoding ({ARCHS[0]} smoke: "
              f"{dcfg.n_layers}-layer draft -> {tcfg.n_layers}-layer "
              f"zero-extended target, k={spec_k}, batch 1):")
        print(f"  target-only {base_tps:8.1f} tok/s")
        print(f"  speculative {spec_tps:8.1f} tok/s "
              f"({out['speedup']:.2f}x, accept rate "
              f"{out['accept_rate']:.3f}, "
              f"{out['tokens_per_round']:.2f} tokens/round)")
        print("  spec tokens bit-identical to target-only: True")
    return out


def run_prefix_shared(verbose: bool = True):
    """Cross-request prefix sharing on a chat-style workload.

    Every request carries the same 48-token system prompt plus a short
    per-user suffix.  The stream is served twice through the chunked
    engine — sharing off, then sharing on — and the bench asserts the
    sharing run is **bit-identical**, that the N-1 follow-up requests
    all hit the prefix index, that while the hits are in flight they
    hold ONE physical copy of the prefix pages (checked on the
    refcounts and the page tables, not the stats), and that the hit
    requests' wall-clock TTFT lands **strictly below** the no-sharing
    baseline (the matched 48 of 51 prompt tokens are never recomputed,
    so a hit pays one chunk step instead of seven).  Feeds the
    ``prefix`` section of ``BENCH_serving.json`` (perf-smoke CI tier)."""
    from repro.serving.telemetry import Telemetry
    cfg = smoke_variant(get(ARCHS[0]))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    prefix = rng.integers(1, cfg.vocab_size, size=48).tolist()  # 6 pages
    suffixes = [rng.integers(1, cfg.vocab_size, size=3).tolist()
                for _ in range(6)]

    def stream():
        return [Request(prompt=prefix + sfx, max_new_tokens=8,
                        id=40_000 + i)
                for i, sfx in enumerate(suffixes)]

    def drive(eng, reqs, ttft, t0):
        for _ in range(10_000):
            busy = eng.step()
            now = time.perf_counter() - t0
            for r in reqs:
                if r.out_tokens and r.id not in ttft:
                    ttft[r.id] = now
            if not busy and not any(s is not None for s in eng.slots):
                break
        assert all(r.done for r in reqs)

    def serve(sharing: bool):
        tel = Telemetry()
        eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=3, max_len=64,
                               cache_mode="paged", page_size=8,
                               prefill_chunk=8, telemetry=tel,
                               prefix_sharing=sharing))
        reqs, ttft = stream(), {}
        # the first request warms the index (a miss either way) ...
        eng.submit(reqs[0])
        drive(eng, reqs[:1], ttft, time.perf_counter())
        # ... then the chat follow-ups arrive together
        t0 = time.perf_counter()
        for r in reqs[1:]:
            eng.submit(r)
        eng.step()
        if sharing:
            # one physical copy while the hits are in flight: every
            # admitted slot's page table starts with the SAME pids,
            # refcounted once per slot plus once for the index
            slots = [eng.slots.index(r) for r in reqs[1:] if r in eng.slots]
            rows = [eng.paged._slot_pages[s][:len(prefix) // 8]
                    for s in slots]
            assert len(rows) >= 2 and all(r == rows[0] for r in rows), rows
            for pid in rows[0]:
                assert eng.paged._ref[pid] == len(rows) + 1
        now = time.perf_counter() - t0
        for r in reqs:
            if r.out_tokens and r.id not in ttft:
                ttft[r.id] = now
        drive(eng, reqs, ttft, t0)
        hit_ttft = [ttft[r.id] for r in reqs[1:]]
        reg = tel.registry
        return {
            "tokens": [r.out_tokens for r in reqs],
            "ttft_hit_mean_s": sum(hit_ttft) / len(hit_ttft),
            "chunk_tokens": eng.n_chunk_tokens,
            "hits": reg.counter("prefix_hit_total").value,
            "misses": reg.counter("prefix_miss_total").value,
            "match_tokens": reg.counter("prefix_match_tokens_total").value,
            "stats": eng.paged.stats(),
        }

    serve(False)                        # warm the jit caches
    off = serve(False)
    on = serve(True)
    assert on.pop("tokens") == off.pop("tokens"), \
        "prefix sharing deviated from the no-sharing engine"
    sp = on.pop("stats")
    off.pop("stats")
    n = len(suffixes)
    assert on["hits"] == n - 1 and on["misses"] == 1, (on["hits"],
                                                      on["misses"])
    assert on["match_tokens"] == (n - 1) * len(prefix)
    assert sp["prefix_cow_splits_total"] == 0
    assert on["chunk_tokens"] == off["chunk_tokens"] - on["match_tokens"]
    assert on["ttft_hit_mean_s"] < off["ttft_hit_mean_s"], (on, off)
    out = {
        "n_requests": n,
        "prefix_tokens": len(prefix),
        "hit_rate": on["hits"] / n,
        "match_tokens": on["match_tokens"],
        "chunk_tokens_nosharing": off["chunk_tokens"],
        "chunk_tokens_shared": on["chunk_tokens"],
        "ttft_hit_nosharing_s": off["ttft_hit_mean_s"],
        "ttft_hit_shared_s": on["ttft_hit_mean_s"],
        "ttft_speedup": off["ttft_hit_mean_s"] / max(on["ttft_hit_mean_s"],
                                                     1e-9),
        "cow_splits": sp["prefix_cow_splits_total"],
        "prefix_retired_total": sp["prefix_retired_total"],
        "bit_identical_to_nosharing": True,
    }
    if verbose:
        print(f"\nprefix sharing ({ARCHS[0]}, batch 3, {n} chat requests, "
              f"{len(prefix)}-token shared system prompt):")
        print(f"  hit rate {out['hit_rate']:.2f} "
              f"({on['hits']} hits / {on['misses']} miss), "
              f"{out['match_tokens']} prompt tokens never recomputed")
        print(f"  prefill chunk tokens {out['chunk_tokens_nosharing']} -> "
              f"{out['chunk_tokens_shared']}")
        print(f"  hit TTFT {out['ttft_hit_nosharing_s'] * 1e3:7.1f} ms -> "
              f"{out['ttft_hit_shared_s'] * 1e3:7.1f} ms "
              f"({out['ttft_speedup']:.2f}x)")
        print("  shared tokens bit-identical to no-sharing: True "
              "(one physical prefix copy asserted on the refcounts)")
    return out


# long-prompt/short-prompt mix for the chunked-prefill TTFT benchmark: the
# long prompts monopolize whole-prompt prefill while the short requests
# wait; chunked prefill bounds that head-of-line blocking per step
MIXED_WORKLOAD = (
    [48, 4, 40, 6, 3, 44, 8, 5],                        # prompt lengths
    [12, 10, 12, 10, 12, 10, 12, 10],                   # max_new_tokens
)


def _mixed_stream(cfg, id_base=20_000):
    rng = np.random.default_rng(7)
    lens, news = MIXED_WORKLOAD
    return [Request(prompt=rng.integers(1, cfg.vocab_size, size=n).tolist(),
                    max_new_tokens=m, id=id_base + i)
            for i, (n, m) in enumerate(zip(lens, news))]


def run_mixed(verbose: bool = True, trace_out: str | None = None):
    """Chunked vs whole-prompt prefill on a mixed long/short stream.

    Drives ``engine.step()`` by hand and records, per request, the
    host wall-clock **time to first token** (submit -> first sampled
    token) plus end-to-end decode tokens/s; asserts the chunked engine's
    tokens are bit-identical to the whole-prompt engine's and that the
    chunk path compiled exactly one prefill program for every prompt
    length in the stream (the whole-prompt engine compiles one per
    distinct length).  These numbers seed ``BENCH_serving.json`` in the
    perf-smoke CI tier (``benchmarks/perf_smoke.py``).

    The **published chunked numbers come from a fully instrumented run**
    (metrics registry + span tracer), so the perf-smoke regression gate
    covers telemetry overhead by construction; the same run's registry
    histograms supply the TTFT p50/p95/p99, and an identical chunked run
    with telemetry *off* pins ``telemetry_overhead_frac`` (tok/s cost of
    observation; target < 2%) and the on/off bit-identity."""
    from repro.serving.telemetry import Telemetry
    cfg = smoke_variant(get(ARCHS[0]))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    short = {i for i, n in enumerate(MIXED_WORKLOAD[0]) if n <= 8}

    def serve(telemetry=None, **kw):
        eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=4, max_len=64,
                               page_size=16, telemetry=telemetry, **kw))
        # the jitted-step caches are process-shared across engines, so
        # report the *delta* this stream caused
        c0 = eng.prefill_compile_count()
        reqs = _mixed_stream(cfg)
        for r in reqs:
            eng.submit(r)
        ttft = {}
        t0 = time.perf_counter()
        for _ in range(10_000):
            busy = eng.step()
            now = time.perf_counter() - t0
            for i, r in enumerate(reqs):
                if r.out_tokens and i not in ttft:
                    ttft[i] = now
            if not busy and not any(s is not None for s in eng.slots):
                break
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        toks = sum(len(r.out_tokens) for r in reqs)
        return {
            "tokens": [r.out_tokens for r in reqs],
            "tok_per_s": toks / max(dt, 1e-9),
            "ttft_mean_s": sum(ttft.values()) / len(ttft),
            "ttft_short_mean_s": (sum(ttft[i] for i in short)
                                  / len(short)),
            "steps": eng.steps,
            "prefill_compiles": eng.prefill_compile_count() - c0,
        }

    whole = serve()
    bare = serve(prefill_chunk=16)          # chunked, telemetry off (warm)
    tel = Telemetry()
    chunked = serve(prefill_chunk=16, telemetry=tel)
    assert bare["tokens"] == whole["tokens"], \
        "chunked prefill deviated from the whole-prompt engine"
    assert chunked.pop("tokens") == bare.pop("tokens"), \
        "telemetry changed the token stream"
    whole.pop("tokens")
    # one chunk program serves every prompt length (0 when an earlier
    # engine in this process already traced it); the whole-prompt engine
    # retraces per distinct length not yet seen by the shared jit cache
    assert bare["prefill_compiles"] <= 1, bare["prefill_compiles"]
    assert whole["prefill_compiles"] >= chunked["prefill_compiles"]
    h = tel.registry.get("serving_ttft_seconds")
    chunked.update(
        ttft_p50_s=h.percentile(0.50), ttft_p95_s=h.percentile(0.95),
        ttft_p99_s=h.percentile(0.99),
        # both chunked runs are warm (`whole` paid the params transfer /
        # first-dispatch cost), so their tok/s ratio isolates what the
        # registry + tracer cost on top of identical engine work
        telemetry_overhead_frac=max(
            1 - chunked["tok_per_s"] / max(bare["tok_per_s"], 1e-9), 0.0))
    if trace_out:
        from repro.runtime.trace_export import export_chrome_trace
        export_chrome_trace(tel.tracer, trace_out, registry=tel.registry)
    out = {"whole": whole, "chunked": chunked,
           "prompt_lengths": sorted(set(MIXED_WORKLOAD[0]))}
    if verbose:
        print(f"\nmixed long/short stream ({ARCHS[0]}, batch 4, "
              f"{len(MIXED_WORKLOAD[0])} requests, prompt lengths "
              f"{out['prompt_lengths']}):")
        for name, r in (("whole-prompt", whole), ("chunked(16)", chunked)):
            print(f"  {name:12s} {r['tok_per_s']:8.1f} tok/s  TTFT mean "
                  f"{r['ttft_mean_s'] * 1e3:7.1f} ms (short "
                  f"{r['ttft_short_mean_s'] * 1e3:7.1f} ms)  "
                  f"{r['prefill_compiles']} prefill compile(s)")
        print(f"  chunked TTFT p50/p95/p99 "
              f"{chunked['ttft_p50_s'] * 1e3:.1f}/"
              f"{chunked['ttft_p95_s'] * 1e3:.1f}/"
              f"{chunked['ttft_p99_s'] * 1e3:.1f} ms (registry histogram)")
        frac = chunked["telemetry_overhead_frac"]
        print(f"  telemetry overhead {frac:.1%} tok/s vs uninstrumented "
              f"chunked (target < 2%)")
        print("  chunked tokens bit-identical to whole-prompt "
              "(telemetry on and off): True")
        if trace_out:
            print(f"  wrote Chrome trace {trace_out}")
    return out


# mixed-length, mixed-priority stream sized so its aggregate page demand
# is >= 2x the raw pools used below; served by _sharded_result too, and
# mirrored by tests/test_serving.py's oversubscription tests
OVERSUB_WORKLOAD = (
    [[i + 1] * (7 + 3 * (i % 3)) for i in range(6)],    # prompts
    [14, 10, 16, 9, 12, 11],                            # max_new_tokens
    [0, 1, 0, 2, 1, 0],                                 # priorities
)


def _oversub_stream():
    prompts, news, prios = OVERSUB_WORKLOAD
    return [Request(prompt=p, max_new_tokens=n, priority=pr, id=10_000 + i)
            for i, (p, n, pr) in enumerate(zip(prompts, news, prios))]


def run_oversubscribed(verbose: bool = True, trace_out: str | None = None):
    """Serve a >= 2x-oversubscribed workload through swap + preemption.

    The seed engine raises ``OutOfPages`` on this stream; with the swap
    tier the whole workload completes, bit-identical to the monolithic
    reference, and the report shows what that cost in swap traffic.
    ``trace_out`` writes the oversubscribed run's Chrome-trace JSON
    (per-request lifecycle spans including the preempted intervals +
    engine evict/fault spans — the CI perf-smoke artifact)."""
    from repro.serving.telemetry import Telemetry
    cfg = smoke_variant(get(ARCHS[0]))
    params = M.init_params(jax.random.PRNGKey(0), cfg)

    def serve(**kw):
        eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=2, max_len=48, **kw))
        reqs = _oversub_stream()
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs], eng

    mono, _ = serve(cache_mode="monolithic")
    tel = Telemetry()
    mon = KVCacheMonitor(registry=tel.registry)
    over, eng = serve(cache_mode="paged", page_size=8, n_pages=5,
                      compress_cold=True, n_cold_slots=1,
                      swap_bytes=1 << 28, kv_monitor=mon, telemetry=tel)
    if trace_out:
        from repro.runtime.trace_export import export_chrome_trace
        trace = export_chrome_trace(tel.tracer, trace_out,
                                    registry=tel.registry)
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"preempted", "resume", "evict", "fault"} <= names, names
    demand = sum(eng.paged.pages_worst_case(len(r.prompt), r.max_new_tokens)
                 for r in _oversub_stream())
    assert demand >= 2 * eng.paged.n_pages, (demand, eng.paged.n_pages)
    assert over == mono, "oversubscribed serve deviated from monolithic"
    s = mon.summary()
    assert s["n_preempted"] > 0 and s["swap_in_bytes_total"] > 0
    out = {
        "aggregate_demand_pages": demand,
        "n_pages": eng.paged.n_pages,
        "oversubscription": demand / eng.paged.n_pages,
        "steps": eng.steps,
        "n_preempted": s["n_preempted"],
        "n_resumed": s["n_resumed"],
        "swap_out_bytes": s["swap_out_bytes_total"],
        "swap_in_bytes": s["swap_in_bytes_total"],
        "peak_swap_bytes": s["peak_swap_bytes"],
        "bit_identical_to_monolithic": True,
    }
    if verbose:
        print(f"\noversubscribed engine ({ARCHS[0]}, batch 2, pool "
              f"{out['n_pages']} pages, demand {demand} pages = "
              f"{out['oversubscription']:.1f}x):")
        print(f"  completed in {out['steps']} steps, "
              f"{out['n_preempted']} preemptions "
              f"({out['n_resumed']} resumed)")
        print(f"  swap traffic out/in {out['swap_out_bytes']}/"
              f"{out['swap_in_bytes']} B, peak host-resident "
              f"{out['peak_swap_bytes']} B")
        print("  tokens bit-identical to monolithic: True")
        if trace_out:
            print(f"  wrote Chrome trace {trace_out} (includes "
                  f"preempt/resume + evict/fault spans)")
    return out


def _sharded_result(n_devices: int) -> dict:
    """Serve the mixed and the oversubscribed streams on ``n_devices``-way
    data / model meshes of this process's first devices."""
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:n_devices])
    cfg = smoke_variant(get('qwen3-8b'))
    params = M.init_params(jax.random.PRNGKey(0), cfg)

    def stream():
        rng = np.random.default_rng(0)
        return [Request(prompt=rng.integers(0, cfg.vocab_size,
                                            size=rng.integers(2, 24)).tolist(),
                        max_new_tokens=int(rng.integers(4, 24)))
                for _ in range(8)]

    def serve(mesh):
        mon = KVCacheMonitor()
        eng = GenerationEngine(params, cfg, config=EngineConfig(
            max_batch=4, max_len=64, page_size=16, compress_cold=True,
            kv_monitor=mon, mesh=mesh))
        reqs = stream()
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        toks = sum(len(r.out_tokens) for r in reqs)
        return eng, {'tok_per_s': toks / max(dt, 1e-9), 'steps': eng.steps,
                     'pages_per_shard_peak': mon.peak_per_shard(),
                     'tokens': [r.out_tokens for r in reqs]}

    out = {}
    _, out['single'] = serve(None)
    eng, out['data_mesh'] = serve(Mesh(devs, ('data',)))
    # data mesh: every slot's pages live on its own shard -> no page bytes
    # ever cross a device for the gather
    out['data_mesh']['cross_shard_gather_bytes_per_step'] = 0
    out['data_mesh']['bit_identical_to_single'] = (
        out['data_mesh'].pop('tokens') == out['single']['tokens'])
    out['single'].pop('tokens')
    _, out['model_mesh'] = serve(Mesh(devs, ('model',)))
    out['model_mesh'].pop('tokens')
    # model mesh: pages split round-robin over model shards; each decode
    # step all-gathers (acc, m, l) per attention layer to merge stats
    B, Hq, hd = 4, cfg.n_heads, cfg.hd
    out['model_mesh']['cross_shard_gather_bytes_per_step'] = (
        eng.paged.n_attn_layers * n_devices
        * (B * Hq * hd * 4 + 2 * B * Hq * 4))

    # oversubscribed + swap on the data mesh: aggregate page demand >= 2x
    # the raw pool, per-shard free lists + per-shard swap ledgers, tokens
    # still bit-identical to the single-device monolithic reference
    def serve_over(mesh, **kw):
        mon = KVCacheMonitor()
        eng = GenerationEngine(params, cfg, config=EngineConfig(
            max_batch=4, max_len=48, kv_monitor=mon, mesh=mesh, **kw))
        reqs = _oversub_stream()
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs], eng, mon

    mono_o, _, _ = serve_over(None, cache_mode='monolithic')
    toks_o, eng_o, mon_o = serve_over(
        Mesh(devs, ('data',)), cache_mode='paged',
        page_size=8, n_pages=8, compress_cold=True, n_cold_slots=2,
        swap_bytes=1 << 28)
    demand = sum(eng_o.paged.pages_worst_case(len(r.prompt),
                                              r.max_new_tokens)
                 for r in _oversub_stream())
    assert demand >= 2 * eng_o.paged.n_pages, (demand, eng_o.paged.n_pages)
    s_o = mon_o.summary()
    assert toks_o == mono_o
    assert s_o['n_preempted'] > 0 and s_o['swap_in_bytes_total'] > 0
    out['oversubscribed_data_mesh'] = {
        'aggregate_demand_pages': demand, 'n_pages': eng_o.paged.n_pages,
        'steps': eng_o.steps, 'n_preempted': s_o['n_preempted'],
        'n_resumed': s_o['n_resumed'],
        'swap_out_bytes': s_o['swap_out_bytes_total'],
        'swap_in_bytes': s_o['swap_in_bytes_total'],
        'bit_identical_to_single': True,
    }
    return out


def run_sharded(n_devices: int = 2, verbose: bool = True):
    """Serve the mixed stream on 2-way data / model meshes and report
    pages-per-shard + cross-shard gather cost.

    Where this process already sees ``n_devices`` accelerator devices it
    runs on them in-process (a child could not reach chips this process
    holds).  Otherwise it runs in a child with that many virtual CPU
    devices (``--xla_force_host_platform_device_count``), keeping this
    process at its own device count."""
    if (jax.default_backend() != "cpu"
            and len(jax.devices()) >= n_devices):
        out = _sharded_result(n_devices)
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ,
                   PYTHONPATH=os.pathsep.join(
                       [repo, os.path.join(repo, "src")]),
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count"
                             f"={n_devices}")
        body = ("import json; from benchmarks.kvcache_bench import "
                f"_sharded_result; print('RESULT ' + json.dumps("
                f"_sharded_result({n_devices})))")
        p = subprocess.run([sys.executable, "-c", body], env=env,
                           capture_output=True, text=True, timeout=900)
        assert p.returncode == 0, \
            f"sharded bench failed:\n{p.stderr[-4000:]}"
        out = json.loads(
            p.stdout.strip().splitlines()[-1].removeprefix("RESULT "))
    assert out["data_mesh"]["bit_identical_to_single"]
    if verbose:
        print(f"\nsharded engine (qwen3-8b smoke, batch 4, {n_devices} "
              f"virtual devices):")
        for name in ("single", "data_mesh", "model_mesh"):
            r = out[name]
            extra = ""
            if "pages_per_shard_peak" in r:
                extra = (f"  pages/shard peak {r['pages_per_shard_peak']}"
                         f"  x-shard gather "
                         f"{r.get('cross_shard_gather_bytes_per_step', 0)}"
                         f" B/step")
            print(f"  {name:11s} {r['tok_per_s']:8.1f} tok/s "
                  f"({r['steps']} steps){extra}")
        print("  data_mesh tokens bit-identical to single-device: True")
        o = out["oversubscribed_data_mesh"]
        print(f"  oversubscribed on the data mesh: demand "
              f"{o['aggregate_demand_pages']} pages vs pool {o['n_pages']}, "
              f"{o['n_preempted']} preemptions, swap out/in "
              f"{o['swap_out_bytes']}/{o['swap_in_bytes']} B, "
              f"bit-identical: True")
    return out


if __name__ == "__main__":
    run()
