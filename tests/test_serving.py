"""Serving engine: continuous batching correctness, slot reuse, and the
oversubscribed swap/preemption tier.

Differential layer (ISSUE 3): random mixed-length, mixed-priority
workloads through the paged+compressed+swap engine must emit tokens
**bit-identical** per request to the monolithic-cache engine — including
runs sized to force eviction and whole-request preemption (hypothesis
property test; example budget raised by the ``ci`` profile, see
conftest.py), and on a 2-device CPU mesh (subprocess, marked slow).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from conftest import run_subprocess

from repro.configs import get, smoke_variant
from repro.models import model as M
from repro.runtime.monitor import KVCacheMonitor
from repro.serving import EngineConfig, GenerationEngine, Request
from repro.serving.sampler import greedy, sample_logits

try:
    from hypothesis import given, strategies as st
except ImportError:          # tier-1 may run without hypothesis
    given = None


def _ref_greedy(params, cfg, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits, _ = M.forward(params, cfg, jnp.asarray(toks, jnp.int32)[None])
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_full_forward_greedy():
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=3, max_len=48))
    reqs = [Request(prompt=[1, 2, 3, 4], max_new_tokens=5),
            Request(prompt=[5, 6, 7], max_new_tokens=6),
            Request(prompt=[9, 10], max_new_tokens=4),
            Request(prompt=[11, 12, 13], max_new_tokens=4)]  # > max_batch
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert all(r.done for r in done)
    for r in done:
        assert r.out_tokens == _ref_greedy(params, cfg, r.prompt,
                                           r.max_new_tokens), r.id


def test_engine_slot_reuse_and_occupancy():
    cfg = smoke_variant(get("xlstm-350m"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=2, max_len=32))
    reqs = [Request(prompt=[i + 1], max_new_tokens=3) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert all(r.done for r in done)
    assert all(len(r.out_tokens) == 3 for r in done)
    # 5 requests x 3 tokens across batch-2 decode steps: slots were reused
    assert eng.steps < 15


def test_samplers():
    logits = jnp.asarray([[[0.0, 5.0, 1.0, -2.0]]])
    assert int(greedy(logits)[0, 0]) == 1
    t = sample_logits(logits, jax.random.PRNGKey(0), temperature=1e-4)
    assert int(t[0, 0]) == 1
    tk = sample_logits(jnp.tile(logits, (8, 1, 1)), jax.random.PRNGKey(1),
                       temperature=1.0, top_k=2)
    assert set(np.asarray(tk).reshape(-1).tolist()) <= {1, 2}


def test_splice_axes():
    """_splice picks the batch axis from the leaf's path: unit-stacked
    leaves carry it at axis 1, tail leaves at axis 0, and cur_len is a
    per-slot scalar write."""
    from repro.serving.engine import _splice
    full = jnp.zeros((3, 4, 2, 8, 5))            # (U, B, n_kv, T, hd)
    frag = jnp.ones((3, 1, 2, 8, 5))
    out = _splice(full, frag, 2, ["units", "pos0", "k"])
    assert float(out[:, 2].min()) == 1.0 and float(out[:, :2].max()) == 0.0

    full_t = jnp.zeros((4, 2, 8, 5))             # (B, n_kv, T, hd)
    out_t = _splice(full_t, jnp.ones((1, 2, 8, 5)), 1,
                    ["tail", "layer0", "v"])
    assert float(out_t[1].min()) == 1.0 and float(out_t[0].max()) == 0.0

    cur = _splice(jnp.zeros((4,), jnp.int32), jnp.asarray(7, jnp.int32), 3,
                  ["cur_len"])
    assert cur.tolist() == [0, 0, 0, 7]


def test_splice_fragment_roundtrips_prefill():
    """Splicing a single-row prefill fragment at slot s reproduces that
    request's cache content at batch row s for every leaf."""
    from repro.serving.engine import splice_fragment
    cfg = smoke_variant(get("gemma2-9b"))        # local+attn mixed pattern
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0,
                              cfg.vocab_size)
    _, frag = M.prefill(params, cfg, toks, max_len=16)
    cache = M.init_cache(cfg, 3, 16, dtype=jnp.float32, per_slot=True)
    cache = splice_fragment(cache, frag, 2)

    def batch_axis(path):
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        return None if "cur_len" in names else (1 if "units" in names else 0)

    flat_c = jax.tree_util.tree_flatten_with_path(cache)[0]
    flat_f = jax.tree_util.tree_flatten(frag)[0]
    for (path, leaf), fr in zip(flat_c, flat_f):
        ax = batch_axis(path)
        if ax is None:
            continue
        got = jnp.take(leaf, jnp.asarray([2]), axis=ax)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(fr, np.float32))
        other = jnp.take(leaf, jnp.asarray([0, 1]), axis=ax)
        assert float(jnp.abs(other).max()) == 0.0


def test_engine_slot_reclamation_mixed_lengths():
    """Finished slots are reclaimed mid-stream (6 requests, 2 slots) and
    every request still matches the full-forward greedy reference."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=2, max_len=48))
    reqs = [Request(prompt=[i + 1, i + 2, i + 3], max_new_tokens=n)
            for i, n in enumerate([2, 9, 4, 7, 3, 5])]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert all(r.done for r in done)
    for r in done:
        assert r.out_tokens == _ref_greedy(params, cfg, r.prompt,
                                           r.max_new_tokens), r.id
    # 30 tokens through 2 slots: reuse means well under 30 decode steps
    assert eng.steps < 25
    if eng.paged is not None:   # all pages returned to the pool
        assert eng.paged.free_pages == eng.paged.n_pages - 1
        assert not eng.paged._slot_pages


def test_run_returns_requests_admitted_before_run():
    """Regression: ``run()`` used to snapshot the queue, so requests
    already admitted to slots (e.g. by a manual ``step()``) were lost
    from its return value."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=2, max_len=48))
    r1 = Request(prompt=[1, 2, 3], max_new_tokens=3)
    r2 = Request(prompt=[4, 5], max_new_tokens=3)
    eng.submit(r1)
    eng.submit(r2)
    eng.step()                       # both now sit in slots, queue empty
    done = eng.run()
    assert r1 in done and r2 in done
    assert all(r.done for r in (r1, r2))
    # late submissions are tracked independently of earlier returns
    r3 = Request(prompt=[7], max_new_tokens=2)
    eng.submit(r3)
    assert eng.run() == [r3] and r3.done


# --------------------------------------------------------------------------
# oversubscription: swap tier + preemptive scheduler (ISSUE 3)
# --------------------------------------------------------------------------

_OVERSUB = dict(cache_mode="paged", page_size=8, n_pages=5,
                compress_cold=True, n_cold_slots=1, swap_bytes=1 << 28)

# the canonical >= 2x-oversubscribed stream (kept in sync with
# benchmarks/kvcache_bench.py::OVERSUB_WORKLOAD, which also injects it
# into its sharded subprocess)
_OVERSUB_WL = (
    [[i + 1] * (7 + 3 * (i % 3)) for i in range(6)],    # prompts
    [14, 10, 16, 9, 12, 11],                            # max_new_tokens
    [0, 1, 0, 2, 1, 0],                                 # priorities
)


def _oversub_requests(id_base=5_000):
    prompts, news, prios = _OVERSUB_WL
    return [Request(prompt=p, max_new_tokens=n, priority=pr,
                    id=id_base + i)
            for i, (p, n, pr) in enumerate(zip(prompts, news, prios))]


def _serve(params, cfg, reqs, **kw):
    eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=2, max_len=48, **kw))
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    return [r.out_tokens for r in reqs], eng


def test_oversubscribed_workload_completes_bit_identical():
    """Acceptance: aggregate page demand >= 2x ``n_pages`` completes
    without OutOfPages via eviction + whole-request preemption, and every
    request's tokens are bit-identical to the monolithic reference."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)

    stream = _oversub_requests
    mono, _ = _serve(params, cfg, stream(), cache_mode="monolithic")
    mon = KVCacheMonitor()
    over, eng = _serve(params, cfg, stream(), kv_monitor=mon, **_OVERSUB)
    demand = sum(eng.paged.pages_worst_case(len(r.prompt), r.max_new_tokens)
                 for r in stream())
    assert demand >= 2 * eng.paged.n_pages, (demand, eng.paged.n_pages)
    assert over == mono
    s = mon.summary()
    assert s["n_preempted"] > 0 and s["n_resumed"] > 0
    assert s["swap_out_bytes_total"] > 0
    assert s["swap_in_bytes_total"] == s["swap_out_bytes_total"]
    # everything drained: no host-resident swap, full free lists
    assert len(eng.paged.swap) == 0 and eng.paged.swap.bytes_used == 0
    assert eng.paged.free_pages == eng.paged.n_pages - 1


def test_priority_classes_preempt_lower_priority_work():
    """A late high-priority request preempts running priority-0 work (the
    victim is swapped out wholesale and still finishes bit-identically)."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    lo = [Request(prompt=[i + 1] * 9, max_new_tokens=14, priority=0,
                  id=6_000 + i) for i in range(2)]
    hi = Request(prompt=[40] * 9, max_new_tokens=8, priority=5, id=6_100)

    ref = {}
    for r in lo + [hi]:
        mono, _ = _serve(params, cfg,
                         [Request(prompt=list(r.prompt),
                                  max_new_tokens=r.max_new_tokens,
                                  id=r.id)],
                         cache_mode="monolithic")
        ref[r.id] = mono[0]

    eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=2, max_len=48, **_OVERSUB))
    for r in lo:
        eng.submit(r)
    for _ in range(3):               # both low-priority requests running
        eng.step()
    eng.submit(hi)
    eng.step()                       # admission preemption kicks one out
    assert eng.scheduler.n_preempted >= 1
    assert hi in eng.slots
    eng.run()
    for r in lo + [hi]:
        assert r.done and r.out_tokens == ref[r.id], r.id


def test_scheduler_never_places_request_on_shard_it_outgrows():
    """Regression: a request whose worst-case working set fits shard 1
    (capacity 4) but not shard 0 (capacity 3 — the garbage page) must
    not be placed on a shard-0 slot just because its *prompt* fits —
    that wedges mid-flight with nothing to preempt."""
    from repro.kvcache import PagedKVCache, SwapStore
    from repro.serving.scheduler import Scheduler
    cfg = smoke_variant(get("qwen3-8b"))
    pkv = PagedKVCache(cfg, 4, 64, dtype=jnp.float32, page_size=16,
                       n_pages=8, n_shards=2)
    pkv.attach_swap(SwapStore())
    sched = Scheduler(paged=pkv)
    big = Request(prompt=[1] * 10, max_new_tokens=54, id=9_400)
    assert pkv.pages_worst_case(10, 54) == 4      # > shard 0's capacity 3
    sched.submit(big)
    assert sched.pick(0) is None and sched.pick(1) is None   # shard 0
    assert sched.pick(2) is big                              # shard 1
    # a shard-0-sized request still lands on shard 0
    small = Request(prompt=[1] * 10, max_new_tokens=8, id=9_401)
    sched.submit(small)
    assert sched.pick(0) is small


def test_hybrid_arch_preemption_preserves_nonpaged_state():
    """Preemption must stash and restore a hybrid architecture's
    *non-paged* per-slot cache state: gemma2's local-attention ring
    buffers live in monolithic batch-dim leaves next to the page pools
    (only 'attn'/'nope' layers page), would be clobbered by the next
    request admitted to the slot, and carry no page ids for the swap
    tier to save.  Regression for `Preempted.state` /
    `snapshot_slot_state`."""
    cfg = smoke_variant(get("gemma2-9b"))        # ('local','attn') pattern
    params = M.init_params(jax.random.PRNGKey(0), cfg)

    def stream():
        return [Request(prompt=[i + 1] * (7 + 3 * (i % 3)),
                        max_new_tokens=n, priority=pr, id=9_500 + i)
                for i, (n, pr) in enumerate(
                    zip([14, 10, 16, 9], [0, 1, 0, 2]))]

    mono, _ = _serve(params, cfg, stream(), cache_mode="monolithic")
    over, eng = _serve(params, cfg, stream(), **_OVERSUB)
    assert eng.cache_mode == "paged"
    assert eng.scheduler.n_preempted > 0, "no preemption - test is vacuous"
    assert over == mono


def test_page_boundary_prompt_swap_roundtrip_bit_identical():
    """A prompt of exactly k * page_size tokens (its fragment exactly
    fills its pages) survives compress -> swap -> restore: preempting the
    slot mid-generation and resuming changes no output bit."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    for k in (1, 2):
        prompt = list(range(1, 8 * k + 1))       # page_size below is 8
        req = Request(prompt=list(prompt), max_new_tokens=10, id=7_000 + k)
        mono, _ = _serve(params, cfg,
                         [Request(prompt=list(prompt), max_new_tokens=10,
                                  id=req.id)],
                         cache_mode="monolithic")
        eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=2, max_len=48,
                               **_OVERSUB))
        eng.submit(req)
        for _ in range(3):
            eng.step()
        slot = eng.slots.index(req)
        assert eng._preempt(slot)                # force the swap round trip
        assert req not in eng.slots
        eng.run()
        assert req.done and req.out_tokens == mono[0], k
        assert eng.scheduler.n_resumed >= 1


def _check_differential_workload(wl, seed, prefill_chunk=0):
    """Differential core: a workload of (prompt_len, max_new, priority,
    temperature) tuples through the paged+compressed+swap engine emits
    per-request tokens bit-identical to the monolithic engine.  The tiny
    pool (5 pages, 1 cold slot) makes most workloads force eviction and
    preemption; sampling keys fold (seed, request.id, position) so even
    sampled requests are schedule-invariant.  ``prefill_chunk`` > 0 runs
    the chunked, decode-interleaved prefill path — same invariant."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=p).tolist()
               for p, _, _, _ in wl]

    def stream():
        return [Request(prompt=list(prompts[i]), max_new_tokens=n,
                        priority=pr, temperature=t, id=8_000 + i)
                for i, (_, n, pr, t) in enumerate(wl)]

    mono, _ = _serve(params, cfg, stream(), cache_mode="monolithic")
    over, eng = _serve(params, cfg, stream(), prefill_chunk=prefill_chunk,
                       **_OVERSUB)
    assert over == mono
    assert len(eng.paged.swap) == 0              # swap fully drained
    if prefill_chunk:
        assert eng.prefill_chunk == prefill_chunk and eng.n_chunks > 0
    return eng


def test_differential_fixed_workloads_bit_identical():
    """Tier-1 anchor for the differential property (no hypothesis
    needed): two hand-picked workloads — one sized to force eviction and
    preemption, one mixing sampled and greedy requests."""
    eng = _check_differential_workload(
        [(20, 12, 1, 0.0), (16, 10, 2, 0.0), (9, 12, 0, 0.0),
         (14, 8, 0, 0.0)], seed=123)
    assert eng.scheduler.n_preempted > 0         # the point of the sizing
    _check_differential_workload(
        [(3, 8, 0, 0.8), (5, 6, 1, 0.0), (2, 5, 0, 0.8)], seed=7)


# --------------------------------------------------------------------------
# chunked, decode-interleaved prefill (ISSUE 4)
# --------------------------------------------------------------------------


def test_chunked_prefill_fixed_workloads_bit_identical():
    """Tier-1 anchor: the chunked-prefill engine (chunk smaller than most
    prompts, so multi-chunk prefill really happens and interleaves with
    decode) emits tokens bit-identical to the monolithic engine, under
    oversubscription with eviction and preemption."""
    eng = _check_differential_workload(
        [(20, 12, 1, 0.0), (16, 10, 2, 0.0), (9, 12, 0, 0.0),
         (14, 8, 0, 0.0)], seed=123, prefill_chunk=4)
    assert eng.scheduler.n_preempted > 0
    assert eng.n_interleaved_steps > 0           # prefill mixed with decode
    _check_differential_workload(                # sampled + greedy mix
        [(13, 8, 0, 0.8), (5, 6, 1, 0.0), (18, 5, 0, 0.8)], seed=7,
        prefill_chunk=8)


def test_chunked_prefill_exactly_one_compile_across_lengths():
    """Regression (the recompile-per-prompt-length failure mode must
    never return silently): a mixed-length stream through the chunked
    engine traces **exactly one** prefill program — counted on the jitted
    chunk step itself — where the whole-prompt path would trace one per
    distinct length.  A second engine with the same shape shares the
    cached program (zero new traces)."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    # max_len=40 is this test's own jit-cache key; compress_cold=False so
    # no second (cold-pool) trace can appear
    kw = dict(max_batch=2, max_len=40, page_size=8, prefill_chunk=8)

    def serve(lens, id_base):
        eng = GenerationEngine(params, cfg, config=EngineConfig(**kw))
        reqs = [Request(prompt=[(i * 7 + j) % 50 + 1 for j in range(n)],
                        max_new_tokens=3, id=id_base + i)
                for i, n in enumerate(lens)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done for r in reqs)
        return eng

    eng = serve([3, 7, 12, 17, 25, 31], id_base=11_000)
    assert eng.n_chunks >= 10
    assert eng.prefill_compile_count() == 1, eng.prefill_compile_count()
    eng2 = serve([5, 9, 2, 33], id_base=11_100)   # new lengths, same program
    assert eng2.prefill_compile_count() == 1, eng2.prefill_compile_count()


def test_chunked_midprefill_preempt_resume_bit_identical():
    """A request preempted **mid-prefill** (Preempted.prefill_pos set)
    swaps its first chunks out, requeues, resumes prefill at the recorded
    position and finishes bit-identical to an unpreempted run."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    req = Request(prompt=list(range(1, 21)), max_new_tokens=8, id=12_000)
    ref, _ = _serve(params, cfg,
                    [Request(prompt=list(req.prompt), max_new_tokens=8,
                             id=req.id)], cache_mode="monolithic")
    eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=2, max_len=48,
                           prefill_chunk=4, prefill_budget=4, **_OVERSUB))
    eng.submit(req)
    eng.step()                                   # one 4-token chunk in
    slot = eng.slots.index(req)
    assert eng._prefill_pos[slot] == 4
    assert eng._preempt(slot)                    # force mid-prefill preempt
    assert req not in eng.slots and not req.out_tokens
    st = eng.scheduler.head()
    assert st.prefill_pos == 4 and st.prefill_tokens_left == 16
    eng.run()
    assert req.done and req.out_tokens == ref[0]
    assert eng.scheduler.n_resumed >= 1


def test_multi_page_chunk_preempt_bit_identical():
    """Regression: a prefill chunk spanning several pages runs out of
    pages part-way through its ``ensure``, preempts a victim and retries.
    ``ensure`` is all-or-nothing, so the retry maps every page of the
    chunk (a partial grant once stayed on the host page list without
    reaching the page table, and the chunk's K/V landed in the garbage
    page).  Tokens stay bit-identical to the monolithic engine."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[(7 * i + j) % 500 + 1 for j in range(n)]
               for i, n in enumerate([40, 30, 36, 20])]

    def stream():
        return [Request(prompt=list(p), max_new_tokens=6, id=13_000 + i)
                for i, p in enumerate(prompts)]

    mono, _ = _serve(params, cfg, stream(), cache_mode="monolithic")
    over, eng = _serve(params, cfg, stream(), cache_mode="paged",
                       page_size=4, n_pages=14, swap_bytes=-1,
                       prefill_chunk=16)
    assert eng.scheduler.n_preempted > 0
    assert over == mono


def test_scheduler_token_budget_blocks_new_prefill_work():
    """pick() with an exhausted prefill budget admits only zero-prefill
    items (decode-phase resumes); a budget-blocked class head blocks its
    class, preserving FIFO."""
    from repro.kvcache import PagedKVCache, SwapStore
    from repro.serving.scheduler import Preempted, Scheduler
    cfg = smoke_variant(get("qwen3-8b"))
    pkv = PagedKVCache(cfg, 2, 64, dtype=jnp.float32, page_size=16)
    pkv.attach_swap(SwapStore())
    sched = Scheduler(paged=pkv, chunk_tokens=8)
    a = Request(prompt=[1] * 10, max_new_tokens=4, id=13_000)
    sched.submit(a)
    assert sched.pick(0, prefill_budget=0) is None       # needs prefill
    assert sched.pick(0, prefill_budget=8) is a
    # a decode-phase resume admits even with no budget left
    done = Preempted(req=Request(prompt=[1] * 4, max_new_tokens=4,
                                 id=13_001),
                     pages=[], skip=set(), host_len=5, last_tok=3)
    sched.requeue(done)
    assert sched.prefill_tokens(done) == 0
    assert sched.pick(1, prefill_budget=0) is done


if given is not None:
    workloads = st.lists(
        st.tuples(st.integers(1, 20),            # prompt length
                  st.integers(2, 12),            # max_new_tokens
                  st.integers(0, 2),             # priority class
                  st.sampled_from([0.0, 0.0, 0.0, 0.8])),  # temperature
        min_size=3, max_size=6)

    @given(workloads, st.integers(0, 2**31 - 1))
    def test_differential_random_workloads_bit_identical(wl, seed):
        _check_differential_workload(wl, seed)

    @given(workloads, st.integers(1, 12), st.integers(0, 2**31 - 1))
    def test_chunked_random_workloads_bit_identical(wl, chunk, seed):
        """Property: for any (prompt length, chunk size, priority,
        temperature) mix — chunks bigger, smaller and incommensurate
        with the page size — the chunked engine is bit-identical to the
        monolithic reference, including runs that preempt mid-prefill."""
        _check_differential_workload(wl, seed, prefill_chunk=chunk)


@pytest.mark.slow
def test_oversubscribed_sharded_bit_identical():
    """Acceptance: the oversubscribed workload on a 2-device data mesh
    (per-shard free lists + per-shard swap ledgers) completes with
    preemption and stays bit-identical to the single-device monolithic
    reference."""
    run_subprocess("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.configs import get, smoke_variant
        from repro.models import model as M
        from repro.runtime.monitor import KVCacheMonitor
        from repro.serving import EngineConfig, GenerationEngine, Request

        cfg = smoke_variant(get('qwen3-8b'))
        params = M.init_params(jax.random.PRNGKey(0), cfg)

        def stream():
            prompts, news, prios = __OVERSUB_WL__
            return [Request(prompt=p, max_new_tokens=n, priority=pr,
                            id=9_000 + i)
                    for i, (p, n, pr) in enumerate(
                        zip(prompts, news, prios))]

        def serve(mesh, **kw):
            mon = KVCacheMonitor()
            eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=4, max_len=48,
                                   kv_monitor=mon, mesh=mesh, **kw))
            reqs = stream()
            for r in reqs:
                eng.submit(r)
            eng.run()
            assert all(r.done for r in reqs)
            return [r.out_tokens for r in reqs], eng, mon

        mono, _, _ = serve(None, cache_mode='monolithic')
        mesh = Mesh(np.array(jax.devices()[:2]), ('data',))
        over, eng, mon = serve(mesh, cache_mode='paged', page_size=8,
                               n_pages=8, compress_cold=True,
                               n_cold_slots=2, swap_bytes=1 << 28)
        assert eng.cache_mode == 'paged' and eng.paged.n_shards == 2
        demand = sum(eng.paged.pages_worst_case(len(r.prompt),
                                                r.max_new_tokens)
                     for r in stream())
        assert demand >= 2 * eng.paged.n_pages
        assert over == mono, (over, mono)
        s = mon.summary()
        assert s['n_preempted'] > 0 and s['swap_in_bytes_total'] > 0
        assert len(eng.paged.swap) == 0
        print('oversubscribed sharded == single-device monolithic: OK')
    """.replace("__OVERSUB_WL__", repr(_OVERSUB_WL)), devices=2)


@pytest.mark.slow
def test_chunked_prefill_sharded_bit_identical():
    """Acceptance: the chunked-prefill engine on a 2-device data mesh
    (owner-shard chunk writes, psum'd outputs) serves the oversubscribed
    mixed-length workload bit-identical to the single-device monolithic
    reference, with preemption mid-run and a bounded number of chunk
    compilations across all prompt lengths."""
    run_subprocess("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.configs import get, smoke_variant
        from repro.models import model as M
        from repro.serving import EngineConfig, GenerationEngine, Request

        cfg = smoke_variant(get('qwen3-8b'))
        params = M.init_params(jax.random.PRNGKey(0), cfg)

        def stream(extra=0):
            prompts, news, prios = __OVERSUB_WL__
            return [Request(prompt=p + [1] * extra, max_new_tokens=n,
                            priority=pr, id=14_000 + 100 * extra + i)
                    for i, (p, n, pr) in enumerate(
                        zip(prompts, news, prios))]

        def serve(mesh, reqs, **kw):
            eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=4, max_len=48,
                                   mesh=mesh, **kw))
            for r in reqs:
                eng.submit(r)
            eng.run()
            assert all(r.done for r in reqs)
            return [r.out_tokens for r in reqs], eng

        kw = dict(cache_mode='paged', page_size=8, n_pages=8,
                  compress_cold=True, n_cold_slots=2, swap_bytes=1 << 28,
                  prefill_chunk=4)
        mesh = Mesh(np.array(jax.devices()[:2]), ('data',))
        mono, _ = serve(None, stream(), cache_mode='monolithic')
        over, eng = serve(mesh, stream(), **kw)
        assert eng.cache_mode == 'paged' and eng.paged.n_shards == 2
        assert eng.prefill_chunk == 4 and eng.n_chunks > 0
        assert over == mono, (over, mono)
        assert eng.scheduler.n_preempted > 0
        c1 = eng.prefill_compile_count()
        # new prompt lengths reuse the same chunk program(s): the count
        # must not grow with the length mix (<= 2 traces per cold/no-cold
        # cache variant, sharding-commit included)
        mono2, _ = serve(None, stream(extra=3), cache_mode='monolithic')
        over2, eng2 = serve(mesh, stream(extra=3), **kw)
        assert over2 == mono2
        assert eng2.prefill_compile_count() == c1, (
            eng2.prefill_compile_count(), c1)
        print('chunked sharded == single-device monolithic: OK')
    """.replace("__OVERSUB_WL__", repr(_OVERSUB_WL)), devices=2)


def test_per_slot_cache_decode_matches_scalar():
    """Per-slot timelines with equal lengths must equal the shared path."""
    cfg = smoke_variant(get("gemma2-9b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                              cfg.vocab_size)
    _, cache_s = M.prefill(params, cfg, toks, max_len=16)
    # build per-slot cache with vector cur_len
    cache_v = dict(cache_s)
    cache_v["cur_len"] = jnp.full((2,), 6, jnp.int32)
    nxt = jnp.asarray([[3], [7]], jnp.int32)
    ls, _ = M.decode_step(params, cfg, nxt, cache_s)
    lv, _ = M.decode_step(params, cfg, nxt, cache_v)
    np.testing.assert_allclose(np.asarray(ls), np.asarray(lv), atol=1e-5)
