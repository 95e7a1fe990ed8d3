"""Paged, ECF8-compressed KV cache: codec, kernel, allocator, end-to-end.

Acceptance (ISSUE 1): the compressed paged cache produces **bit-identical**
decode outputs to the monolithic cache on the same request stream, and
compressed cold pages cost <= 0.75x raw bf16 bytes on trained-like
(alpha-stable) synthetic data.

Acceptance (ISSUE 2): the same paged+compressed engine on a >= 2-device
CPU mesh (pool/table sharded over the batch axes, per-shard free lists)
emits **bit-identical** tokens and logits to the single-device monolithic
baseline.  Multi-device tests run in subprocesses (conftest: the main
pytest process must keep seeing 1 device).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from conftest import run_subprocess

from repro.configs import get, smoke_variant
from repro.core import theory
from repro.kvcache import OutOfPages, PagedKVCache, codec, kernels
from repro.models import model as M
from repro.runtime.monitor import KVCacheMonitor
from repro.serving import EngineConfig, GenerationEngine, Request
from repro.serving.engine import splice_fragment


def _rand_bits(rng, n, dtype_name):
    if dtype_name == "float8_e4m3fn":
        return rng.integers(0, 256, n).astype(np.uint8)
    if dtype_name == "bfloat16":
        return rng.integers(0, 1 << 16, n).astype(np.uint16)
    return rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)


_VIEW = {"float8_e4m3fn": (np.uint8, jnp.float8_e4m3fn),
         "bfloat16": (np.uint16, jnp.bfloat16),
         "float32": (np.uint32, np.float32)}


# --------------------------------------------------------------------------
# codec
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype_name", list(_VIEW))
@pytest.mark.parametrize("n", [1, 127, 128, 1000, 4096])
def test_codec_roundtrip_bit_exact(dtype_name, n):
    """Any bit content (NaNs included) roundtrips through host + jnp."""
    uint, view = _VIEW[dtype_name]
    bits = _rand_bits(np.random.default_rng(n), n, dtype_name)
    cp = codec.encode_page(bits.view(view))
    np.testing.assert_array_equal(
        np.asarray(codec.decode_page(cp)).view(uint), bits)
    got = codec.decode_pages_jnp(
        jnp.asarray(cp.payload)[None], jnp.asarray(cp.signmant)[None],
        jnp.asarray(cp.tables())[None], jnp.asarray(cp.perm)[None],
        n_elem=cp.n_elem, dtype_name=dtype_name)
    np.testing.assert_array_equal(np.asarray(got)[0].view(uint), bits)


def test_codec_ratio_alpha_stable_bf16():
    """Acceptance: cold-page bytes <= 0.75x raw bf16 on trained-like data."""
    for alpha, seed in [(1.9, 0), (1.7, 1), (1.5, 2)]:
        v = theory.sample_alpha_stable((16384,), alpha=alpha, seed=seed)
        page = np.asarray(jnp.asarray(v * 0.15, jnp.bfloat16))
        cp = codec.encode_page(page)
        np.testing.assert_array_equal(
            np.asarray(codec.decode_page(cp)).view(np.uint16),
            page.view(np.uint16))
        assert cp.ratio() <= 0.75, (alpha, cp.ratio())


def test_kernel_matches_jnp_and_oracle():
    """Pallas decode (interpret) == jnp decode == per-lane host oracle,
    across pages with different codebooks zero-padded to one stride."""
    rng = np.random.default_rng(7)
    pages = [np.asarray(jnp.asarray(rng.standard_normal(2048) * s,
                                    jnp.bfloat16))
             for s in (0.05, 1.0, 300.0)]
    cps = [codec.encode_page(p) for p in pages]
    sb = max(c.stride for c in cps)
    pay = np.zeros((len(cps), sb, codec.LANES), np.uint8)
    for i, c in enumerate(cps):
        pay[i, : c.stride] = c.payload
    args = (jnp.asarray(pay), jnp.asarray(np.stack([c.signmant for c in cps])),
            jnp.asarray(np.stack([c.tables() for c in cps])),
            jnp.asarray(np.stack([c.perm for c in cps])))
    got_k = kernels.decode_pages(*args, n_elem=2048, dtype_name="bfloat16",
                                 interpret=True)
    got_j = codec.decode_pages_jnp(*args, n_elem=2048, dtype_name="bfloat16")
    for i, (p, c) in enumerate(zip(pages, cps)):
        want = p.view(np.uint16)
        np.testing.assert_array_equal(np.asarray(got_k)[i].view(np.uint16),
                                      want)
        np.testing.assert_array_equal(np.asarray(got_j)[i].view(np.uint16),
                                      want)
        np.testing.assert_array_equal(
            np.asarray(codec.decode_page(c)).view(np.uint16), want)


# (exponent bits, sign shift, mantissa bits) of each page dtype's bit view
_FIELDS = {"float8_e4m3fn": (4, 7, 3), "bfloat16": (8, 15, 7),
           "float32": (8, 31, 23)}


def _page_with_n_exponents(rng, dtype_name, k, n):
    """An ``n``-element page whose exponent field takes ``k`` distinct
    values (every field, Inf/NaN included, when ``k`` is all of them);
    sign and mantissa bits random."""
    exp_bits, sign_at, mant_bits = _FIELDS[dtype_name]
    uint, view = _VIEW[dtype_name]
    fields = rng.permutation(1 << exp_bits)[:k]
    exp = np.concatenate([fields, rng.choice(fields, n - k)])
    sign = rng.integers(0, 2, n).astype(np.uint64)
    mant = rng.integers(0, 1 << mant_bits, n, dtype=np.uint64)
    bits = (sign << sign_at) | (exp.astype(np.uint64) << mant_bits) | mant
    return bits.astype(uint).view(view)


@pytest.mark.parametrize("dtype_name", list(_VIEW))
def test_kernel_permutes_every_active_symbol_count(dtype_name):
    """The kernel's in-register canonical permutation == the jnp decode's
    gather == the host oracle, bit for bit, on pages whose active-symbol
    count runs from 1 to every exponent field, beside an all-zero
    never-written cold slot in the same batch."""
    n_sym = 1 << _FIELDS[dtype_name][0]
    uint, _ = _VIEW[dtype_name]
    rng = np.random.default_rng(n_sym)
    counts = sorted({1, 2, 3, n_sym // 2 + 1, n_sym - 1, n_sym})
    pages = [_page_with_n_exponents(rng, dtype_name, k, 2048)
             for k in counts]
    cps = [codec.encode_page(p) for p in pages]
    assert [c.n_active for c in cps] == counts
    n = len(cps) + 1                     # the last slot was never written
    pay = np.zeros((n, max(c.stride for c in cps), codec.LANES), np.uint8)
    for i, c in enumerate(cps):
        pay[i, : c.stride] = c.payload
    sm = np.zeros((n, cps[0].signmant.size), np.uint8)
    tab = np.zeros((n, *cps[0].tables().shape), np.int32)
    perm = np.zeros((n, n_sym), np.int32)
    for i, c in enumerate(cps):
        sm[i], tab[i], perm[i] = c.signmant, c.tables(), c.perm
    args = tuple(jnp.asarray(a) for a in (pay, sm, tab, perm))
    got_k = np.asarray(kernels.decode_pages(
        *args, n_elem=2048, dtype_name=dtype_name, interpret=True)).view(uint)
    got_j = np.asarray(codec.decode_pages_jnp(
        *args, n_elem=2048, dtype_name=dtype_name)).view(uint)
    np.testing.assert_array_equal(got_k, got_j)
    for i, (p, c) in enumerate(zip(pages, cps)):
        np.testing.assert_array_equal(got_k[i], p.view(uint))
        np.testing.assert_array_equal(
            np.asarray(codec.decode_page(c)).view(uint), p.view(uint))
    np.testing.assert_array_equal(got_k[-1], 0)


def test_kernel_splits_page_batches_beyond_smem(monkeypatch):
    """Batches of more pages than one kernel call's SMEM table budget are
    decoded in several calls, bit-identical to the jnp decode."""
    monkeypatch.setattr(kernels, "_MAX_PAGES_PER_CALL", 2)
    rng = np.random.default_rng(8)
    cps = [codec.encode_page(np.asarray(jnp.asarray(
        rng.standard_normal(1024) * s, jnp.bfloat16)))
        for s in (0.1, 1.0, 10.0, 100.0, 1000.0)]
    sb = max(c.stride for c in cps)
    pay = np.zeros((len(cps), sb, codec.LANES), np.uint8)
    for i, c in enumerate(cps):
        pay[i, : c.stride] = c.payload
    args = (jnp.asarray(pay), jnp.asarray(np.stack([c.signmant for c in cps])),
            jnp.asarray(np.stack([c.tables() for c in cps])),
            jnp.asarray(np.stack([c.perm for c in cps])))
    kernels.decode_page_indices_pallas.clear_cache()
    got = kernels.decode_pages(*args, n_elem=1024, dtype_name="bfloat16")
    kernels.decode_page_indices_pallas.clear_cache()
    want = codec.decode_pages_jnp(*args, n_elem=1024, dtype_name="bfloat16")
    np.testing.assert_array_equal(np.asarray(got).view(np.uint16),
                                  np.asarray(want).view(np.uint16))


# --------------------------------------------------------------------------
# allocator
# --------------------------------------------------------------------------

def test_allocator_lifecycle_and_garbage_page():
    cfg = smoke_variant(get("qwen3-8b"))
    pkv = PagedKVCache(cfg, 2, 32, dtype=jnp.float32, page_size=8, n_pages=5)
    assert pkv.pages_per_slot == 4
    assert 0 not in pkv._free[0]       # garbage page is never allocatable
    assert pkv.pages_needed(7) == 1 and pkv.pages_needed(8) == 2
    assert pkv.can_admit(20)
    tiny = PagedKVCache(cfg, 2, 32, dtype=jnp.float32, page_size=8,
                        n_pages=3)
    assert not tiny.can_admit(20)      # needs 3 pages, pool holds 2

    params = M.init_params(jax.random.PRNGKey(0), cfg)
    cache = pkv.init_cache()
    _, frag = M.prefill(params, cfg, jnp.ones((1, 9), jnp.int32), max_len=32)
    cache = pkv.admit(cache, 0, frag, 9)
    assert pkv._slot_pages[0] == [1, 2] and pkv.free_pages == 2
    cache = pkv.ensure(cache, 0, 16)   # write pos 16 -> third page
    assert len(pkv._slot_pages[0]) == 3 and pkv.free_pages == 1
    with pytest.raises(OutOfPages):
        pkv.admit(cache, 1, frag, 9)   # needs 2 pages, 1 free
    cache = pkv.release(cache, 0)
    assert pkv.free_pages == 4 and not pkv._slot_pages
    assert np.all(np.asarray(cache["page_table"]) == 0)


def test_ensure_is_all_or_nothing():
    """A multi-page ``ensure`` that runs dry part-way raises OutOfPages
    with the slot's page list, the free list and the page table exactly as
    before (the caller keeps its old cache); a retry once pages are free
    maps every new page into the page table."""
    cfg = smoke_variant(get("qwen3-8b"))
    pkv = PagedKVCache(cfg, 2, 32, dtype=jnp.float32, page_size=4,
                       n_pages=6)
    cache = pkv.admit_slot(pkv.init_cache(), 1, 1)
    cache = pkv.admit_slot(cache, 0, 1)        # slot 0: 1 page, 3 free
    pages, free = list(pkv._slot_pages[0]), [list(f) for f in pkv._free]
    with pytest.raises(OutOfPages):
        pkv.ensure(cache, 0, 19)               # needs 4 more pages
    assert pkv._slot_pages[0] == pages and pkv._free == free
    cache = pkv.release(cache, 1)              # frees one page
    cache = pkv.ensure(cache, 0, 19)
    row = np.asarray(cache["page_table"])[0]
    assert len(pkv._slot_pages[0]) == 5
    assert row[:5].tolist() == pkv._slot_pages[0]


def test_page_write_drops_swap_sentinels():
    """The decode-step K/V write of a slot whose row holds swap sentinels
    (negative ids, left by a preempted request) is dropped, never wrapped
    onto a live page."""
    from repro.kvcache.paged import page_write
    pool = jnp.zeros((6, 2, 4, 8), jnp.float32)
    table = jnp.asarray([[3, 4], [-1, -2]], jnp.int32)
    cur_len = jnp.asarray([1, 2], jnp.int32)
    out = page_write(pool, table, cur_len, jnp.ones((2, 2, 1, 8)))
    touched = np.flatnonzero(np.asarray(out).reshape(6, -1).any(axis=1))
    assert touched.tolist() == [3]


def test_fault_path_runs_page_kernel_in_backend_mode(monkeypatch):
    """A swap fault restores pages through ``kernels.decode_pages`` with no
    ``interpret`` override: the kernel's mode comes from the backend
    helper (compiled on a TPU, interpreted here)."""
    calls, modes = [], []
    real_decode, real_mode = kernels.decode_pages, kernels.interpret_mode

    def spy_decode(*args, **kw):
        calls.append(kw.get("interpret"))
        return real_decode(*args, **kw)

    def spy_mode():
        modes.append(real_mode())
        return modes[-1]

    monkeypatch.setattr(kernels, "decode_pages", spy_decode)
    monkeypatch.setattr(kernels, "interpret_mode", spy_mode)
    kernels.decode_page_indices_pallas.clear_cache()   # re-resolve the mode
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = GenerationEngine(params, cfg, config=EngineConfig(
        max_batch=2, max_len=48, page_size=8, n_pages=5,
        swap_bytes=1 << 28))
    for i in range(6):
        eng.submit(Request(prompt=[i + 1] * (7 + 3 * (i % 3)),
                           max_new_tokens=10, id=14_000 + i))
    eng.run()
    assert eng.paged.swap.swap_in_bytes > 0
    assert calls and all(c is None for c in calls)
    assert modes and all(m == (jax.default_backend() != "tpu")
                         for m in modes)


# --------------------------------------------------------------------------
# end-to-end: paged + compressed == monolithic, bit for bit
# --------------------------------------------------------------------------

def _mixed_stream():
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [5, 6, 7], [9, 10] * 4,
               [11, 12, 13], [2] * 7, [40, 41]]
    news = [30, 25, 20, 12, 18, 6]
    return prompts, news


def test_engine_paged_bit_identical_to_monolithic():
    """Same mixed-length stream through all three cache modes -> identical
    tokens (greedy decode is bit-exact end to end)."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts, news = _mixed_stream()

    def run(**kw):
        eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=2, max_len=64, **kw))
        reqs = [Request(prompt=p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs], eng

    mono, _ = run(cache_mode="monolithic")
    paged, ep = run(cache_mode="paged", page_size=16)
    comp, ec = run(cache_mode="paged", page_size=16, compress_cold=True)
    assert ep.cache_mode == "paged" and ec.cache_mode == "paged"
    assert mono == paged
    assert mono == comp
    # the compressed run actually exercised the cold pool
    assert ec.paged.compress and not ec.paged._cold_bytes  # all released
    assert ec.paged.free_pages == ec.paged.n_pages - 1     # all returned


def test_decode_step_logits_bit_identical_with_compression():
    """Stronger than token equality: the jitted decode step's logits are
    bit-identical between the monolithic cache and a paged cache whose
    cold pages live entropy-coded (decode-on-use in-graph)."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    B, max_len, ps = 2, 32, 8
    pkv = PagedKVCache(cfg, B, max_len, dtype=jnp.float32, page_size=ps,
                       compress_cold=True)
    cache_p = pkv.init_cache()
    cache_m = M.init_cache(cfg, B, max_len, dtype=jnp.float32, per_slot=True)
    lens = [11, 6]
    for slot, T in enumerate(lens):
        toks = jnp.arange(1, T + 1, dtype=jnp.int32)[None] + 3 * slot
        _, frag = M.prefill(params, cfg, toks, max_len=max_len)
        cache_p = pkv.admit(cache_p, slot, frag, T)
        cache_m = splice_fragment(cache_m, frag, slot)
        cache_m["cur_len"] = cache_m["cur_len"].at[slot].set(T)

    tok = jnp.asarray([[17], [29]], jnp.int32)
    for step in range(12):
        for slot in range(B):
            cache_p = pkv.ensure(cache_p, slot, lens[slot])
        lp, cache_p = M.decode_step(params, cfg, tok, cache_p)
        lm, cache_m = M.decode_step(params, cfg, tok, cache_m)
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(lm))
        for slot in range(B):
            lens[slot] += 1
            cache_p = pkv.compress_cold_pages(cache_p, slot, lens[slot])
        tok = (tok + 7) % cfg.vocab_size
    assert pkv._cold_bytes, "no page was ever compressed - test is vacuous"


def test_engine_undersized_pool_serializes_admission():
    """An oversubscribed pool (n_pages < worst case) defers admission
    until a slot releases its pages — and still matches monolithic."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[i + 1] * 9 for i in range(3)]

    def run(**kw):
        eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=2, max_len=32, **kw))
        reqs = [Request(prompt=p, max_new_tokens=7) for p in prompts]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs], eng

    mono, _ = run(cache_mode="monolithic")
    # pool of 2 usable pages = exactly one request's working set
    tight, eng = run(cache_mode="paged", page_size=8, n_pages=3)
    assert mono == tight
    # 6 decode tokens per request (first comes from prefill), no overlap
    assert eng.steps >= 18
    assert eng.paged.free_pages == 2  # all pages returned


# --------------------------------------------------------------------------
# mesh-sharded paged cache (ISSUE 2)
# --------------------------------------------------------------------------

def test_allocator_per_shard_free_lists():
    """Pages and cold slots partition into per-shard ranges; exhaustion is
    per shard and OutOfPages names the shard that ran dry."""
    cfg = smoke_variant(get("qwen3-8b"))
    pkv = PagedKVCache(cfg, 4, 32, dtype=jnp.float32, page_size=8,
                       n_pages=8, n_shards=2)
    assert pkv.pages_per_shard == 4
    assert pkv._free[0] == [3, 2, 1]       # shard 0 loses id 0 (garbage)
    assert pkv._free[1] == [7, 6, 5, 4]
    assert pkv.shard_of_slot(1) == 0 and pkv.shard_of_slot(2) == 1

    params = M.init_params(jax.random.PRNGKey(0), cfg)
    cache = pkv.init_cache()
    _, frag = M.prefill(params, cfg, jnp.ones((1, 9), jnp.int32), max_len=32)
    cache = pkv.admit(cache, 2, frag, 9)   # slot 2 -> shard 1 ids only
    assert pkv._slot_pages[2] == [4, 5]
    cache = pkv.admit(cache, 3, frag, 9)   # shard 1 now fully allocated
    with pytest.raises(OutOfPages, match="shard 1"):
        pkv.ensure(cache, 2, 16)           # slot 2 needs a third page
    with pytest.raises(OutOfPages, match="shard 1"):
        pkv.admit(cache, 3, frag, 9)
    # shard 0 is untouched: its slots still admit
    assert pkv.can_admit(9, slot=0) and not pkv.can_admit(9, slot=2)
    assert pkv.free_pages_per_shard == [3, 0]
    cache = pkv.release(cache, 3)
    assert pkv.free_pages_per_shard == [3, 2]  # returned to shard 1's list


@pytest.mark.slow
def test_engine_sharded_paged_bit_identical_to_monolithic():
    """Acceptance (ISSUE 2): the sharded paged+compressed engine on 2- and
    4-device data meshes emits bit-identical tokens to the single-device
    monolithic baseline."""
    run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get, smoke_variant
        from repro.models import model as M
        from repro.serving import EngineConfig, GenerationEngine, Request

        cfg = smoke_variant(get('qwen3-8b'))
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        prompts = [[1,2,3,4,5,6,7,8,9,10], [5,6,7], [9,10]*4,
                   [11,12,13], [2]*7, [40,41]]
        news = [18, 12, 10, 8, 9, 6]

        def run(mesh=None, **kw):
            eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=4, max_len=64,
                                   mesh=mesh, **kw))
            reqs = [Request(prompt=p, max_new_tokens=n)
                    for p, n in zip(prompts, news)]
            for r in reqs:
                eng.submit(r)
            eng.run()
            assert all(r.done for r in reqs)
            return [r.out_tokens for r in reqs], eng

        mono, _ = run(cache_mode='monolithic')
        for n_dev in (2, 4):
            mesh = Mesh(np.array(jax.devices()[:n_dev]), ('data',))
            got, eng = run(mesh=mesh, cache_mode='paged', page_size=16,
                           compress_cold=True)
            assert eng.cache_mode == 'paged', 'fell back to monolithic'
            assert eng.paged.n_shards == n_dev
            assert got == mono, (n_dev, got, mono)
            assert eng.paged.free_pages == eng.paged.n_pages - 1

        # hybrid arch: local-attention ring buffers stay monolithic
        # per-slot leaves (GSPMD batch-sharded) next to the paged pools
        cfg = smoke_variant(get('gemma2-9b'))
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        mono, _ = run(cache_mode='monolithic')
        mesh = Mesh(np.array(jax.devices()[:2]), ('data',))
        got, eng = run(mesh=mesh, cache_mode='paged', page_size=16,
                       compress_cold=True)
        assert eng.cache_mode == 'paged' and got == mono
        print('sharded paged engine == single-device monolithic: OK')
    """, devices=4)


@pytest.mark.slow
def test_sharded_decode_step_logits_bit_identical():
    """Stronger than token equality: jitted decode-step logits on a
    2-device data mesh (paged + cold pages entropy-coded per shard) are
    bit-identical to the single-device monolithic cache."""
    run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get, smoke_variant
        from repro.kvcache import PagedKVCache
        from repro.models import model as M
        from repro.runtime import sharding as SH
        from repro.serving.engine import splice_fragment

        cfg = smoke_variant(get('qwen3-8b'))
        params = M.init_params(jax.random.PRNGKey(1), cfg)
        B, max_len, ps = 2, 32, 8
        mesh = Mesh(np.array(jax.devices()[:2]), ('data',))
        pkv = PagedKVCache(cfg, B, max_len, dtype=jnp.float32, page_size=ps,
                           compress_cold=True, n_shards=2)
        cache_p = pkv.init_cache()
        cache_m = M.init_cache(cfg, B, max_len, dtype=jnp.float32,
                               per_slot=True)
        lens = [11, 6]
        for slot, T in enumerate(lens):
            toks = jnp.arange(1, T + 1, dtype=jnp.int32)[None] + 3 * slot
            _, frag = M.prefill(params, cfg, toks, max_len=max_len)
            cache_p = pkv.admit(cache_p, slot, frag, T)
            cache_m = splice_fragment(cache_m, frag, slot)
            cache_m['cur_len'] = cache_m['cur_len'].at[slot].set(T)
        cache_p = jax.device_put(cache_p, SH.named(
            mesh, SH.cache_pspecs(cfg, cache_p, mesh)))
        dec = jax.jit(lambda p, t, c: M.decode_step(p, cfg, t, c, mesh=mesh))
        tok = jnp.asarray([[17], [29]], jnp.int32)
        for step in range(12):
            for slot in range(B):
                cache_p = pkv.ensure(cache_p, slot, lens[slot])
            lp, cache_p = dec(params, tok, cache_p)
            lm, cache_m = M.decode_step(params, cfg, tok, cache_m)
            np.testing.assert_array_equal(np.asarray(lp), np.asarray(lm))
            for slot in range(B):
                lens[slot] += 1
                cache_p = pkv.compress_cold_pages(cache_p, slot, lens[slot])
            tok = (tok + 7) % cfg.vocab_size
        assert pkv._cold_bytes, 'no page went cold - test is vacuous'
        print('sharded paged+cold logits bit-identical: OK')
    """, devices=2)


@pytest.mark.slow
def test_paged_model_axis_and_sharded_kernel():
    """The model-axis page split (local attend-stats + cross-shard stat
    merge) matches the single-device paged decode, and the sharded Pallas
    cold-page decode is bit-exact vs the unsharded kernel."""
    run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get, smoke_variant
        from repro.kvcache import PagedKVCache, codec, kernels
        from repro.models import model as M
        from repro.runtime import sharding as SH

        # sharded Pallas decode == unsharded, bit for bit
        rng = np.random.default_rng(3)
        pages = [np.asarray(jnp.asarray(rng.standard_normal(2048) * s,
                                        jnp.bfloat16))
                 for s in (0.05, 1.0, 300.0, 7.0)]
        cps = [codec.encode_page(p) for p in pages]
        sb = max(c.stride for c in cps)
        pay = np.zeros((len(cps), sb, codec.LANES), np.uint8)
        for i, c in enumerate(cps):
            pay[i, : c.stride] = c.payload
        args = (jnp.asarray(pay),
                jnp.asarray(np.stack([c.signmant for c in cps])),
                jnp.asarray(np.stack([c.tables() for c in cps])),
                jnp.asarray(np.stack([c.perm for c in cps])))
        mesh_d = Mesh(np.array(jax.devices()[:2]), ('data',))
        got = kernels.decode_pages_sharded(*args, mesh_d, n_elem=2048,
                                           dtype_name='bfloat16')
        want = kernels.decode_pages(*args, n_elem=2048,
                                    dtype_name='bfloat16')
        np.testing.assert_array_equal(np.asarray(got).view(np.uint16),
                                      np.asarray(want).view(np.uint16))

        # model-axis combine: decode steps match the single-device paged
        # path (flash-merge across shards -> allclose, not bit-equal)
        cfg = smoke_variant(get('qwen3-8b'))
        params = M.init_params(jax.random.PRNGKey(1), cfg)
        B, max_len, ps = 2, 32, 8
        mesh = Mesh(np.array(jax.devices()[:2]), ('model',))
        pkv = PagedKVCache(cfg, B, max_len, dtype=jnp.float32, page_size=ps,
                           compress_cold=True)
        cache_s = pkv.init_cache()
        pkv1 = PagedKVCache(cfg, B, max_len, dtype=jnp.float32, page_size=ps,
                            compress_cold=True)
        cache_1 = pkv1.init_cache()
        lens = [11, 6]
        for slot, T in enumerate(lens):
            toks = jnp.arange(1, T + 1, dtype=jnp.int32)[None] + 3 * slot
            _, frag = M.prefill(params, cfg, toks, max_len=max_len)
            cache_s = pkv.admit(cache_s, slot, frag, T)
            cache_1 = pkv1.admit(cache_1, slot, frag, T)
        dec = jax.jit(lambda p, t, c: M.decode_step(p, cfg, t, c, mesh=mesh))
        tok = jnp.asarray([[17], [29]], jnp.int32)
        for step in range(10):
            for slot in range(B):
                cache_s = pkv.ensure(cache_s, slot, lens[slot])
                cache_1 = pkv1.ensure(cache_1, slot, lens[slot])
            ls, cache_s = dec(params, tok, cache_s)
            l1, cache_1 = M.decode_step(params, cfg, tok, cache_1)
            np.testing.assert_allclose(np.asarray(ls), np.asarray(l1),
                                       atol=3e-4)
            for slot in range(B):
                lens[slot] += 1
                cache_s = pkv.compress_cold_pages(cache_s, slot, lens[slot])
                cache_1 = pkv1.compress_cold_pages(cache_1, slot,
                                                   lens[slot])
            tok = (tok + 7) % cfg.vocab_size
        assert pkv._cold_bytes
        print('model-axis paged decode + sharded kernel: OK')
    """, devices=2)


def test_paged_memory_stats_beat_monolithic():
    """Short requests hold only the pages they wrote."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    mon = KVCacheMonitor()
    eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=4, max_len=64,
                           page_size=16, compress_cold=True, kv_monitor=mon))
    for i in range(6):
        eng.submit(Request(prompt=[i + 1, i + 2], max_new_tokens=6))
    eng.run()
    s = mon.summary()
    assert s["steps"] > 0
    assert s["peak_paged_bytes"] < s["monolithic_bytes"]
    assert s["peak_pages_in_use"] <= 6
