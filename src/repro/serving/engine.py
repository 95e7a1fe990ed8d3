"""Continuous-batching generation engine (the serving driver).

The paper's RQ2 regime: weight-streaming-bound batched decode under a fixed
memory budget — ECF8's smaller weights buy a bigger batch, and the batch is
what buys throughput.  The engine keeps a fixed (max_batch, max_len) cache
(static shapes: one compiled decode step serves the whole run) and fills it
with requests continuously:

  * every slot has its own timeline (per-slot ``cur_len``, see
    ``model.init_cache(per_slot=True)``) — a finished request's slot is
    immediately reused by the next queued request without draining the batch;
  * a new request is prefilled as a single-row batch and its cache fragment
    is spliced into the batched cache at the free slot (stacked leaves at
    batch-axis 1, tail leaves at 0);
  * decode steps always run the full batch; inactive slots compute garbage
    that is never read (standard static-batch padding trade).

Weights may be an ECF8-compressed pytree (``core.store.compress_tree``) —
decode-on-use happens inside the same jitted step.

The default cache is **paged** (``repro.kvcache``): attention layers write
through a shared page table into fixed-size pages, short requests only
hold the pages they wrote, and full (cold) pages can be entropy-coded
losslessly in place (``compress_cold=True``) with in-graph decode-on-use —
the cache-side mirror of the paper's weight story.

With a **swap tier** (``swap_bytes``) the device pool stops being a hard
ceiling: admission is scheduled against *virtual* capacity
(``serving.scheduler.Scheduler`` — priority classes, FIFO within a
class), and when pages run out a whole victim request is compressed and
swapped to host memory (``kvcache.swap.SwapStore``), requeued, and later
resumed by faulting its pages back — bit-identical to a run that was
never preempted, because page restore is lossless and sampling keys are
folded from ``(rng_seed, request.id, position)`` only.  The engine
faults every active slot fully resident before each decode step
(fault-before-gather), so the jitted graph never sees a swapped page.

With **chunked prefill** (``prefill_chunk=C``) the compute side of
admission is rebuilt around continuous batching: each admitted prompt is
split into fixed-size ``C``-token chunks (padded, so every chunk call
has one static shape — **exactly one prefill compilation per (cfg,
mesh, max_len, C)** regardless of prompt length, where the whole-prompt
path retraces per length), chunk K/V is appended straight into the
slot's pages across chunk boundaries (``models.model.prefill_chunk``),
and prefill chunks interleave with decode steps under a per-step
**prefill token budget** (``prefill_budget``, default ``C``): every
engine step spends at most ~budget prompt tokens on prefill — draining
mid-prefill slots first (FIFO within priority), then admitting new
work — before running one batched decode step for the decode-phase
slots, so a long prompt can no longer stall every decoding request
behind a monolithic prefill (Sarathi/vLLM-style scheduling).  A slot
mid-prefill participates in the batched decode step as a masked row
(its garbage write lands at the next chunk's first position and is
overwritten; its timeline is rolled back after the step) and can be
preempted like any other slot — ``Preempted.prefill_pos`` records the
resume point, and the continuation is bit-identical to an unchunked
run.  Chunked prefill requires the paged cache and an architecture
whose every layer pages ('attn'/'nope'); other configs fall back to
whole-prompt prefill.

Under a JAX **mesh** the paged cache stays paged: the page pool, cold
pool, page table and per-slot timelines shard over the mesh's batch axes
(``runtime.sharding.batch_axes``), the allocator keeps one free list per
batch shard so every slot's pages are local to its shard, and the decode
step routes through ``models.decode_sharded.paged_decode_attention_
sharded`` (fully local page scatter/gather per batch shard; an optional
``model`` axis splits each slot's pages and merges softmax stats).  On a
pure batch-axes mesh the sharded engine is **bit-identical** to the
single-device run.  ``cache_mode="monolithic"`` keeps the original
contiguous cache; encoder-decoders, pure recurrent stacks (nothing to
page) and meshes whose batch-axes size does not divide ``max_batch``
still fall back to it.
"""
from __future__ import annotations

import itertools
import time
import warnings
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.kvcache import OutOfPages, PagedKVCache, SwapStore
from repro.kvcache.paged import restore_cold, strip_cold
from repro.kvcache.swap import SwapExhausted
from repro.models import model as M
from repro.runtime import sharding as SH
from repro.runtime.tracing import span
from . import spec as SPEC
from .config import EngineConfig
from .sampler import greedy, request_key, root_key, sample_logits
from .scheduler import Preempted, Scheduler

_ids = itertools.count()


@dataclass
class Request:
    prompt: list
    max_new_tokens: int = 32
    temperature: float = 0.0
    priority: int = 0           # higher runs first; FIFO within a class
    id: int = field(default_factory=lambda: next(_ids))
    out_tokens: list = field(default_factory=list)
    done: bool = False


# one jitted prefill/decode pair per (cfg, mesh, max_len) — engines are
# cheap, throwaway objects (tests build hundreds); sharing the jit cache
# across instances avoids recompiling identical programs
_STEP_CACHE: dict = {}
# one jitted chunk-prefill per (cfg, mesh, max_len, chunk): slot, start
# and n_valid are traced, so this single entry serves every prompt
# length — the whole point of the fixed chunk shape
_CHUNK_CACHE: dict = {}
# one jitted speculative verify per (cfg, mesh, max_len, k + 1): the
# verify width is static, slot/n_valid are traced — one compilation
# serves every request, acceptance length and timeline position
_VERIFY_CACHE: dict = {}


def _jitted_steps(cfg: ArchConfig, mesh, max_len: int):
    key = (cfg, mesh, max_len)
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = (
            jax.jit(lambda p, t, c: M.decode_step(p, cfg, t, c, mesh=mesh)),
            jax.jit(lambda p, t: M.prefill(p, cfg, t, mesh=mesh,
                                           max_len=max_len)))
    return _STEP_CACHE[key]


def _jitted_chunk(cfg: ArchConfig, mesh, max_len: int, chunk: int):
    key = (cfg, mesh, max_len, chunk)
    if key not in _CHUNK_CACHE:
        _CHUNK_CACHE[key] = jax.jit(
            lambda p, t, c, s, n: M.prefill_chunk(p, cfg, t, c, s, n,
                                                  mesh=mesh))
    return _CHUNK_CACHE[key]


def _jitted_verify(cfg: ArchConfig, mesh, max_len: int, width: int):
    key = (cfg, mesh, max_len, width)
    if key not in _VERIFY_CACHE:
        _VERIFY_CACHE[key] = jax.jit(
            lambda p, t, c, s, n: M.verify_chunk(p, cfg, t, c, s, n,
                                                 mesh=mesh))
    return _VERIFY_CACHE[key]


def compile_count(fn) -> int:
    """Traced-program count of a jitted step.  The perf-smoke tier and the
    recompile regression test read this off ``engine._jitted_steps``/
    ``_jitted_chunk`` entries to pin "exactly one prefill compilation per
    chunk shape"."""
    return int(fn._cache_size())


def _splice(full, frag, slot: int, path_names):
    """Insert a single-request cache fragment at ``slot`` of the batch.

    ``path_names`` are the stringified pytree path keys of the leaf; the
    batch axis is inferred from them (see :func:`splice_fragment`)."""
    axis = 1 if "units" in path_names else 0
    if "cur_len" in path_names:
        return full.at[slot].set(frag)
    return jax.lax.dynamic_update_slice_in_dim(
        full, frag.astype(full.dtype), slot, axis=axis)


def splice_fragment(cache, frag, slot: int):
    """Splice a single-request prefill fragment into the monolithic batched
    cache.

    Leaf placement is dispatched on the pytree *path names* (the cache is
    a plain dict tree, no metadata): leaves under ``"units"`` are
    scan-stacked ``(n_units, B, ...)`` so the batch sits at axis 1; leaves
    under ``"tail"`` (and everything else) carry the batch at axis 0; the
    ``"cur_len"`` leaf is a per-slot ``(B,)`` vector indexed directly.
    ``frag`` must have the same treedef with batch size 1."""
    flat_full, treedef = jax.tree_util.tree_flatten_with_path(cache)
    flat_frag = jax.tree_util.tree_flatten(frag)[0]
    new_leaves = []
    for (path, full), fr in zip(flat_full, flat_frag):
        names = [str(getattr(k, "key", getattr(k, "idx", k)))
                 for k in path]
        new_leaves.append(_splice(full, fr, slot, names))
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


class GenerationEngine:
    def __init__(self, params, cfg: ArchConfig,
                 config: EngineConfig | None = None, **legacy):
        """``config`` (``serving.config.EngineConfig``) is the primary
        constructor input: every engine option lives there as a grouped,
        validated field, and the feature-gating matrix (chunked / mesh /
        spec / prefix interactions) is applied by
        ``EngineConfig.validate`` — see that module's docstring for the
        matrix and the per-field semantics.  Passing the old flat
        keyword arguments still works via a deprecation shim
        (``GenerationEngine(params, cfg, max_batch=8, ...)`` becomes
        ``EngineConfig(max_batch=8, ...)`` with a ``DeprecationWarning``).

        Incompatible feature requests warn and fall back here exactly as
        before (the warnings now originate from ``validate``); callers
        that want errors instead validate strictly up front, like
        ``launch/serve.py`` does at argument-parse time."""
        if legacy:
            if config is not None:
                raise TypeError(
                    "pass either config=EngineConfig(...) or legacy "
                    "keyword arguments, not both")
            warnings.warn(
                "GenerationEngine(params, cfg, **kwargs) is deprecated; "
                "pass config=EngineConfig(...) instead",
                DeprecationWarning, stacklevel=2)
            config = EngineConfig(**legacy)
        elif config is None:
            config = EngineConfig()
        if (config.draft_params is None) != (config.draft_cfg is None):
            raise ValueError(
                "draft_params and draft_cfg must be provided together")
        config = config.validate(cfg)
        self.config = config
        # place the weights once: replicated over a mesh (weights left on
        # one device would be resharded by every step), otherwise on the
        # default device (host arrays move in one transfer per leaf)
        place = (None if config.mesh is None
                 else NamedSharding(config.mesh, P()))
        self.params, self.cfg = jax.device_put(params, place), cfg
        max_batch = self.max_batch = config.max_batch
        max_len = self.max_len = config.max_len
        mesh = self.mesh = config.mesh
        self.slots: list = [None] * max_batch   # Request or None
        self._inflight: list = []               # submitted, not yet returned
        n_shards = config.n_shards()
        self.cache_mode = cache_mode = config.cache_mode
        self.kv_monitor = config.kv_monitor
        if cache_mode == "paged":
            self.paged = PagedKVCache(
                cfg, max_batch, max_len, dtype=jnp.dtype(cfg.dtype),
                page_size=config.page_size, n_pages=config.n_pages,
                compress_cold=config.compress_cold,
                n_cold_slots=config.n_cold_slots, n_shards=n_shards)
            if config.swap_bytes:
                self.paged.attach_swap(SwapStore(
                    capacity_bytes=(None if config.swap_bytes < 0
                                    else config.swap_bytes),
                    n_shards=n_shards))
            self.cache = self.paged.init_cache()
            if mesh is not None:
                # pin the pool/table/cur_len layout so every decode step
                # starts from the sharded placement instead of resharding
                self.cache = jax.device_put(self.cache, SH.named(
                    mesh, SH.cache_pspecs(cfg, self.cache, mesh)))
        else:
            self.paged = None
            self.cache = M.init_cache(cfg, max_batch, max_len,
                                      dtype=jnp.dtype(cfg.dtype),
                                      per_slot=True)
        chunk = self.prefill_chunk = config.prefill_chunk
        self.prefill_budget = config.prefill_budget
        self.prefix_sharing = config.prefix_sharing
        if self.prefix_sharing:
            self.paged.enable_prefix_sharing()
        self._prefill_pos: dict[int, int] = {}  # slot -> prompt tokens done
        self._prefill_order: list[int] = []     # admission order (FIFO)
        self._stalled_ids: set = set()          # self-preempted this step
        self.n_chunks = self.n_chunk_tokens = self.n_interleaved_steps = 0
        self.spec_on = config.draft_cfg is not None
        self.spec_k = config.spec_k
        if self.spec_on:
            self.draft_params = jax.device_put(config.draft_params, place)
            self.draft_cfg = draft_cfg = config.draft_cfg
            self._draft_decode, self._draft_prefill = _jitted_steps(
                draft_cfg, mesh, max_len)
            self._verify = _jitted_verify(cfg, mesh, max_len,
                                          self.spec_k + 1)
            # the paired draft cache: always monolithic (a small draft
            # needs no paging, and rejection rollback is a per-slot
            # snapshot re-splice — works for recurrent drafts too,
            # where no positional rollback exists)
            self.draft_cache = M.init_cache(
                draft_cfg, max_batch, max_len,
                dtype=jnp.dtype(draft_cfg.dtype), per_slot=True)
        self.n_spec_rounds = self.n_spec_drafted = self.n_spec_accepted = 0
        self.scheduler = Scheduler(paged=self.paged,
                                   preemption=config.preemption,
                                   chunk_tokens=chunk)
        self._host_len = [0] * max_batch        # next write position per slot
        # sampling keys fold (rng_seed, request.id, position) — the token
        # stream of a sampled request is a pure function of its own state,
        # independent of batching, scheduling and preemption
        self.rng0 = root_key(config.rng_seed)
        self._decode, self._prefill = _jitted_steps(cfg, mesh, max_len)
        self._chunk = (_jitted_chunk(cfg, mesh, max_len, chunk)
                       if chunk else None)
        self.last_tok = jnp.zeros((max_batch, 1), jnp.int32)
        # logits of the newest model call (prefill, chunk, verify or
        # decode step): read-only observation, e.g. for bit-exactness
        # probes against another weight format
        self.last_logits = None
        self.steps = 0
        # telemetry is host-side observation only (None = off): per-request
        # lifecycle spans, engine-phase spans and the metrics registry
        telemetry = self.tel = config.telemetry
        # the buffer the engine's spans also feed (runtime.tracing.span)
        self.tracer = telemetry.tracer if telemetry is not None else None
        self._submit_t: dict = {}       # request id -> submit wall time
        if telemetry is not None:
            from repro.runtime.monitor import KVCacheMonitor
            if self.kv_monitor is None:
                self.kv_monitor = KVCacheMonitor(
                    registry=telemetry.registry)
            self.scheduler.telemetry = telemetry
            if self.paged is not None:
                self.paged.telemetry = telemetry
                if self.paged.swap is not None:
                    self.paged.swap.attach_registry(telemetry.registry)
        # the jit caches are shared across engines: remember the counts at
        # construction so compile events register only when *this* engine
        # triggers a trace
        self._decode_compiles_seen = compile_count(self._decode)
        self._prefill_compiles_seen = self.prefill_compile_count()

    # -- telemetry ---------------------------------------------------------

    def _note_compiles(self):
        """Publish newly traced programs since the last check as compile
        events — decode retraces (e.g. the no-cold variant appearing)
        used to be invisible next to the prefill count."""
        tel = self.tel
        if tel is None:
            return
        n = compile_count(self._decode)
        if n > self._decode_compiles_seen:
            tel.registry.counter("serving_decode_compile_total").inc(
                n - self._decode_compiles_seen)
            if tel.tracer is not None:
                tel.tracer.instant("engine", "decode_compile",
                                   args={"step": self.steps})
            self._decode_compiles_seen = n
        n = self.prefill_compile_count()
        if n > self._prefill_compiles_seen:
            tel.registry.counter("serving_prefill_compile_total").inc(
                n - self._prefill_compiles_seen)
            if tel.tracer is not None:
                tel.tracer.instant("engine", "prefill_compile",
                                   args={"step": self.steps})
            self._prefill_compiles_seen = n

    def _sample_gauges(self):
        """Per-step level samples: queue depth and slot occupancy, as
        registry gauges (peak-tracking) and tracer counter tracks."""
        tel = self.tel
        if tel is None:
            return
        q = self.scheduler.waiting
        act = sum(1 for s in self.slots if s is not None)
        tel.registry.gauge("serving_queue_depth").set(q)
        tel.registry.gauge("serving_active_slots").set(act)
        if self.prefill_chunk:
            tel.registry.gauge("serving_prefilling_slots").set(
                len(self._prefill_pos))
        if self.prefix_sharing:
            tel.registry.gauge("prefix_shared_pages").set(
                self.paged.n_shared_pages())
        if tel.tracer is not None:
            tel.tracer.counter("serving_queue_depth", q)
            tel.tracer.counter("serving_active_slots", act)

    # -- scheduling --------------------------------------------------------

    def load(self) -> int:
        """Requests this engine currently owns: occupied slots plus the
        scheduler backlog (queued + preempted).  The router's
        least-loaded placement signal."""
        return (sum(1 for s in self.slots if s is not None)
                + self.scheduler.waiting)

    def prefix_match_tokens(self, prompt) -> int:
        """Longest index-resident prefix of ``prompt`` this engine could
        adopt by reference (0 without prefix sharing) — the router's
        prefix-affinity placement signal.  Purely advisory: reading the
        index allocates nothing and changes no state."""
        if self.paged is None or self.paged.prefix is None:
            return 0
        return self.paged.match_prefix(list(prompt))

    def submit(self, req: Request):
        self.scheduler.submit(req)
        self._inflight.append(req)
        if self.tel is not None:
            self._submit_t[req.id] = time.perf_counter()
            self.tel.registry.counter(
                "serving_requests_submitted_total").inc()
            if self.tel.requests is not None:
                self.tel.requests.transition(req.id, "queued")

    def _start(self, slot: int, req: Request):
        """Prefill a fresh request and splice it into ``slot``."""
        tel = self.tel
        if tel is not None and tel.requests is not None:
            tel.requests.transition(req.id, "prefilling",
                                    args={"slot": slot})
        with span("engine.prefill", self.tracer, req=req.id,
                  tokens=len(req.prompt)) as sp:
            toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
            logits, frag = self._prefill(self.params, toks)
            self.last_logits = logits
            if self.paged is not None:
                self.cache = self.paged.admit(self.cache, slot, frag,
                                              len(req.prompt))
            else:
                self.cache = splice_fragment(self.cache, frag, slot)
            if self.spec_on:
                # spec-aware prefill: the paired draft consumes the prompt
                # too (its logits are unused — the first token is sampled
                # from the *target* prefill, identical to target-only)
                _, dfrag = self._draft_prefill(self.draft_params, toks)
                self.draft_cache = splice_fragment(self.draft_cache, dfrag,
                                                   slot)
            self._host_len[slot] = len(req.prompt)
            tok = self._sample_one(logits, req)
            req.out_tokens.append(int(tok))
            self.last_tok = self.last_tok.at[slot, 0].set(tok)
            self.slots[slot] = req
        if tel is not None:
            sub = self._submit_t.get(req.id)
            if sub is not None:
                tel.registry.histogram("serving_queue_wait_seconds").observe(
                    sp.t0 - sub)
                tel.registry.histogram("serving_ttft_seconds").observe(
                    sp.t0 + sp.seconds - sub)
            tel.registry.counter("serving_tokens_generated_total").inc()
            if tel.requests is not None:
                tel.requests.transition(req.id, "decoding")
            self._note_compiles()

    def _start_chunked(self, slot: int, req: Request):
        """Admit a request for chunked prefill: allocate its page grant
        (``Scheduler.admission_grant`` — the same count ``pick`` tested
        against) and enter the prefill phase; no prompt compute yet,
        chunks run under the step's token budget in
        :func:`_prefill_phase`.

        With prefix sharing, admission matches the prompt against the
        prefix index first: matched pages are adopted by reference
        (``admit_shared``) and prefill resumes at the match boundary —
        the matched positions are never recomputed."""
        grant = self.scheduler.admission_grant(req)
        matched = 0
        if self.prefix_sharing:
            self.cache, matched = self.paged.admit_shared(
                self.cache, slot, req.prompt, grant)
        else:
            self.cache = self.paged.admit_slot(self.cache, slot, grant)
        self._host_len[slot] = matched
        self._prefill_pos[slot] = matched
        self._prefill_order.append(slot)
        self.slots[slot] = req
        if self.tel is not None and self.prefix_sharing:
            reg = self.tel.registry
            reg.counter("prefix_hit_total" if matched
                        else "prefix_miss_total").inc()
            if matched:
                reg.counter("prefix_match_tokens_total").inc(matched)
        if self.tel is not None:
            sub = self._submit_t.get(req.id)
            if sub is not None:
                self.tel.registry.histogram(
                    "serving_queue_wait_seconds").observe(
                        time.perf_counter() - sub)
            if self.tel.requests is not None:
                self.tel.requests.transition(req.id, "prefilling",
                                             args={"slot": slot})

    def _resume(self, slot: int, st: Preempted):
        """Re-splice a preempted request: reinstall its page list, fault
        every page back (lossless restore), reinstall any non-paged
        per-slot state (hybrid archs) and rebuild the slot timeline —
        the continuation is bit-identical to an unpreempted run.  A
        mid-prefill record re-enters the prefill phase at
        ``st.prefill_pos`` instead of rejoining the decode batch."""
        tel = self.tel
        with span("engine.resume", self.tracer, req=st.req.id,
                  slot=slot) as sp:
            self.cache = self.paged.attach_slot(self.cache, slot, st.pages,
                                                st.skip)
            self.cache = self.paged.fault(self.cache, slot)
            if st.state:
                self.cache = self.paged.restore_slot_state(self.cache, slot,
                                                           st.state)
            if self.spec_on and st.draft_state is not None:
                # reinstall the paired draft-cache row (bit-exact: the
                # state never left its original bit pattern on the host)
                self._draft_restore(slot, st.draft_state)
            self.cache = dict(self.cache)
            self.cache["cur_len"] = self.cache["cur_len"].at[slot].set(
                st.host_len)
            self._host_len[slot] = st.host_len
            if st.prefill_pos is not None:
                self._prefill_pos[slot] = st.prefill_pos
                self._prefill_order.append(slot)
            else:
                self.last_tok = self.last_tok.at[slot, 0].set(st.last_tok)
            self.slots[slot] = st.req
            self.scheduler.n_resumed += 1
        if tel is not None:
            tel.registry.counter("serving_resumed_total").inc()
            tel.registry.histogram("serving_resume_seconds").observe(
                sp.seconds)
            if tel.requests is not None:
                tel.requests.transition(
                    st.req.id, ("prefilling" if st.prefill_pos is not None
                                else "decoding"),
                    args={"slot": slot, "resumed": True})

    def _preempt(self, slot: int) -> bool:
        """Swap out a whole active request and requeue it (front of its
        priority class).  Returns False — with the engine state intact —
        when the swap store cannot take the pages."""
        req = self.slots[slot]
        tel = self.tel
        store = self.paged.swap
        traffic = (store.swap_out_bytes, store.swap_in_bytes,
                   store.n_swap_out, store.n_swap_in)
        with span("engine.preempt", self.tracer, req=req.id,
                  slot=slot) as sp:
            try:
                self.cache = self.paged.evict(self.cache, slot)
            except SwapExhausted:
                # roll back any partially evicted pages (their device
                # space was just freed, so the fault cannot itself run
                # dry), and un-count the aborted attempt so the monitor
                # only reports swapping that actually happened
                self.cache = self.paged.fault(self.cache, slot)
                (store.swap_out_bytes, store.swap_in_bytes,
                 store.n_swap_out, store.n_swap_in) = traffic
                store.sync_registry()
                sp.done(aborted=1)
                return False
            state = self.paged.snapshot_slot_state(self.cache, slot)
            pages, skip = self.paged.detach_slot(slot)
            st = Preempted(req=req, pages=pages, skip=skip, state=state,
                           host_len=self._host_len[slot],
                           last_tok=int(self.last_tok[slot, 0]),
                           prefill_pos=self._prefill_pos.get(slot),
                           draft_state=(self._draft_snapshot(slot)
                                        if self.spec_on else None))
            if slot in self._prefill_pos:       # preempted mid-prefill
                del self._prefill_pos[slot]
                self._prefill_order.remove(slot)
            self.slots[slot] = None
            self.scheduler.n_preempted += 1
            self.scheduler.requeue(st)
        if tel is not None:
            tel.registry.counter("serving_preempted_total").inc()
            tel.registry.histogram("serving_preempt_seconds").observe(
                sp.seconds)
            if tel.requests is not None:
                tel.requests.transition(req.id, "preempted")
        return True

    def _admit(self, prefill_budget: int | None = None):
        """Fill free slots from the scheduler; preempt strictly-lower-
        priority work when the head of the queue is blocked on pages.
        ``prefill_budget``: remaining chunked-prefill tokens this step —
        once spent, only zero-prefill items (decode-phase resumes) admit,
        and the admission-victim hunt stands down (preempting for a
        request we cannot prefill yet would only flap)."""
        sched = self.scheduler
        with span("engine.admit", self.tracer,
                  waiting=sched.waiting) as sp:
            admitted = 0
            while True:
                progress = False
                for slot in range(self.max_batch):
                    if self.slots[slot] is not None:
                        continue
                    item = sched.pick(slot, prefill_budget)
                    if item is None:
                        continue
                    if isinstance(item, Preempted):
                        self._resume(slot, item)
                    elif self.prefill_chunk:
                        self._start_chunked(slot, item)
                    else:
                        self._start(slot, item)
                    progress = True
                    admitted += 1
                if progress:
                    continue
                head = sched.head()
                if head is None:
                    break
                if (prefill_budget is not None and prefill_budget <= 0
                        and sched.prefill_tokens(head) > 0):
                    break
                victim = sched.admission_victim(self.slots, head)
                if victim is None or not self._preempt(victim):
                    break
            sp.done(admitted=admitted)
        if (sched.waiting and self.paged is not None
                and not (prefill_budget is not None and prefill_budget <= 0)
                and not any(s is not None for s in self.slots)):
            # every slot is free yet nothing could be admitted: no release
            # will ever refill the free lists.  Raised only once the
            # batch has drained, so in-flight work always completes first.
            bad = sched.impossible()
            if bad is not None:
                raise OutOfPages(
                    f"request {bad.id} needs "
                    f"{self.paged.pages_worst_case(len(bad.prompt), bad.max_new_tokens)}"
                    f" resident pages; largest shard holds "
                    f"{max(self.paged.shard_capacity(k) for k in range(self.paged.n_shards))}"
                    f" (swap cannot hold a single slot's working set)")
            raise OutOfPages(
                f"queued work cannot be admitted with an empty batch (free "
                f"per shard: {self.paged.free_pages_per_shard})")

    def _sample_one(self, logits, req: Request):
        if req.temperature <= 0:
            return greedy(logits)[0, 0]
        key = request_key(self.rng0, req.id, len(req.out_tokens))
        return sample_logits(logits, key, temperature=req.temperature)[0, 0]

    def _finish(self, s: int, req: Request):
        """Retire a finished request: clear the slot, publish telemetry,
        release its pages."""
        req.done = True
        self.slots[s] = None
        tel = self.tel
        if tel is not None:
            tel.registry.counter("serving_requests_finished_total").inc()
            sub = self._submit_t.pop(req.id, None)
            if sub is not None:
                tel.registry.histogram(
                    "serving_request_latency_seconds").observe(
                        time.perf_counter() - sub)
            if tel.requests is not None:
                tel.requests.finish(req.id,
                                    args={"tokens": len(req.out_tokens)})
        if self.paged is not None:
            self.cache = self.paged.release(self.cache, s)

    # -- speculative decoding ----------------------------------------------

    def _draft_leaf_axis(self, path):
        """(names, batch axis) of a draft-cache leaf from its pytree path
        — the same dispatch as :func:`splice_fragment`."""
        names = [str(getattr(k, "key", getattr(k, "idx", k)))
                 for k in path]
        return names, (1 if "units" in names else 0)

    def _draft_snapshot(self, slot: int) -> list:
        """Host copies of every draft-cache leaf's ``slot`` slice — the
        paired draft row stashed into ``Preempted.draft_state`` when the
        target slot is preempted (preempting one preempts both)."""
        flat, _ = jax.tree_util.tree_flatten_with_path(self.draft_cache)
        slices = []
        for path, leaf in flat:
            names, axis = self._draft_leaf_axis(path)
            if "cur_len" in names:
                slices.append(leaf[slot])
            else:
                slices.append(jax.lax.dynamic_slice_in_dim(
                    leaf, slot, 1, axis=axis))
        # one transfer for the whole row: the preemption path's host
        # sync count stays independent of the pytree size
        return jax.device_get(slices)

    def _draft_restore(self, slot: int, snap: list):
        """Inverse of :func:`_draft_snapshot` (bit-exact: the row never
        left its original dtype/bit pattern on the host)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.draft_cache)
        leaves = []
        for (path, leaf), fr in zip(flat, snap):
            names, _ = self._draft_leaf_axis(path)
            leaves.append(_splice(leaf, jnp.asarray(fr), slot, names))
        self.draft_cache = jax.tree_util.tree_unflatten(treedef, leaves)

    def _draft_rollback(self, slot: int, snap_cache):
        """Re-splice ``slot``'s draft row from a retained round snapshot
        — the draft-side rejection rollback.  Snapshots are the
        (immutable) cache pytrees returned by each draft step, so this
        is a device-side slice/update per leaf, no host round trip; it
        also restores the recurrent state of non-positional drafts
        (slstm/mlstm), which no ``cur_len`` rollback could."""
        flat_cur, treedef = jax.tree_util.tree_flatten_with_path(
            self.draft_cache)
        flat_snap = jax.tree_util.tree_flatten(snap_cache)[0]
        leaves = []
        for (path, cur), sv in zip(flat_cur, flat_snap):
            names, axis = self._draft_leaf_axis(path)
            if "cur_len" in names:
                leaves.append(cur.at[slot].set(sv[slot]))
            else:
                fr = jax.lax.dynamic_slice_in_dim(sv, slot, 1, axis=axis)
                leaves.append(jax.lax.dynamic_update_slice_in_dim(
                    cur, fr, slot, axis=axis))
        self.draft_cache = jax.tree_util.tree_unflatten(treedef, leaves)

    def _spec_round(self, active):
        """One speculative round for every decode-phase slot: ``k``
        batched draft proposal steps (+1 state-advance step, snapshots
        retained), one verify forward per slot appending ``k + 1``
        tokens' K/V (``models.model.verify_chunk``), exact rejection
        sampling (``serving.spec.verify``), then timeline + page +
        draft-state rollback of each rejected suffix.  Emits 1..k+1
        tokens per slot; the emitted stream is distribution-identical
        to target-only decoding (token-identical under greedy).

        Draft snapshot indexing: ``snaps[j]`` is the draft cache after
        ``j`` steps, i.e. having consumed proposals ``1..j-1``.  A slot
        that emits ``j`` tokens needs exactly ``snaps[j]`` — its new
        last token is the ``j``-th emission, which the draft consumes
        at the start of the *next* round."""
        k, tel = self.spec_k, self.tel
        # grow every slot's page list to cover its whole verify window
        # *before* drafting: ensure-with-pressure can preempt another
        # active slot, and a victim's paired draft row must be stashed
        # in its round-start state, not mid-round advanced
        windows = {}
        for s in active:
            if self.slots[s] is None:
                continue            # preempted by an earlier slot's ensure
            n_cache = self._host_len[s]
            k_eff = max(min(k, self.max_len - 1 - n_cache), 0)
            # speculation never preempts a neighbour just to draft
            # deeper: under page pressure the window shrinks, and only
            # the mandatory +1 write (k_eff == 0: exactly the
            # target-only step's allocation) applies preemption pressure
            while k_eff:
                try:
                    self.cache = self.paged.ensure(self.cache, s,
                                                   n_cache + k_eff)
                    break
                except OutOfPages:
                    k_eff -= 1
            if not k_eff:
                self._ensure_with_pressure(s)
            windows[s] = (n_cache, k_eff)
        active = [s for s in active if self.slots[s] is not None]
        if not active:
            return
        snaps = [self.draft_cache]
        q_rows = []                     # draft logits per proposal (B, 1, V)
        props = np.zeros((self.max_batch, k), np.int64)
        tok = self.last_tok
        for j in range(1, k + 2):
            logits, dc = self._draft_decode(self.draft_params, tok,
                                            self.draft_cache)
            self.draft_cache = dc
            snaps.append(dc)
            if j > k:
                break                   # final step only advances state
            q_rows.append(logits)
            nxt = np.asarray(greedy(logits)).copy()           # (B, 1)
            # sampled rows propose with the plain-decode rule and key
            # (serving.spec module docstring) — same batched vmapped
            # draw as the target-only step loop
            samp = [s for s in active if self.slots[s].temperature > 0]
            if samp:
                rows = logits[jnp.asarray(samp)]
                ids = jnp.asarray([self.slots[s].id for s in samp],
                                  jnp.int32)
                pos = jnp.asarray(
                    [len(self.slots[s].out_tokens) + j - 1 for s in samp],
                    jnp.int32)
                temps = jnp.asarray(
                    [self.slots[s].temperature for s in samp], jnp.float32)

                def draw(row, i, p, t):
                    key = request_key(self.rng0, i, p)
                    return sample_logits(row[None] / t, key,
                                         temperature=1.0)[0, 0]

                # the per-iteration sync is inherent: draft step j+1
                # consumes step j's token (already batched over slots)
                got = np.asarray(jax.vmap(draw)(  # lint: disable=eager-loop-sync
                    rows, ids, pos, temps))
                for s, g in zip(samp, got.tolist()):
                    nxt[s, 0] = g
            props[:, j - 1] = nxt[:, 0]
            tok = jnp.asarray(nxt.astype(np.int32))
        width = k + 1
        for s in active:
            req = self.slots[s]
            # the window clamps to the slot's remaining timeline (the
            # verify writes positions n_cache .. n_cache + k_eff)
            n_cache, k_eff = windows[s]
            toks = np.zeros((1, width), np.int32)
            toks[0, 0] = int(self.last_tok[s, 0])
            toks[0, 1:1 + k_eff] = props[s, :k_eff]
            cache_in, stash = self._maybe_strip()
            logits, new_cache = self._verify(self.params,
                                             jnp.asarray(toks), cache_in,
                                             s, k_eff + 1)
            self.last_logits = logits
            self.cache = (restore_cold(new_cache, stash) if stash
                          else new_cache)
            p_log = np.asarray(logits[0], np.float32)[: k_eff + 1]
            q_log = (np.stack([np.asarray(q_rows[j][s, 0])
                               for j in range(k_eff)])
                     if k_eff else
                     np.zeros((0, p_log.shape[-1]), np.float32))
            out, m = SPEC.verify(p_log, q_log, props[s, :k_eff].tolist(),
                                 rng0=self.rng0, req_id=req.id,
                                 pos0=len(req.out_tokens),
                                 temperature=req.temperature)
            # clip to the request's budget and the window (both >= 1:
            # a finished request never re-enters the active list)
            allow = min(req.max_new_tokens - len(req.out_tokens),
                        self.max_len - len(req.prompt)
                        - len(req.out_tokens))
            emit = out[: max(allow, 1)]
            j_emit = len(emit)
            new_len = n_cache + j_emit
            self.cache = self.paged.rollback(self.cache, s, new_len)
            self._host_len[s] = new_len
            if j_emit <= k:
                self._draft_rollback(s, snaps[j_emit])
            req.out_tokens.extend(int(t) for t in emit)
            self.last_tok = self.last_tok.at[s, 0].set(int(emit[-1]))
            self.n_spec_rounds += 1
            self.n_spec_drafted += k_eff
            self.n_spec_accepted += m
            if tel is not None:
                tel.registry.counter("spec_drafted_total").inc(k_eff)
                tel.registry.counter("spec_accepted_total").inc(m)
                tel.registry.histogram("spec_accept_rate").observe(
                    m / k_eff if k_eff else 0.0)
                tel.registry.counter(
                    "serving_tokens_generated_total").inc(j_emit)
            if (len(req.out_tokens) >= req.max_new_tokens
                    or len(req.prompt) + len(req.out_tokens)
                    >= self.max_len):
                self._finish(s, req)

    def spec_counters(self) -> dict:
        """Host-side speculative-decoding counters (mirrored into the
        telemetry registry as ``spec_*`` when telemetry is on)."""
        return {"spec_rounds": self.n_spec_rounds,
                "spec_drafted": self.n_spec_drafted,
                "spec_accepted": self.n_spec_accepted,
                "spec_accept_rate": (self.n_spec_accepted
                                     / max(self.n_spec_drafted, 1))}

    # -- chunked prefill ---------------------------------------------------

    def _maybe_strip(self):
        """(cache for the jitted call, stash) — while nothing is cold,
        both the decode step and the chunk step trace their no-cold-pool
        variant (the in-graph entropy decode of an empty pool is waste)."""
        if (self.paged is not None and self.paged.compress
                and not self.paged.has_cold):
            return strip_cold(self.cache)
        return self.cache, None

    def _ensure_prefill(self, slot: int, pos: int) -> bool:
        """Grow ``slot``'s page list to cover a chunk write at ``pos``.
        On pressure, preempt same-shard victims; as a last resort the
        prefilling request preempts *itself* (its chunks so far swap out
        losslessly and resume at the recorded position) — at most once
        per step, after which it pauses holding its pages, so an
        evict/fault ping-pong can never spin inside one step.  Returns
        False when the chunk must not run (self-preempted or paused)."""
        req = self.slots[slot]
        while True:
            try:
                self.cache = self.paged.ensure(self.cache, slot, pos)
                return True
            except OutOfPages:
                victim = self.scheduler.victim(
                    self.slots, shard=self.paged.shard_of_slot(slot),
                    exclude=(slot,))
                if victim is not None and self._preempt(victim):
                    continue
                if (self.scheduler._can_preempt()
                        and req.id not in self._stalled_ids
                        and self._preempt(slot)):
                    self._stalled_ids.add(req.id)
                    return False
                if self.scheduler._can_preempt():
                    return False        # paused: retry next step
                raise

    def _advance_prefill(self, slot: int, allowance: int) -> int:
        """Run prefill chunks for ``slot`` until its prompt is done or
        ~``allowance`` tokens were spent (the last chunk may overshoot by
        at most ``chunk - 1``).  The final chunk's logits produce the
        request's first token and move the slot to the decode phase.
        Returns the tokens spent."""
        req = self.slots[slot]
        C = self.prefill_chunk
        spent = 0
        while (self.slots[slot] is req and slot in self._prefill_pos
               and spent < allowance):
            pos = self._prefill_pos[slot]
            part = req.prompt[pos:pos + C]
            n = len(part)
            if not self._ensure_prefill(slot, pos + n - 1):
                return spent                    # self-preempted: requeued
            if self.prefix_sharing:
                # CoW safety invariant: block-aligned matching means the
                # chunk write starts at the match boundary, so this is
                # structurally a no-op — but any shared page in the write
                # window must split before the in-graph scatter lands
                self.cache = self.paged.make_writable(self.cache, slot,
                                                      pos, pos + n - 1)
            toks = jnp.asarray(list(part) + [0] * (C - n),
                               jnp.int32)[None, :]
            cache_in, stash = self._maybe_strip()
            with span("engine.prefill_chunk", self.tracer, slot=slot,
                      tokens=n) as sp:
                logits, new_cache = self._chunk(self.params, toks,
                                                cache_in, slot, n)
            self.last_logits = logits
            self.cache = (restore_cold(new_cache, stash) if stash
                          else new_cache)
            self._prefill_pos[slot] = pos + n
            self._host_len[slot] = pos + n
            if self.prefix_sharing:
                # publish the slot's newly completed prompt blocks so
                # concurrent and future requests share them
                self.paged.register_prefix(slot, req.prompt, pos + n)
            self.n_chunks += 1
            self.n_chunk_tokens += n
            spent += n
            tel = self.tel
            if tel is not None:
                tel.registry.histogram(
                    "serving_prefill_chunk_seconds").observe(sp.seconds)
            if pos + n >= len(req.prompt):      # final chunk: first token
                tok = self._sample_one(logits, req)
                req.out_tokens.append(int(tok))
                self.last_tok = self.last_tok.at[slot, 0].set(tok)
                del self._prefill_pos[slot]
                self._prefill_order.remove(slot)
                if tel is not None:
                    sub = self._submit_t.get(req.id)
                    if sub is not None:
                        tel.registry.histogram(
                            "serving_ttft_seconds").observe(
                                time.perf_counter() - sub)
                    tel.registry.counter(
                        "serving_tokens_generated_total").inc()
                    if tel.requests is not None:
                        tel.requests.transition(req.id, "decoding")
        return spent

    def _prefill_phase(self) -> int:
        """Spend up to ``prefill_budget`` prompt tokens on prefill work:
        mid-prefill slots drain first in admission order (FIFO within
        priority — an earlier prompt finishes before a later one starts),
        then new work admits against the remaining budget and runs its
        first chunks in the same step.  Returns tokens spent."""
        budget = self.prefill_budget
        spent = 0
        self._stalled_ids.clear()
        with span("engine.prefill_phase", self.tracer) as sp:
            while True:
                for slot in list(self._prefill_order):
                    if spent >= budget:
                        break
                    if (self.slots[slot] is not None
                            and slot in self._prefill_pos):
                        spent += self._advance_prefill(slot, budget - spent)
                before = len(self._prefill_order)
                had_free = any(s is None for s in self.slots)
                self._admit(prefill_budget=budget - spent)
                if len(self._prefill_order) == before or spent >= budget \
                        or not had_free:
                    break
            sp.done(tokens=spent)
        if spent and self.tel is not None:
            self._note_compiles()
        return spent

    # -- stepping ----------------------------------------------------------

    def _ensure_with_pressure(self, slot: int, pos: int | None = None):
        """Grow ``slot``'s page list to cover a write at ``pos``
        (default: this step's single decode write); on page pressure,
        preempt victims on the same shard until it fits."""
        if pos is None:
            pos = self._host_len[slot]
        while True:
            try:
                self.cache = self.paged.ensure(self.cache, slot, pos)
                return
            except OutOfPages:
                victim = self.scheduler.victim(
                    self.slots, shard=self.paged.shard_of_slot(slot),
                    exclude=(slot,))
                if victim is None or not self._preempt(victim):
                    raise

    def step(self) -> bool:
        """One engine step: budgeted prefill work (chunked mode), then
        one batched decode step for the decode-phase slots.  Returns
        False when idle."""
        with span("engine.step", self.tracer, step=self.steps):
            return self._step()

    def _step(self) -> bool:
        if self.prefill_chunk:
            prefill_spent = self._prefill_phase()
        else:
            self._admit()
            prefill_spent = 0
        active = [s for s in range(self.max_batch)
                  if self.slots[s] is not None
                  and s not in self._prefill_pos]
        if not active:
            if self._prefill_pos:
                self._record_monitor()
                self._sample_gauges()
                return True         # prefill in flight, nothing to decode
            return self.scheduler.waiting > 0
        if self.paged is not None:
            with span("kv.ensure", self.tracer, rows=len(active)):
                active = self._ensure_pages(active)
        if self.spec_on:
            # speculative mode replaces the single decode step with a
            # draft/verify round (1..k+1 tokens per slot); chunked
            # prefill is gated off, so no mid-prefill rows exist here
            with span("engine.spec_round", self.tracer, step=self.steps,
                      rows=len(active)) as sp:
                self._spec_round(active)
            if self.tel is not None:
                self.tel.registry.histogram(
                    "serving_decode_step_seconds").observe(sp.seconds)
                self._note_compiles()
            self.steps += 1
        else:
            self._decode_step(active, prefill_spent)
        if self.paged is not None and self.paged.compress:
            self.cache = self.paged.compress_cold(
                self.cache, [(s, self._host_len[s])
                             for s in range(self.max_batch)
                             if self.slots[s] is not None])
        self._record_monitor()
        self._sample_gauges()
        return True

    def _ensure_pages(self, active) -> list:
        """Grow the page lists of the decode-phase slots to cover this
        step's write and fault back any swapped page; returns the slots
        still active (growth may preempt)."""
        for s in active:
            if self.slots[s] is not None and s not in self._prefill_pos:
                self._ensure_with_pressure(s)
                if self.prefix_sharing:
                    # CoW safety invariant for the decode write (a
                    # structural no-op: decode writes land past the
                    # prompt, and full prompt blocks are the only
                    # shareable ones)
                    self.cache = self.paged.make_writable(
                        self.cache, s, self._host_len[s],
                        self._host_len[s])
        active = [s for s in range(self.max_batch)
                  if self.slots[s] is not None
                  and s not in self._prefill_pos]
        # fault-before-gather: the decode step must never see a swapped
        # page of an active slot (normally a no-op; resume already
        # faults, and whole-request preemption only swaps vacated slots)
        for s in active:
            if self.paged.has_swapped(s):
                self.cache = self.paged.fault(self.cache, s)
        return active

    def _decode_step(self, active, prefill_spent: int) -> None:
        """One batched decode step for ``active``, then the commit of
        each row's token.  The step's input cache dies with this frame,
        before the caller codes pages, so coding keeps no dead copy of
        the cache on the device."""
        tr = self.tracer
        with span("engine.decode_step", tr, step=self.steps,
                  rows=len(active)) as sp:
            with span("engine.dispatch", tr):
                # while nothing is cold, run the decode variant without
                # the cold pool (its in-graph entropy decode would be
                # pure waste)
                cache_in, stash = self._maybe_strip()
                logits, new_cache = self._decode(self.params, self.last_tok,
                                                 cache_in)
                self.last_logits = logits
                self.cache = (restore_cold(new_cache, stash) if stash
                              else new_cache)
                if self._prefill_pos:
                    # mid-prefill rows decoded as masked garbage: the
                    # batched step advanced every timeline, so roll theirs
                    # back (their stray write sits at the next chunk's
                    # first position and is overwritten by it)
                    idx = jnp.asarray(sorted(self._prefill_pos), jnp.int32)
                    self.cache = dict(self.cache)
                    self.cache["cur_len"] = \
                        self.cache["cur_len"].at[idx].add(-1)
            self.steps += 1
            if prefill_spent:
                self.n_interleaved_steps += 1
            with span("engine.sync", tr):
                toks = np.asarray(greedy(logits))  # (B, 1)
            # one batched draw for every sampled row: per-row keys fold
            # (rng_seed, request.id, position) — identical values to
            # calling _sample_one row by row, without k eager dispatches
            samp = [s for s in active if self.slots[s].temperature > 0]
            sampled = {}
            if samp:
                with span("engine.sample", tr, rows=len(samp)):
                    sampled = self._sample_rows(logits, samp)
        tel = self.tel
        if tel is not None:
            tel.registry.histogram("serving_decode_step_seconds").observe(
                sp.seconds)
            tel.registry.counter("serving_tokens_generated_total").inc(
                len(active))
            self._note_compiles()
        with span("engine.commit", tr):
            for s in active:
                req = self.slots[s]
                t = int(toks[s, 0] if req.temperature <= 0 else sampled[s])
                req.out_tokens.append(t)
                self.last_tok = self.last_tok.at[s, 0].set(t)
                self._host_len[s] += 1
                if len(req.out_tokens) >= req.max_new_tokens or (
                        len(req.prompt) + len(req.out_tokens)
                        >= self.max_len):
                    self._finish(s, req)

    def _sample_rows(self, logits, samp) -> dict:
        """slot -> sampled token for the rows ``samp`` of ``logits``."""
        rows = logits[jnp.asarray(samp)]                      # (k, 1, V)
        ids = jnp.asarray([self.slots[s].id for s in samp], jnp.int32)
        pos = jnp.asarray([len(self.slots[s].out_tokens) for s in samp],
                          jnp.int32)
        temps = jnp.asarray([self.slots[s].temperature for s in samp],
                            jnp.float32)

        def draw(row, i, p, t):
            key = request_key(self.rng0, i, p)
            return sample_logits(row[None] / t, key, temperature=1.0)[0, 0]

        got = np.asarray(jax.vmap(draw)(rows, ids, pos, temps))
        return dict(zip(samp, got.tolist()))

    def _record_monitor(self):
        if self.kv_monitor is None or self.paged is None:
            return
        stats = self.paged.stats()
        stats.update(self.scheduler.counters())
        if self.prefill_chunk:
            stats.update({
                "n_prefill_chunks": self.n_chunks,
                "prefill_chunk_tokens": self.n_chunk_tokens,
                "n_interleaved_steps": self.n_interleaved_steps,
                "prefilling_slots": len(self._prefill_pos),
            })
        self.kv_monitor.record(stats)
        if self.tel is not None and self.tel.tracer is not None:
            tr = self.tel.tracer
            tr.counter("kvcache_pages_in_use",
                       stats.get("pages_in_use", 0))
            if "swap_bytes_used" in stats:
                tr.counter("kvcache_swap_bytes_used",
                           stats["swap_bytes_used"])

    def prefill_compile_count(self) -> int:
        """Traced-program count of this engine's prefill path: the chunk
        step in chunked mode (must stay at 1 — or 2 once cold pages
        appear and the no-cold variant retraces — across *every* prompt
        length), else the whole-prompt prefill (retraces per length)."""
        return compile_count(self._chunk if self.prefill_chunk
                             else self._prefill)

    def decode_compile_count(self) -> int:
        """Traced-program count of this engine's decode step (1, or 2
        once cold pages appear and the no-cold variant retraces).  The
        registry's ``serving_decode_compile_total`` counts the retraces
        this engine itself triggered while stepping."""
        return compile_count(self._decode)

    def run(self, max_steps: int = 10_000, on_step=None) -> list:
        """Drain the queue; returns every submitted request that finished
        (whether it was queued, already admitted to a slot, or preempted
        when ``run`` was called — ``submit`` is the tracking point, not
        the queue snapshot).  ``on_step(step_index)``, when given, is
        called after every engine step (``launch/serve.py`` hangs the
        periodic stats line and the jax.profiler window off it)."""
        for i in range(max_steps):
            busy = self.step()
            if on_step is not None:
                on_step(i)
            if not busy and not any(s is not None for s in self.slots):
                break
        done = [r for r in self._inflight if r.done]
        self._inflight = [r for r in self._inflight if not r.done]
        return done
