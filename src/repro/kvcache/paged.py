"""Paged KV cache: fixed-size pages, per-slot page tables, free-list alloc.

Replaces the serving engine's monolithic ``(max_batch, max_len)`` cache.
Every batch slot owns a list of fixed-size pages (``page_size`` token
positions x all KV heads); a shared ``(max_batch, pages_per_slot)`` page
table maps logical page index -> physical page id, identically for every
attention layer (one allocation decision serves the whole stack, as in
vLLM).  Slot reuse stops over-reserving: a short request only ever holds
the pages it wrote, and the engine reports pages-in-use, not worst case.

Physical id space:
  * id 0 is the **garbage page** — inactive slots' table rows point at it
    so the batched decode step can scatter/gather unconditionally;
  * ids ``1 .. n_pages-1`` are raw pool pages;
  * ids ``>= n_pages`` address the **cold pool**: pages that filled up are
    entropy-coded by ``kvcache.codec`` (lossless, exponent plane) and live
    compressed; decode-on-use happens inside the same jitted step, exactly
    like ECF8 weights.  A page whose coded stream would exceed the uniform
    stride budget stays raw (rare: adversarial exponent content);
  * **negative** ids are **swapped** pages: ``-(key + 1)`` indexes the
    host-side :class:`repro.kvcache.swap.SwapStore` (``attach_swap``).  A
    swapped page holds no device memory at all; its page-table entry is
    the same negative sentinel, which the decode path clamps to the
    garbage page — the serving engine faults every active slot resident
    (``fault``) before any decode step gathers it.

Page lifecycle (with a swap store attached)::

    hot (raw pool) --page full--> cold (compressed pool)
        \\                           |
         \\--evict (encode)--\\      evict (device->host copy)
                              v      v
                            swapped (host SwapStore)
                              |         |
               fault (Pallas decode)  fault (reinstall container)
                              v         v
                             hot       cold

Mesh sharding (``n_shards > 1``): the pool's page dim and the page table's
batch dim shard over the mesh's batch axes (``runtime.sharding
.batch_axes``).  Batch shard ``k`` owns slots ``[k*B/n, (k+1)*B/n)``, raw
page ids ``[k*n_pages/n, (k+1)*n_pages/n)`` and the matching cold-slot
range, each with its own free list — so every slot's history is entirely
local to its shard and the sharded decode step never gathers pages across
devices (``models.decode_sharded.paged_decode_attention_sharded``).

In-graph ops (``page_write`` / ``page_gather``) are pure functions used by
``models.model``'s decode attention; the ``PagedKVCache`` class is the
host-side controller driven by ``serving.engine`` across the request
lifecycle (admit -> ensure -> compress cold -> release).
"""
from __future__ import annotations

import warnings

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import codec
from .codec import LANES
from repro.kernels import interpret_mode
from repro.runtime.tracing import span

GARBAGE_PAGE = 0
PAGED_KINDS = ("attn", "nope")   # "local" keeps its ring, recurrents a state


class OutOfPages(RuntimeError):
    """Raised when the raw pool cannot cover a request's next page."""


# --------------------------------------------------------------------------
# in-graph ops (called from models.model inside the jitted decode step)
# --------------------------------------------------------------------------

def page_write(pool, page_table, cur_len, kv):
    """Scatter one new token's K (or V) into each slot's tail page.

    pool: (n_pool, n_kv, ps, hd); page_table: (B, P) int32 page ids;
    cur_len: (B,) write positions; kv: (B, n_kv, 1, hd).

    Tail pages are raw by construction (a page is only compressed once
    full), so the scatter targets the raw pool; out-of-range and negative
    ids are dropped (``mode="drop"``) — which also makes this the
    per-shard write under a mesh: the sharded caller translates global
    ids to local ones and parks non-local entries out of range
    (``decode_sharded.paged_decode_attention_sharded``)."""
    ps = pool.shape[2]
    P = page_table.shape[1]
    p_idx = jnp.clip(cur_len // ps, 0, P - 1)
    off = cur_len % ps
    pids = jnp.take_along_axis(page_table, p_idx[:, None], axis=1)[:, 0]
    # a swap sentinel (negative id, left in the row of a preempted slot)
    # must drop too: a negative index would wrap onto a live page
    pids = jnp.where(pids < 0, pool.shape[0], pids)
    return pool.at[pids, :, off, :].set(
        kv[:, :, 0, :].astype(pool.dtype), mode="drop")


def page_write_chunk(pool, row, positions, kv, n_valid):
    """Scatter one prefill chunk's K (or V) into a single slot's pages.

    pool: (n_pool, n_kv, ps, hd); row: (P,) int32 page ids (the slot's
    page-table row); positions: (C,) absolute token positions of the
    chunk; kv: (1, n_kv, C, hd); n_valid: scalar count of real (unpadded)
    tokens.  Padded tokens are parked out of range and dropped
    (``mode="drop"``), which is also how the sharded caller silences
    non-owner shards (``decode_sharded.paged_prefill_chunk_sharded``)."""
    ps = pool.shape[2]
    P = row.shape[0]
    C = positions.shape[0]
    p_idx = jnp.clip(positions // ps, 0, P - 1)
    off = positions % ps
    pids = jnp.where(jnp.arange(C) < n_valid, row[p_idx], pool.shape[0])
    return pool.at[pids, :, off, :].set(
        kv[0].transpose(1, 0, 2).astype(pool.dtype), mode="drop")


def cold_leaves(cache: dict, kn: str):
    """The compressed-pool leaves for ``kn`` in {'k','v'}, or None.

    Returns (payload (n_cold, stride, LANES) u8, signmant (n_cold, sm) u8,
    tables (n_cold, 3, max_len) i32, perm (n_cold, n_sym) i32) — the
    argument order of ``codec.decode_pages_jnp``.  See docs/FORMATS.md §3
    for the leaf layout."""
    if f"{kn}_cpl" not in cache:
        return None
    return (cache[f"{kn}_cpl"], cache[f"{kn}_csm"],
            cache[f"{kn}_ctab"], cache[f"{kn}_cperm"])


_COLD_SUFFIXES = ("_cpl", "_csm", "_ctab", "_cperm")


def strip_cold(cache: dict):
    """Drop the cold-pool leaves from a paged cache -> (stripped, stash).

    While no page is cold, decoding the (empty) cold pool in-graph every
    step is pure waste; the engine strips these leaves so the decode step
    traces a no-cold variant, and restores them afterwards.  Costs one
    extra jit trace the first time a page actually goes cold."""
    stash = {}
    new = dict(cache)
    for section in ("units", "tail"):
        sec = dict(cache.get(section, {}))
        for name, leafd in sec.items():
            if not isinstance(leafd, dict) or "k_cpl" not in leafd:
                continue
            stash[(section, name)] = {
                k: v for k, v in leafd.items() if k.endswith(_COLD_SUFFIXES)}
            sec[name] = {k: v for k, v in leafd.items()
                         if not k.endswith(_COLD_SUFFIXES)}
        if sec:
            new[section] = sec
    return new, stash


def restore_cold(cache: dict, stash: dict):
    """Inverse of :func:`strip_cold` (cold leaves are read-only in-graph)."""
    new = dict(cache)
    for (section, name), cold in stash.items():
        sec = dict(new[section])
        sec[name] = {**sec[name], **cold}
        new[section] = sec
    return new


def page_gather(pool, page_table, cpool=None):
    """Gather each slot's pages into a contiguous KV history.

    pool: (n_pool, n_kv, ps, hd); page_table: (B, P) ids into the
    *virtual* pool; cpool: optional :func:`cold_leaves` tuple.  Cold pages
    (ids >= n_pool) are entropy-decoded in-graph and appended to the raw
    pool as a virtual suffix before the gather; ids are clipped, so
    garbage rows gather page 0 (their positions are masked by ``kv_len``
    downstream).  Returns (B, n_kv, P * ps, hd)."""
    n_kv, ps, hd = pool.shape[1:]
    virtual = pool
    if cpool is not None:
        from . import kernels
        # the compiled Pallas page decode on a TPU, the jnp decoder (its
        # bit-identical reference) elsewhere
        decode = (codec.decode_pages_jnp if interpret_mode()
                  else kernels.decode_pages)
        payload, signmant, tables, perm = cpool
        dec = decode(
            payload, signmant, tables, perm, n_elem=n_kv * ps * hd,
            dtype_name=str(pool.dtype))
        virtual = jnp.concatenate(
            [pool, dec.reshape(-1, n_kv, ps, hd)], axis=0)
    ids = jnp.clip(page_table, 0, virtual.shape[0] - 1)
    gath = jnp.take(virtual, ids, axis=0)          # (B, P, n_kv, ps, hd)
    B, P = page_table.shape
    return gath.transpose(0, 2, 1, 3, 4).reshape(B, n_kv, P * ps, hd)


# --------------------------------------------------------------------------
# host-side controller
# --------------------------------------------------------------------------

class PrefixIndex:
    """Content-addressed index of page-aligned prompt-prefix blocks.

    Key: the token content of blocks ``0..i`` as a tuple (the dict hashes
    it; equality is checked on lookup, so a hash collision can never
    mis-match a prefix — bit-identity survives by construction).  Value:
    the physical entry holding block ``i``'s K/V — a raw pool page id,
    or a negative swap sentinel (``-(key + 1)``) once the page was
    retired to the swap tier's prefix cache.  Dict insertion order
    doubles as LRU order: :meth:`touch` moves a matched key to the back,
    reclaim walks from the front."""

    def __init__(self):
        self._entries: dict[tuple, int] = {}

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, key):
        return self._entries.get(key)

    def put(self, key, entry: int) -> None:
        """Insert or update; an update keeps the key's LRU position."""
        self._entries[key] = entry

    def touch(self, key) -> None:
        self._entries[key] = self._entries.pop(key)

    def drop(self, key) -> None:
        self._entries.pop(key, None)

    def lru_keys(self) -> list:
        """Keys, least recently matched first."""
        return list(self._entries)

    def entries(self):
        return self._entries.values()


class PagedKVCache:
    """Allocator + lifecycle manager for the paged, compressible cache."""

    def __init__(self, cfg: ArchConfig, max_batch: int, max_len: int, *,
                 dtype, page_size: int = 16, n_pages: int | None = None,
                 compress_cold: bool = False, n_cold_slots: int | None = None,
                 budget_bits: int | None = None, n_shards: int = 1):
        """Args:
          cfg: architecture config (layer kinds decide which groups page).
          max_batch/max_len: static engine batch shape; every slot can hold
            at most ``max_len`` tokens (``pages_per_slot`` pages).
          dtype: cache storage dtype (fp8/bf16/f32 — must have a page-codec
            plane spec when ``compress_cold``).
          page_size: token positions per page; rounded down to a divisor of
            ``max_len``.
          n_pages: raw pool size (id 0 is the garbage page); defaults to
            the worst case (every slot full) plus the garbage page, and is
            rounded up to a multiple of ``n_shards``.
          compress_cold: entropy-code full pages into the cold pool.
          n_cold_slots: cold pool size (default: worst case minus one tail
            page per slot), rounded up to a multiple of ``n_shards``.
          budget_bits: uniform cold-payload budget in bits/symbol (default:
            the raw exponent width — never worse than the raw plane).
          n_shards: batch-shard count of the mesh the cache will live on
            (``runtime.sharding.batch_axes`` sizes multiplied); slots,
            raw pages and cold slots are partitioned contiguously into
            ``n_shards`` ranges with one free list each.  ``max_batch``
            must be divisible by it.
        """
        self.cfg = cfg
        self.max_batch, self.max_len = max_batch, max_len
        self.dtype = jnp.dtype(dtype)
        self.dtype_name = str(self.dtype)
        if n_shards < 1 or max_batch % n_shards:
            raise ValueError(
                f"max_batch={max_batch} not divisible by n_shards={n_shards}")
        self.n_shards = n_shards
        self.slots_per_shard = max_batch // n_shards
        ps = max(1, min(page_size, max_len))
        while max_len % ps:
            ps -= 1
        if ps != page_size:
            warnings.warn(
                f"page_size={page_size} does not divide max_len={max_len}; "
                f"using {ps} (a tiny page inflates the page table and the "
                f"per-token scatter/gather)", stacklevel=2)
        self.page_size = ps
        self.pages_per_slot = max_len // ps
        n_pages = n_pages or (
            n_shards + max_batch * self.pages_per_slot)
        # each shard owns a contiguous, equal range of page ids
        self.n_pages = -(-n_pages // n_shards) * n_shards
        self.pages_per_shard = self.n_pages // n_shards

        unit = cfg.unit
        self.n_units = cfg.n_layers // unit
        self.n_tail = cfg.n_layers - self.n_units * unit
        self.n_attn_layers = sum(
            1 for i in range(cfg.n_layers) if cfg.layer_kind(i) in PAGED_KINDS)
        self.has_attn = self.n_attn_layers > 0

        self.page_elems = cfg.n_kv_heads * ps * cfg.hd
        exp_bits, self.max_code_len, _ = codec.plane_spec(self.dtype_name)
        self.n_sym = 1 << exp_bits
        self.S = codec.sym_per_lane(self.page_elems)
        self.sm_nbytes = codec.sm_bytes(self.dtype_name, self.page_elems)
        self.compress = bool(compress_cold) and self.has_attn
        if budget_bits is None:
            budget_bits = exp_bits  # never worse than the raw exponent plane
        self.stride_budget = max(codec.MIN_STRIDE,
                                 -(-self.S * budget_bits // 8))
        default_cold = max_batch * max(self.pages_per_slot - 1, 1)
        n_cold = (n_cold_slots if n_cold_slots is not None
                  else default_cold) if self.compress else 0
        self.n_cold = -(-n_cold // n_shards) * n_shards if n_cold else 0
        self.cold_per_shard = self.n_cold // n_shards

        # per-shard free lists (descending, so pop() hands out low ids
        # first); shard 0's range excludes the garbage page id 0
        pps = self.pages_per_shard
        self._free = [list(range((k + 1) * pps - 1, max(k * pps, 1) - 1, -1))
                      for k in range(n_shards)]
        cps = self.cold_per_shard
        self._cold_free = [list(range((k + 1) * cps - 1, k * cps - 1, -1))
                           for k in range(n_shards)]
        self._slot_pages: dict[int, list[int]] = {}
        self._skip: dict[int, set[int]] = {}
        self._cold_bytes: dict[int, int] = {}
        # physical-page reference counts: every live raw pid has an entry
        # (1 = private).  Holders are slots (one ref per slot whose page
        # list contains the pid) and the prefix index (one ref per index
        # entry).  A pid returns to its shard's free list only when the
        # count hits zero — the audit invariant of release/rollback/
        # evict/compress, property-tested in tests/test_prefix_sharing.py
        self._ref: dict[int, int] = {}
        self.prefix = None              # PrefixIndex (enable_prefix_sharing)
        self.n_prefix_retired = 0       # index pages retired to swap
        self.n_prefix_dropped = 0       # index pages dropped (no swap room)
        self.n_cow_splits = 0           # shared pages split before a write
        self.swap = None                # SwapStore (attach_swap)
        self.telemetry = None           # serving.telemetry.Telemetry
        #   (engine-set; evict/fault publish page counts through it, and
        #   the spans also feed its tracer — pure observation)

    @property
    def tracer(self):
        """The span buffer the cache's spans also feed (or None)."""
        return None if self.telemetry is None else self.telemetry.tracer

    # -- structure ---------------------------------------------------------

    def _groups(self):
        """Yield (section, name, kind, stacked) for every layer group."""
        unit = self.cfg.unit
        for j in range(unit):
            yield "units", f"pos{j}", self.cfg.pattern[j], True
        for t in range(self.n_tail):
            kind = self.cfg.layer_kind(self.n_units * unit + t)
            yield "tail", f"layer{t}", kind, False

    def _pool_leaves(self, stacked: bool) -> dict:
        cfg, ps = self.cfg, self.page_size
        lead = (self.n_units,) if stacked else ()
        pool = lead + (self.n_pages, cfg.n_kv_heads, ps, cfg.hd)
        d = {"k_pool": jnp.zeros(pool, self.dtype),
             "v_pool": jnp.zeros(pool, self.dtype)}
        if self.compress:
            for kn in ("k", "v"):
                d[f"{kn}_cpl"] = jnp.zeros(
                    lead + (self.n_cold, self.stride_budget, LANES),
                    jnp.uint8)
                d[f"{kn}_csm"] = jnp.zeros(
                    lead + (self.n_cold, self.sm_nbytes), jnp.uint8)
                d[f"{kn}_ctab"] = jnp.zeros(
                    lead + (self.n_cold, 3, self.max_code_len), jnp.int32)
                d[f"{kn}_cperm"] = jnp.zeros(
                    lead + (self.n_cold, self.n_sym), jnp.int32)
        return d

    def init_cache(self) -> dict:
        """The paged cache pytree: monolithic layout with attn/nope leaves
        replaced by page pools, plus the shared page table."""
        from repro.models import model as M
        cache = M.init_cache(self.cfg, self.max_batch, self.max_len,
                             dtype=self.dtype, per_slot=True)
        for section, name, kind, stacked in self._groups():
            if kind in PAGED_KINDS:
                cache[section] = {**cache[section],
                                  name: self._pool_leaves(stacked)}
        cache["page_table"] = jnp.zeros(
            (self.max_batch, self.pages_per_slot), jnp.int32)
        return cache

    # -- allocator ---------------------------------------------------------

    def _alloc_raw(self, sh: int) -> int:
        """Pop a raw page off ``sh``'s free list with refcount 1."""
        pid = self._free[sh].pop()
        self._ref[pid] = 1
        return pid

    def _incref(self, pid: int) -> None:
        self._ref[pid] = self._ref.get(pid, 0) + 1

    def _decref(self, pid: int) -> None:
        """Drop one reference; the page frees only when nobody holds it."""
        n = self._ref.get(pid, 1) - 1
        if n <= 0:
            self._ref.pop(pid, None)
            self._free[pid // self.pages_per_shard].append(pid)
        else:
            self._ref[pid] = n

    def shard_of_slot(self, slot: int) -> int:
        """Batch shard owning ``slot`` (contiguous slot ranges per shard)."""
        return slot // self.slots_per_shard

    @property
    def free_pages(self) -> int:
        """Total free raw pages across all shards."""
        return sum(len(f) for f in self._free)

    @property
    def free_pages_per_shard(self) -> list[int]:
        return [len(f) for f in self._free]

    @property
    def has_cold(self) -> bool:
        return bool(self._cold_bytes)

    def pages_needed(self, prompt_len: int) -> int:
        """Pages to cover the prompt and the first decode write."""
        return min(prompt_len // self.page_size + 1, self.pages_per_slot)

    def pages_for_prefix(self, n_tokens: int) -> int:
        """Pages that hold the first ``n_tokens`` cache positions — the
        chunked-prefill admission grant (unlike :func:`pages_needed` it
        does not cover the first decode write; later chunks and the
        decode step grow the slot page by page via :func:`ensure`)."""
        return min(max(-(-n_tokens // self.page_size), 1),
                   self.pages_per_slot)

    def can_admit(self, prompt_len: int, slot: int | None = None) -> bool:
        """Whether ``slot``'s shard (any shard when ``slot`` is None) has
        enough free pages for a ``prompt_len``-token prompt."""
        need = self.pages_needed(prompt_len)
        if slot is None:
            return any(len(f) >= need for f in self._free)
        return len(self._free[self.shard_of_slot(slot)]) >= need

    # -- request lifecycle -------------------------------------------------

    def admit(self, cache: dict, slot: int, frag: dict, prompt_len: int):
        """Allocate a fresh slot's pages (from its shard's free list) and
        splice the prefill fragment into the pool."""
        need = self.pages_needed(prompt_len)
        sh = self.shard_of_slot(slot)
        free = self._free[sh]
        if len(free) < need:
            cache = self._reclaim_prefix(cache, sh, need - len(free))
        if len(free) < need:
            raise OutOfPages(f"shard {sh}: slot {slot} needs {need} pages, "
                             f"{len(free)} free")
        pids = [self._alloc_raw(sh) for _ in range(need)]
        self._slot_pages[slot] = pids
        self._skip[slot] = set()

        row = np.zeros(self.pages_per_slot, np.int32)
        row[:need] = pids
        cache = dict(cache)
        cache["page_table"] = cache["page_table"].at[slot].set(
            jnp.asarray(row))
        cache["cur_len"] = cache["cur_len"].at[slot].set(prompt_len)
        ids = jnp.asarray(pids, jnp.int32)

        for section, name, kind, stacked in self._groups():
            dst, src = cache[section][name], frag[section][name]
            if kind in PAGED_KINDS:
                new = dict(dst)
                for kn in ("k", "v"):
                    pages = self._frag_pages(src[kn], stacked)
                    pool = dst[f"{kn}_pool"]
                    if stacked:
                        new[f"{kn}_pool"] = pool.at[:, ids].set(
                            pages[:, :need].astype(pool.dtype))
                    else:
                        new[f"{kn}_pool"] = pool.at[ids].set(
                            pages[:need].astype(pool.dtype))
            else:
                axis = 1 if stacked else 0
                new = jax.tree_util.tree_map(
                    lambda full, fr: jax.lax.dynamic_update_slice_in_dim(
                        full, fr.astype(full.dtype), slot, axis=axis),
                    dst, src)
            cache[section] = {**cache[section], name: new}
        return cache

    def admit_slot(self, cache: dict, slot: int, need: int):
        """Allocate a fresh slot for **chunked prefill**: grant ``need``
        pages (no fragment is spliced — chunks write K/V in-graph via
        :func:`page_write_chunk`) and reset the slot's timeline to
        position 0.  The grant is the first chunk's pages
        (:func:`pages_for_prefix`) when preemption can resolve later
        pressure, or the whole-prompt :func:`pages_needed` reservation
        when it cannot; later chunks append pages across chunk
        boundaries with :func:`ensure`."""
        sh = self.shard_of_slot(slot)
        free = self._free[sh]
        if len(free) < need:
            cache = self._reclaim_prefix(cache, sh, need - len(free))
        if len(free) < need:
            raise OutOfPages(f"shard {sh}: slot {slot} needs {need} pages, "
                             f"{len(free)} free")
        pids = [self._alloc_raw(sh) for _ in range(need)]
        self._slot_pages[slot] = pids
        self._skip[slot] = set()
        row = np.zeros(self.pages_per_slot, np.int32)
        row[:need] = pids
        cache = dict(cache)
        cache["page_table"] = cache["page_table"].at[slot].set(
            jnp.asarray(row))
        cache["cur_len"] = cache["cur_len"].at[slot].set(0)
        return cache

    def _frag_pages(self, x, stacked: bool):
        """Prefill fragment (.., 1, n_kv, max_len, hd) -> page-major view."""
        cfg, ps, P = self.cfg, self.page_size, self.pages_per_slot
        if stacked:
            x = x.reshape(self.n_units, cfg.n_kv_heads, P, ps, cfg.hd)
            return x.transpose(0, 2, 1, 3, 4)       # (U, P, n_kv, ps, hd)
        x = x.reshape(cfg.n_kv_heads, P, ps, cfg.hd)
        return x.transpose(1, 0, 2, 3)              # (P, n_kv, ps, hd)

    def ensure(self, cache: dict, slot: int, pos: int):
        """Grow the slot's page list to cover a write at ``pos`` (allocating
        from the slot's shard)."""
        pages = self._slot_pages.get(slot)
        if pages is None:
            return cache
        sh = self.shard_of_slot(slot)
        p = min(pos // self.page_size, self.pages_per_slot - 1)
        new = []
        try:
            while len(pages) + len(new) <= p:
                if not self._free[sh]:
                    cache = self._reclaim_prefix(cache, sh, 1)
                if not self._free[sh]:
                    raise OutOfPages(
                        f"shard {sh}: slot {slot} needs page "
                        f"{len(pages) + len(new)}")
                new.append(self._alloc_raw(sh))
        except OutOfPages:
            # all or nothing: the caller keeps its cache (page table) on
            # failure, so the slot's host page list must not grow either
            for pid in reversed(new):
                self._decref(pid)
            raise
        if new:
            cache = dict(cache)
            cache["page_table"] = cache["page_table"].at[
                slot, len(pages):len(pages) + len(new)].set(
                    jnp.asarray(new, jnp.int32))
            pages.extend(new)
        return cache

    def rollback(self, cache: dict, slot: int, n_tokens: int):
        """Truncate ``slot``'s timeline to ``n_tokens`` cache positions —
        the speculative-verify rejection path: a verify forward appended
        ``k + 1`` tokens' K/V (``page_write_chunk``) and the rejected
        suffix must disappear again.

        Pages past the boundary (beyond ``ceil(n_tokens / page_size)``,
        floored at one page so the admission grant is never undercut)
        return to the shard free list **in reverse-allocation order**:
        :func:`ensure` pops from the tail of the descending free list,
        so popping the slot's page list from its own tail and appending
        each id back restores the free list — and with it every future
        allocation decision — bit-exactly to the pre-verify state
        (tests/test_speculative.py rollback property test).  Freed pages
        are raw by construction: speculation allocates and rolls back
        within one engine step, before cold compression or eviction can
        touch the new pages.  Stale K/V between ``n_tokens`` and the old
        timeline inside *kept* pages is overwritten by the slot's next
        write at ``n_tokens`` and masked by ``kv_len`` until then — the
        chunked-prefill stray-write discipline.  ``cur_len[slot]`` is
        set to ``n_tokens``."""
        cache = dict(cache)
        pages = self._slot_pages.get(slot)
        if pages is not None:
            keep = min(max(-(-n_tokens // self.page_size), 1),
                       self.pages_per_slot)
            while len(pages) > keep:
                pid = pages.pop()
                if not (GARBAGE_PAGE < pid < self.n_pages):
                    raise ValueError(
                        f"rollback({slot}): page {pid} is not raw — only "
                        f"pages allocated by the current verify window "
                        f"can be rolled back")
                cache["page_table"] = cache["page_table"].at[
                    slot, len(pages)].set(GARBAGE_PAGE)
                self._decref(pid)
        cache["cur_len"] = cache["cur_len"].at[slot].set(n_tokens)
        return cache

    def release(self, cache: dict, slot: int):
        """Free a finished slot's raw pages, cold-pool entries and swapped
        pages back to the free lists / swap store that own the ids."""
        for e in self._slot_pages.pop(slot, []):
            if e < 0:
                if self.swap is not None:
                    self.swap.discard(-e - 1)
            elif e >= self.n_pages:
                cs = e - self.n_pages
                self._cold_free[cs // max(self.cold_per_shard, 1)].append(cs)
                self._cold_bytes.pop(cs, None)
            elif e != GARBAGE_PAGE:
                self._decref(e)     # shared prefix pages stay for the index
        self._skip.pop(slot, None)
        cache = dict(cache)
        cache["page_table"] = cache["page_table"].at[slot].set(
            jnp.zeros(self.pages_per_slot, jnp.int32))
        return cache

    # -- swap tier (hot/cold -> host, see kvcache/swap.py) -----------------

    def attach_swap(self, store) -> None:
        """Wire a :class:`repro.kvcache.swap.SwapStore` as the host tier;
        ``evict``/``fault`` require one."""
        self.swap = store

    def has_swapped(self, slot: int) -> bool:
        return any(e < 0 for e in self._slot_pages.get(slot, ()))

    def resident_raw_pages(self, slot: int) -> int:
        """Raw pool pages the slot currently holds (what preempting it
        would hand back to its shard's free list; cold and swapped
        entries free cold slots / swap bytes instead)."""
        return sum(1 for e in self._slot_pages.get(slot, ())
                   if GARBAGE_PAGE < e < self.n_pages)

    def n_swapped(self, slot: int) -> int:
        return sum(1 for e in self._slot_pages.get(slot, ()) if e < 0)

    def pages_worst_case(self, prompt_len: int, max_new: int) -> int:
        """Pages the request can ever hold at once: its last cache write
        lands at position ``min(prompt+max_new, max_len) - 2`` (the final
        sampled token is never written), floored at ``prompt_len`` (the
        admission grant covers the first decode write)."""
        last = max(min(prompt_len + max_new, self.max_len) - 2, prompt_len)
        return min(last // self.page_size + 1, self.pages_per_slot)

    def shard_capacity(self, shard: int) -> int:
        """Allocatable raw pages in ``shard``'s id range (shard 0 loses
        the garbage page)."""
        return self.pages_per_shard - (1 if shard == 0 else 0)

    def _iter_subpages(self):
        """Yield (section, name, stacked, kn, u) in the canonical sub-page
        order shared by evict and fault."""
        for section, name, kind, stacked in self._groups():
            if kind not in PAGED_KINDS:
                continue
            for kn in ("k", "v"):
                for u in (range(self.n_units) if stacked else (None,)):
                    yield section, name, stacked, kn, u

    def _encode_raw_page(self, cache: dict, pid: int):
        """Entropy-code one raw pool page into a host SwappedPage."""
        from . import swap as SW
        page = SW.SwappedPage(was_cold=False)
        for section, name, stacked, kn, u in self._iter_subpages():
            pool = cache[section][name][f"{kn}_pool"]
            sub = np.asarray(pool[u, pid] if stacked else pool[pid])
            cp = codec.encode_page(sub)
            page.entries.append(SW.SwapEntry(
                section, name, stacked, kn, u, cp.payload, cp.signmant,
                cp.tables(), cp.perm))
            page.nbytes += cp.nbytes()
        return page

    def _copy_cold_page(self, cache: dict, cslot: int):
        """Copy an already-coded cold page's container to the host (the
        cheap, cold-first eviction path: no re-encode)."""
        from . import swap as SW
        page = SW.SwappedPage(was_cold=True,
                              nbytes=self._cold_bytes.get(cslot, 0))
        for section, name, stacked, kn, u in self._iter_subpages():
            leafd = cache[section][name]
            idx = (u, cslot) if stacked else (cslot,)
            page.entries.append(SW.SwapEntry(
                section, name, stacked, kn, u,
                np.asarray(leafd[f"{kn}_cpl"][idx]),
                np.asarray(leafd[f"{kn}_csm"][idx]),
                np.asarray(leafd[f"{kn}_ctab"][idx]),
                np.asarray(leafd[f"{kn}_cperm"][idx])))
        return page

    def evict(self, cache: dict, slot: int, page_idxs=None):
        """Swap the slot's device-resident pages out to the host store.

        Cold pages go first (their container copies without re-encoding);
        raw pages are entropy-coded on the host — losslessly for *any*
        bit content, so even a half-written tail page round-trips
        bit-exactly.  Freed raw pages / cold slots return to their
        shard's free lists; the page list and page-table entries become
        negative swap sentinels (``-(key + 1)``)."""
        if self.swap is None:
            raise RuntimeError("evict() needs attach_swap(SwapStore)")
        pages = self._slot_pages.get(slot)
        if pages is None:
            return cache
        sh = self.shard_of_slot(slot)
        idxs = list(range(len(pages))) if page_idxs is None else list(page_idxs)
        # cold-first: already-compressed pages are the cheapest victims
        idxs.sort(key=lambda p: (pages[p] < self.n_pages, p))
        cache = dict(cache)
        n_moved = 0
        with span("swap.evict", self.tracer, slot=slot) as ev:
            for p in idxs:
                e = pages[p]
                if e < 0 or e == GARBAGE_PAGE:
                    continue
                if e >= self.n_pages:
                    cs = e - self.n_pages
                    sp = self._copy_cold_page(cache, cs)
                    key = self.swap.put(sp, sh)
                    self._cold_free[cs // max(self.cold_per_shard, 1)
                                    ].append(cs)
                    self._cold_bytes.pop(cs, None)
                else:
                    # a shared page gets a *private* swap copy and a
                    # decref: the prefix index keeps its own (still-
                    # resident) reference, so sharing degrades gracefully
                    # under memory pressure and detach_slot's all-swapped
                    # assertion holds
                    sp = self._encode_raw_page(cache, e)
                    key = self.swap.put(sp, sh)
                    self._decref(e)
                pages[p] = -(key + 1)
                cache["page_table"] = cache["page_table"].at[slot, p].set(
                    -(key + 1))
                n_moved += 1
            ev.done(pages=n_moved)
        if n_moved and self.telemetry is not None:
            self.telemetry.registry.counter(
                "kvcache_evict_pages_total").inc(n_moved)
        return cache

    def fault(self, cache: dict, slot: int, page_idxs=None):
        """Restore the slot's swapped pages to the device (the inverse of
        :func:`evict`; a no-op when nothing is swapped).

        Cold-swapped pages reinstall their coded container into a fresh
        cold slot (never decoded); raw-swapped pages are **batch-decoded
        through the Pallas page-decode path** (``kernels.decode_pages``)
        into fresh raw pages.  Raises :class:`OutOfPages` — before any
        state is mutated — if the slot's shard cannot cover the restore.
        """
        pages = self._slot_pages.get(slot)
        if pages is None:
            return cache
        idxs = [p for p in (range(len(pages)) if page_idxs is None
                            else page_idxs) if pages[p] < 0]
        if not idxs:
            return cache
        with span("swap.fault", self.tracer, slot=slot, pages=len(idxs)):
            cache = self._fault(cache, slot, pages, idxs)
        if self.telemetry is not None:
            self.telemetry.registry.counter(
                "kvcache_fault_pages_total").inc(len(idxs))
        return cache

    def _fault(self, cache: dict, slot: int, pages: list, idxs: list):
        sh = self.shard_of_slot(slot)
        # placement plan (peek only): cold-swapped pages take cold slots
        # while they last, everything else needs a raw page
        plan = []                       # (p, SwappedPage, to_cold)
        cold_budget = len(self._cold_free[sh]) if self.compress else 0
        raw_need = 0
        for p in idxs:
            sp = self.swap.peek(-pages[p] - 1)
            to_cold = sp.was_cold and cold_budget > 0
            cold_budget -= int(to_cold)
            raw_need += int(not to_cold)
            plan.append((p, sp, to_cold))
        if raw_need > len(self._free[sh]):
            cache = self._reclaim_prefix(
                cache, sh, raw_need - len(self._free[sh]))
        if raw_need > len(self._free[sh]):
            raise OutOfPages(
                f"shard {sh}: faulting {len(idxs)} swapped pages of slot "
                f"{slot} needs {raw_need} raw pages, "
                f"{len(self._free[sh])} free")

        cache = dict(cache)
        raw_jobs = []                   # (entry, pid) scattered after decode
        for p, sp, to_cold in plan:
            self.swap.pop(-pages[p] - 1)
            if to_cold:
                cs = self._cold_free[sh].pop()
                for ent in sp.entries:
                    leafd = dict(cache[ent.section][ent.name])
                    idx = (ent.u, cs) if ent.stacked else (cs,)
                    pay = np.zeros((self.stride_budget, LANES), np.uint8)
                    pay[: ent.payload.shape[0]] = ent.payload
                    leafd[f"{ent.kn}_cpl"] = \
                        leafd[f"{ent.kn}_cpl"].at[idx].set(pay)
                    leafd[f"{ent.kn}_csm"] = \
                        leafd[f"{ent.kn}_csm"].at[idx].set(ent.signmant)
                    leafd[f"{ent.kn}_ctab"] = \
                        leafd[f"{ent.kn}_ctab"].at[idx].set(ent.tables)
                    leafd[f"{ent.kn}_cperm"] = \
                        leafd[f"{ent.kn}_cperm"].at[idx].set(ent.perm)
                    cache[ent.section] = {**cache[ent.section],
                                          ent.name: leafd}
                self._cold_bytes[cs] = sp.nbytes
                entry = self.n_pages + cs
            else:
                pid = self._alloc_raw(sh)
                raw_jobs.extend((ent, pid) for ent in sp.entries)
                entry = pid
            pages[p] = entry
            cache["page_table"] = cache["page_table"].at[slot, p].set(entry)

        if raw_jobs:
            cache = self._restore_raw(cache, raw_jobs)
        return cache

    def _restore_raw(self, cache: dict, jobs):
        """Batch-decode swapped sub-pages and scatter them into the raw
        pool: one Pallas ``decode_pages`` call covers every sub-page of
        every page being faulted (stride padded to the batch max)."""
        from . import kernels
        stride = max(e.payload.shape[0] for e, _ in jobs)
        stride = -(-stride // 4) * 4        # bucket shapes for the jit cache
        pay = np.zeros((len(jobs), stride, LANES), np.uint8)
        for i, (e, _) in enumerate(jobs):
            pay[i, : e.payload.shape[0]] = e.payload
        dec = kernels.decode_pages(
            jnp.asarray(pay),
            jnp.asarray(np.stack([e.signmant for e, _ in jobs])),
            jnp.asarray(np.stack([e.tables for e, _ in jobs])),
            jnp.asarray(np.stack([e.perm for e, _ in jobs])),
            n_elem=self.page_elems, dtype_name=self.dtype_name)
        shape = (self.cfg.n_kv_heads, self.page_size, self.cfg.hd)
        for i, (e, pid) in enumerate(jobs):
            pool = cache[e.section][e.name][f"{e.kn}_pool"]
            sub = dec[i].reshape(shape).astype(pool.dtype)
            idx = (e.u, pid) if e.stacked else (pid,)
            cache[e.section] = {
                **cache[e.section],
                e.name: {**cache[e.section][e.name],
                         f"{e.kn}_pool": pool.at[idx].set(sub)}}
        return cache

    def snapshot_slot_state(self, cache: dict, slot: int) -> dict:
        """Host copies of the slot's **non-paged** per-slot cache state —
        local-attention ring buffers and recurrent (rglru/slstm/mlstm)
        states of hybrid architectures live in monolithic batch-dim
        leaves next to the page pools, hold no page ids, and would be
        clobbered by the next request admitted to the slot.  Preemption
        stashes them with this and reinstalls via
        :func:`restore_slot_state` on resume."""
        snap = {}
        for section, name, kind, stacked in self._groups():
            if kind in PAGED_KINDS:
                continue
            axis = 1 if stacked else 0
            snap[(section, name)] = jax.tree_util.tree_map(
                lambda x: np.asarray(
                    jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=axis)),
                cache[section][name])
        return snap

    def restore_slot_state(self, cache: dict, slot: int,
                           snap: dict) -> dict:
        """Inverse of :func:`snapshot_slot_state` (bit-exact: the state
        never leaves its original dtype/bit pattern)."""
        cache = dict(cache)
        for (section, name), sub in snap.items():
            axis = 1 if section == "units" else 0
            cache[section] = {**cache[section], name: jax.tree_util.tree_map(
                lambda full, fr: jax.lax.dynamic_update_slice_in_dim(
                    full, jnp.asarray(fr).astype(full.dtype), slot,
                    axis=axis),
                cache[section][name], sub)}
        return cache

    def detach_slot(self, slot: int):
        """Pop a preempted slot's host state -> (page list, skip set).

        Every entry must already be swapped (call :func:`evict` first);
        the engine stashes the result in its preemption record and
        reinstalls it with :func:`attach_slot` on resume."""
        pages = self._slot_pages.pop(slot)
        assert all(e < 0 for e in pages), \
            f"detach_slot({slot}): resident pages remain {pages}"
        return pages, self._skip.pop(slot, set())

    def attach_slot(self, cache: dict, slot: int, pages, skip):
        """Reinstall a preempted slot's page list (all swap sentinels) and
        page-table row; follow with :func:`fault` to make it resident."""
        self._slot_pages[slot] = list(pages)
        self._skip[slot] = set(skip)
        row = np.zeros(self.pages_per_slot, np.int32)
        row[: len(pages)] = pages
        cache = dict(cache)
        cache["page_table"] = cache["page_table"].at[slot].set(
            jnp.asarray(row))
        return cache

    # -- cross-request prefix sharing --------------------------------------

    def enable_prefix_sharing(self) -> None:
        """Attach a :class:`PrefixIndex` so requests with a common
        page-aligned prompt prefix share one physical copy of its pages
        (copy-on-write protected).  Single-shard only: page ids are
        shard-local, so a prefix cached by one shard would be unreachable
        from slots of any other."""
        if self.n_shards != 1:
            raise ValueError(
                f"prefix sharing requires n_shards == 1 (got "
                f"{self.n_shards}): pages are shard-local and slots must "
                f"gather only their own shard's pages")
        if self.prefix is None:
            self.prefix = PrefixIndex()

    @property
    def prefix_sharing(self) -> bool:
        return self.prefix is not None

    def _prefix_key(self, prompt, i: int) -> tuple:
        """Content address of prompt block ``i``: the token ids of blocks
        ``0..i``.  Keys are prefix-closed — block ``i``'s K/V is fully
        determined by (and only by) the tokens in the key, so equal keys
        imply bit-identical page content."""
        return tuple(prompt[: (i + 1) * self.page_size])

    def match_prefix(self, prompt) -> int:
        """Longest index-resident prefix of ``prompt``, in tokens (always
        a multiple of ``page_size``).

        Capped at ``(len(prompt) - 1) // page_size`` blocks so the final
        prompt token is always prefilled (it produces the first-token
        logits) and the first unmatched write lands exactly on the match
        boundary — writes never land inside a matched page.  Swap-retired
        entries whose key was LRU-evicted from the store drop out of the
        index here."""
        if self.prefix is None or not len(prompt):
            return 0
        n = 0
        for i in range((len(prompt) - 1) // self.page_size):
            key = self._prefix_key(prompt, i)
            ent = self.prefix.get(key)
            if ent is None:
                break
            if ent < 0 and (self.swap is None
                            or not self.swap.contains(-ent - 1)):
                self.prefix.drop(key)
                break
            n += 1
        return n * self.page_size

    def admit_shared(self, cache: dict, slot: int, prompt, extra: int):
        """Admit a chunked-prefill slot against the prefix index ->
        ``(cache, matched_tokens)``.

        Matched raw pages are increffed (the slot becomes a co-holder of
        the same physical page); matched swap-retired pages are faulted
        back bit-exactly (batch Pallas decode) into fresh raw pages that
        the index re-adopts; ``extra`` fresh pages cover the unmatched
        suffix.  ``cur_len`` starts at ``matched_tokens``, so prefill
        chunks resume at the match boundary with zero new compilations
        (``prefill_chunk`` reads its start position in-graph)."""
        sh = self.shard_of_slot(slot)
        ps = self.page_size
        cache = dict(cache)
        shared: list[int] = []
        raw_jobs = []               # (SwapEntry, pid) for _restore_raw
        n_faulted = 0
        if self.prefix is not None:
            for i in range((len(prompt) - 1) // ps if len(prompt) else 0):
                key = self._prefix_key(prompt, i)
                ent = self.prefix.get(key)
                if ent is None:
                    break
                if ent < 0:
                    k = -ent - 1
                    if self.swap is None or not self.swap.contains(k):
                        self.prefix.drop(key)
                        break
                    if not self._free[sh]:
                        cache = self._reclaim_prefix(cache, sh, 1)
                    if not self._free[sh]:
                        break       # match shrinks; the suffix is prefilled
                    sp = self.swap.pop(k)
                    ent = self._alloc_raw(sh)       # the index's reference
                    raw_jobs.extend((e2, ent) for e2 in sp.entries)
                    self.prefix.put(key, ent)
                    n_faulted += 1
                self._incref(ent)                   # the slot's reference
                self.prefix.touch(key)
                shared.append(ent)
        if raw_jobs:
            with span("swap.prefix_fault", self.tracer, slot=slot,
                      pages=n_faulted):
                cache = self._restore_raw(cache, raw_jobs)

        extra = max(min(extra, self.pages_per_slot - len(shared)), 0)
        free = self._free[sh]
        if len(free) < extra:
            cache = self._reclaim_prefix(cache, sh, extra - len(free))
        if len(free) < extra:
            for pid in shared:      # undo: the admission failed whole
                self._decref(pid)
            raise OutOfPages(f"shard {sh}: slot {slot} needs {extra} "
                             f"pages past its shared prefix, "
                             f"{len(free)} free")
        pids = [self._alloc_raw(sh) for _ in range(extra)]
        self._slot_pages[slot] = shared + pids
        self._skip[slot] = set()
        row = np.zeros(self.pages_per_slot, np.int32)
        row[: len(shared) + extra] = shared + pids
        cache["page_table"] = cache["page_table"].at[slot].set(
            jnp.asarray(row))
        cache["cur_len"] = cache["cur_len"].at[slot].set(len(shared) * ps)
        if n_faulted and self.telemetry is not None:
            self.telemetry.registry.counter(
                "kvcache_fault_pages_total").inc(n_faulted)
        return cache, len(shared) * ps

    def register_prefix(self, slot: int, prompt, n_tokens: int) -> None:
        """Publish the slot's fully-prefilled, page-aligned prompt blocks
        into the index (called after each prefill chunk lands).

        Caps at ``len(prompt) // page_size`` blocks: a full prompt block
        is never written again (the first decode write lands at position
        ``len(prompt)``, in a later block), so published pages are
        immutable while referenced.  Blocks whose content is already
        indexed keep the incumbent copy (LRU-touched, not replaced)."""
        if self.prefix is None:
            return
        pages = self._slot_pages.get(slot)
        if pages is None:
            return
        nb = min(min(n_tokens, len(prompt)) // self.page_size, len(pages))
        for i in range(nb):
            pid = pages[i]
            if not (GARBAGE_PAGE < pid < self.n_pages):
                continue            # cold/swapped entries are not shareable
            key = self._prefix_key(prompt, i)
            if self.prefix.get(key) is not None:
                self.prefix.touch(key)
                continue
            self.prefix.put(key, pid)
            self._incref(pid)

    def make_writable(self, cache: dict, slot: int, lo: int, hi: int):
        """Copy-on-write guard: split any shared raw page of ``slot``
        covering positions ``[lo, hi]`` into a private device copy before
        an in-graph write lands there.

        Block-aligned matching makes this structurally unreachable on the
        normal path (writes start at the match boundary and full prompt
        blocks are never rewritten), so it is a safety invariant, not the
        common path; ``n_cow_splits`` counts actual splits."""
        pages = self._slot_pages.get(slot)
        if pages is None or self.prefix is None:
            return cache
        ps = self.page_size
        sh = self.shard_of_slot(slot)
        for p in range(lo // ps, min(hi // ps, len(pages) - 1) + 1):
            pid = pages[p]
            if (not (GARBAGE_PAGE < pid < self.n_pages)
                    or self._ref.get(pid, 1) <= 1):
                continue
            if not self._free[sh]:
                cache = self._reclaim_prefix(cache, sh, 1)
            if not self._free[sh]:
                raise OutOfPages(f"shard {sh}: CoW split of slot {slot} "
                                 f"page {p} has no free page")
            new = self._alloc_raw(sh)
            cache = dict(cache)
            for section, name, kind, stacked in self._groups():
                if kind not in PAGED_KINDS:
                    continue
                leafd = dict(cache[section][name])
                for kn in ("k", "v"):
                    pool = leafd[f"{kn}_pool"]
                    leafd[f"{kn}_pool"] = (
                        pool.at[:, new].set(pool[:, pid]) if stacked
                        else pool.at[new].set(pool[pid]))
                cache[section] = {**cache[section], name: leafd}
            self._decref(pid)
            pages[p] = new
            cache["page_table"] = cache["page_table"].at[slot, p].set(new)
            self.n_cow_splits += 1
        return cache

    def _reclaim_prefix(self, cache: dict, sh: int, need: int):
        """Retire up to ``need`` index-only prefix pages (refcount 1 — no
        slot co-holds them) on shard ``sh``, least recently matched
        first.  With a swap store attached each page is entropy-coded
        into the store's **unpinned** LRU prefix cache (it faults back
        bit-exactly on the next match); when the store cannot hold it —
        or there is no store — the entry is dropped.  Either way the raw
        page frees, so every allocation site can treat index-only pages
        as reclaimable headroom."""
        if self.prefix is None or need <= 0:
            return cache
        freed = 0
        for key in self.prefix.lru_keys():
            if freed >= need:
                break
            ent = self.prefix.get(key)
            if (ent is None or ent < 0
                    or ent // self.pages_per_shard != sh
                    or self._ref.get(ent, 1) != 1):
                continue
            k = None
            if self.swap is not None:
                sp = self._encode_raw_page(cache, ent)
                k = self.swap.put(sp, sh, pinned=False)
            if k is not None:
                self.prefix.put(key, -(k + 1))      # keeps LRU position
                self.n_prefix_retired += 1
            else:
                self.prefix.drop(key)
                self.n_prefix_dropped += 1
            self._decref(ent)
            freed += 1
        return cache

    def reclaimable_pages(self, shard: int = 0) -> int:
        """Raw pages held only by the prefix index (refcount 1) on
        ``shard`` — on-demand headroom the scheduler counts as available
        when sizing admission."""
        if self.prefix is None:
            return 0
        return sum(1 for e in self.prefix.entries()
                   if e > 0 and e // self.pages_per_shard == shard
                   and self._ref.get(e, 0) == 1)

    def n_shared_pages(self) -> int:
        """Raw index pages currently co-held by at least one slot."""
        if self.prefix is None:
            return 0
        return sum(1 for e in self.prefix.entries()
                   if e > 0 and self._ref.get(e, 0) > 1)

    # -- cold compression --------------------------------------------------

    def compress_cold(self, cache: dict, slots):
        """:func:`compress_cold_pages` for each ``(slot, pos)`` of
        ``slots``, under one ``kv.code_pages`` span whose end marker
        counts the pages coded, the pages left raw and the coded bytes."""
        tally = [0, 0, 0]
        with span("kv.code_pages", self.tracer, slots=len(slots)) as sp:
            for slot, pos in slots:
                cache = self.compress_cold_pages(cache, slot, pos, tally)
            sp.done(pages=tally[0], raw=tally[1], bytes=tally[2])
        return cache

    def compress_cold_pages(self, cache: dict, slot: int, pos: int,
                            tally: list | None = None):
        """Entropy-code the slot's full (non-tail) pages into the cold pool.

        ``pos`` is the next write position; pages strictly below
        ``pos // page_size`` are complete and never written again.
        ``tally``, if given, adds [pages coded, pages left raw, bytes]."""
        if not self.compress or slot not in self._slot_pages:
            return cache
        sh = self.shard_of_slot(slot)
        full = min(pos // self.page_size, len(self._slot_pages[slot]))
        for p in range(full):
            # shared prefix pages (refcount > 1) stay raw: compressing
            # the slot's copy would duplicate a page other holders still
            # gather from, defeating the one-physical-copy invariant
            if (self._slot_pages[slot][p] >= self.n_pages
                    or p in self._skip[slot]
                    or self._ref.get(self._slot_pages[slot][p], 1) > 1):
                continue
            if not self._cold_free[sh]:
                return cache
            with span("kv.code_page", self.tracer, slot=slot,
                      page=p) as sp:
                cache, nbytes = self._compress_one(cache, slot, p)
                if nbytes is None:
                    self._skip[slot].add(p)
                    sp.done(raw=1)
            if tally is not None:
                tally[0] += nbytes is not None
                tally[1] += nbytes is None
                tally[2] += nbytes or 0
        return cache

    def _compress_one(self, cache: dict, slot: int, p: int):
        """(cache, coded bytes) with page ``p`` of ``slot`` moved into a
        cold slot, or (cache, None) when it does not fit the stride
        budget and stays raw."""
        pid = self._slot_pages[slot][p]
        subs = []                   # (section, name, stacked, kn, u, page)
        tr = self.tracer
        with span("kv.page_fetch", tr):
            for section, name, kind, stacked in self._groups():
                if kind not in PAGED_KINDS:
                    continue
                leafd = cache[section][name]
                for kn in ("k", "v"):
                    pool = leafd[f"{kn}_pool"]
                    for u in (range(self.n_units) if stacked else (None,)):
                        # slice the one page on device; only page-sized
                        # data crosses to the host for encoding
                        subs.append((section, name, stacked, kn, u,
                                     np.asarray(pool[u, pid] if stacked
                                                else pool[pid])))
        enc = []
        with span("kv.page_encode", tr):
            for *where, page in subs:
                cp = codec.encode_page(page)
                if cp.stride > self.stride_budget:
                    return cache, None          # incompressible: stay raw
                enc.append((*where, cp))

        cslot = self._cold_free[self.shard_of_slot(slot)].pop()
        total = 0
        cache = dict(cache)
        with span("kv.page_install", tr):
            for section, name, stacked, kn, u, cp in enc:
                pay = np.zeros((self.stride_budget, LANES), np.uint8)
                pay[: cp.stride] = cp.payload
                leafd = dict(cache[section][name])
                idx = (u, cslot) if stacked else (cslot,)
                leafd[f"{kn}_cpl"] = leafd[f"{kn}_cpl"].at[idx].set(pay)
                leafd[f"{kn}_csm"] = leafd[f"{kn}_csm"].at[idx].set(
                    cp.signmant)
                leafd[f"{kn}_ctab"] = leafd[f"{kn}_ctab"].at[idx].set(
                    cp.tables())
                leafd[f"{kn}_cperm"] = leafd[f"{kn}_cperm"].at[idx].set(
                    cp.perm)
                cache[section] = {**cache[section], name: leafd}
                total += cp.nbytes()

        entry = self.n_pages + cslot
        self._slot_pages[slot][p] = entry
        cache["page_table"] = cache["page_table"].at[slot, p].set(entry)
        self._decref(pid)
        self._cold_bytes[cslot] = total
        return cache, total

    # -- accounting --------------------------------------------------------

    def stats(self) -> dict:
        """Live memory accounting (bytes; 'raw_equiv' = same pages kept
        uncompressed, 'monolithic' = the replaced (B, max_len) cache).

        ``pages_in_use_per_shard`` counts raw+cold pages held by each batch
        shard's slots — the load-balance signal for sharded serving."""
        # physical accounting: with prefix sharing a pid can appear in
        # several slots' page lists (and in the index with no slot at
        # all) but occupies device memory exactly once
        raw_phys = {e for pages in self._slot_pages.values()
                    for e in pages if GARBAGE_PAGE < e < self.n_pages}
        prefix_resident = prefix_only = 0
        if self.prefix is not None:
            for e in self.prefix.entries():
                if e > 0:
                    prefix_resident += 1
                    if self._ref.get(e, 0) == 1:
                        prefix_only += 1
                    raw_phys.add(e)
        raw = len(raw_phys)
        cold = len(self._cold_bytes)
        swapped = sum(1 for pages in self._slot_pages.values()
                      for e in pages if e < 0)
        per_shard = [0] * self.n_shards
        for slot, pages in self._slot_pages.items():
            per_shard[self.shard_of_slot(slot)] += sum(
                1 for e in pages if e > GARBAGE_PAGE)
        page_bytes = (self.n_attn_layers * 2 * self.page_elems
                      * self.dtype.itemsize)
        cold_uniform = self.n_attn_layers * 2 * (
            self.stride_budget * LANES + self.sm_nbytes
            + 4 * (3 * self.max_code_len + self.n_sym))
        out = {
            "page_size": self.page_size,
            "n_shards": self.n_shards,
            "pages_in_use_per_shard": per_shard,
            "free_pages_per_shard": self.free_pages_per_shard,
            "pages_in_use": raw,
            "cold_pages_in_use": cold,
            "swapped_pages": swapped,
            "page_bytes": page_bytes,
            "raw_bytes_in_use": raw * page_bytes,
            "cold_bytes_ragged": sum(self._cold_bytes.values()),
            "cold_bytes_uniform": cold * cold_uniform,
            "cache_bytes_paged": raw * page_bytes
            + sum(self._cold_bytes.values()),
            "cache_bytes_raw_equiv": (raw + cold) * page_bytes,
            "monolithic_bytes": self.max_batch * self.pages_per_slot
            * page_bytes,
        }
        if self.prefix is not None:
            out.update({
                "prefix_index_blocks": len(self.prefix),
                "prefix_resident_blocks": prefix_resident,
                "prefix_reclaimable_pages": prefix_only,
                "prefix_shared_pages": self.n_shared_pages(),
                "prefix_retired_total": self.n_prefix_retired,
                "prefix_dropped_total": self.n_prefix_dropped,
                "prefix_cow_splits_total": self.n_cow_splits,
            })
        if self.swap is not None:
            out.update(self.swap.stats())
        return out
