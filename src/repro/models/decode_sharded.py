"""Distributed decode attention: sequence-sharded KV cache + stat merge,
and the mesh-sharded paged-cache decode path.

The decode-step profile (§Perf cell 3) showed GSPMD gathering f32 cache
chunks across the model axis every (layer x kv-chunk) when the cache
shards on head_dim (the only shardable dim for MQA archs like granite).
The scalable structure shards the cache on the *sequence* dim instead:

  * each model shard owns a contiguous S/n_model slice of the cache with
    full head_dim — the new token's K/V is written only by the owning
    shard (a masked in-place update);
  * each shard attends over its local slice, producing an *unnormalized*
    accumulator plus online-softmax row stats (m, l);
  * shards merge with one tiny all-gather of (o_partial, m, l) —
    O(B x H x D) bytes per layer instead of O(B x S x D) cache gathers.

This is the flash-attention merge rule applied across devices (tree
attention); forward-only, so no custom VJP is needed.

:func:`paged_decode_attention_sharded` applies the same structure to the
**paged** cache (``repro.kvcache``) under a mesh:

  * the page pool's page dim and the page table's batch dim shard over
    the mesh's **batch axes** (``runtime.sharding.batch_axes``); the
    allocator (``PagedKVCache(n_shards=...)``) only ever hands a slot
    pages from its own shard's range, so page scatter/gather is fully
    local — zero cross-device page traffic, and (with no model axis) the
    local path is the *same program* as the single-device paged decode,
    making sharded serving bit-identical to the monolithic baseline;
  * an optional **model** axis splits each slot's logical pages
    round-robin across model shards (page ``p`` -> shard ``p % n_model``,
    a compute/VMEM split of the replicated local pool): every model shard
    gathers only its page columns (entropy-decoding cold pages from the
    local shard only), attends with a per-position validity mask, and
    shards merge with the same tiny (acc, m, l) all-gather as above.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kvcache import paged as paged_kv
from .flash_attention import _gqa_scores, _gqa_combine

F32 = jnp.float32
NEG = -1e30


def _local_attend_stats(q, k, v, kv_len_local, softcap: float):
    """One-token attention over the local cache slice, unnormalized.

    q: (B, Hq, 1, D); k/v: (B, Hkv, S_loc, D); kv_len_local: scalar.
    Returns (acc (B, Hq, 1, D) f32, m (B, Hq, 1) f32, l (B, Hq, 1) f32)."""
    D = q.shape[-1]
    s = _gqa_scores(q * (D ** -0.5), k)            # (B, Hq, 1, S_loc) f32
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    pos = jnp.arange(k.shape[2])
    s = jnp.where((pos < kv_len_local)[None, None, None, :], s, NEG)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where((pos < kv_len_local)[None, None, None, :], p, 0.0)
    l = p.sum(axis=-1)
    acc = _gqa_combine(p.astype(v.dtype), v)       # f32 accumulate
    return acc, m, l


def decode_attention_update_sharded(q, k_cache, v_cache, new_k, new_v,
                                    vlen, slot, mesh, *,
                                    softcap: float = 0.0):
    """Sharded decode: cache update + attention + merge, one shard_map.

    q/new_k/new_v: (B, H*, 1, D); caches: (B, Hkv, S, D) sharded on S over
    ``model``; ``vlen``: scalar count of valid cache slots *after* the
    update (cur_len+1, or min(cur_len+1, W) for ring buffers); ``slot``:
    scalar write position (cur_len, or cur_len % W for rings).
    Returns (o (B, Hq, 1, D), new_k_cache, new_v_cache)."""
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    ba = ba if len(ba) != 1 else ba[0]
    B, S = k_cache.shape[0], k_cache.shape[2]
    n_model = mesh.shape["model"]
    s_loc = S // n_model
    b_ax = ba if B % _axes_size(mesh, ba) == 0 else None

    def body(q_l, kc, vc, nk, nv, vlen_g, slot_g):
        i = jax.lax.axis_index("model")
        lo = i * s_loc
        slot_l = jnp.clip(slot_g - lo, 0, s_loc - 1)
        owned = (slot_g >= lo) & (slot_g < lo + s_loc)
        # write the new token only on the owning shard (masked update:
        # non-owners re-write the existing value at slot_l)
        cur_k = jax.lax.dynamic_slice_in_dim(kc, slot_l, 1, axis=2)
        cur_v = jax.lax.dynamic_slice_in_dim(vc, slot_l, 1, axis=2)
        up_k = jnp.where(owned, nk.astype(kc.dtype), cur_k)
        up_v = jnp.where(owned, nv.astype(vc.dtype), cur_v)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, up_k, slot_l, axis=2)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, up_v, slot_l, axis=2)

        kv_len_local = jnp.clip(vlen_g - lo, 0, s_loc)
        acc, m, l = _local_attend_stats(q_l, kc, vc, kv_len_local, softcap)
        o = _merge_stats(acc, m, l, "model")
        return o.astype(vc.dtype), kc, vc

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(b_ax, None, None, None),          # q
                  P(b_ax, None, "model", None),       # k cache
                  P(b_ax, None, "model", None),       # v cache
                  P(b_ax, None, None, None),          # new k
                  P(b_ax, None, None, None),          # new v
                  P(), P()),
        out_specs=(P(b_ax, None, None, None),
                   P(b_ax, None, "model", None),
                   P(b_ax, None, "model", None)),
        check_vma=False,
    )(q, k_cache, v_cache, new_k, new_v, vlen, slot)


def _axes_size(mesh, ba):
    n = 1
    for a in (ba if isinstance(ba, tuple) else (ba,)):
        n *= mesh.shape[a]
    return n


def _merge_stats(acc, m, l, axis_name):
    """Flash-attention merge of per-shard softmax stats across ``axis_name``.

    acc: (B, Hq, 1, D) unnormalized f32 accumulator; m/l: (B, Hq, 1) f32
    row max / row sum.  One tiny all-gather of (acc, m, l) — O(B x Hq x D)
    bytes — then the tree-attention combine.  Shards with no valid
    position carry m == NEG and weigh in as exp(NEG - m_g) == 0."""
    acc_all = jax.lax.all_gather(acc, axis_name)     # (n, B, Hq, 1, D)
    m_all = jax.lax.all_gather(m, axis_name)         # (n, B, Hq, 1)
    l_all = jax.lax.all_gather(l, axis_name)
    m_g = m_all.max(axis=0)
    w = jnp.exp(m_all - m_g[None])                   # (n, B, Hq, 1)
    denom = (l_all * w).sum(axis=0)
    num = (acc_all * w[..., None]).sum(axis=0)
    return num / jnp.maximum(denom, 1e-30)[..., None]


def _attend_stats_masked(q, k, v, valid, softcap: float):
    """One-token attention over a gathered history with an explicit
    per-position validity mask, unnormalized.

    q: (B, Hq, 1, D); k/v: (B, Hkv, S, D); valid: (B, S) bool.
    Returns (acc (B, Hq, 1, D) f32, m (B, Hq, 1) f32, l (B, Hq, 1) f32)."""
    D = q.shape[-1]
    s = _gqa_scores(q * (D ** -0.5), k).astype(F32)  # (B, Hq, 1, S)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    vm = valid[:, None, None, :]
    s = jnp.where(vm, s, NEG)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(vm, p, 0.0)
    l = p.sum(axis=-1)
    acc = _gqa_combine(p.astype(v.dtype), v).astype(F32)
    return acc, m, l


# --------------------------------------------------------------------------
# paged cache under a mesh
# --------------------------------------------------------------------------

def paged_shardable(cache: dict, page_table, cur_len, mesh) -> bool:
    """Whether this paged cache leaf-dict can take the sharded decode path.

    Requires per-slot timelines, at least one mesh axis of size > 1, and
    batch / pool / cold dims divisible by the batch-axes size (the
    ``PagedKVCache(n_shards=batch_axes_size)`` layout guarantees this)."""
    if mesh is None or page_table is None or cur_len.ndim != 1:
        return False
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_ba = int(np.prod([mesh.shape[a] for a in ba])) if ba else 1
    n_model = mesh.shape["model"] if "model" in mesh.axis_names else 1
    if n_ba == 1 and n_model == 1:
        return False
    B = page_table.shape[0]
    if B % n_ba:
        return False
    if cache["k_pool"].shape[0] % n_ba:
        return False
    if "k_cpl" in cache and cache["k_cpl"].shape[0] % n_ba:
        return False
    return True


def chunk_shardable(cache: dict, mesh) -> bool:
    """Whether a chunk-prefill call on this paged leaf-dict should take
    :func:`paged_prefill_chunk_sharded` — a mesh with batch axes of size
    > 1 and pool/cold dims divisible by that size (the
    ``PagedKVCache(n_shards=...)`` layout).  A model-axis-only mesh
    returns False; the engine gates chunked prefill off there."""
    if mesh is None:
        return False
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_ba = int(np.prod([mesh.shape[a] for a in ba])) if ba else 1
    if n_ba == 1:
        return False
    if cache["k_pool"].shape[0] % n_ba:
        return False
    if "k_cpl" in cache and cache["k_cpl"].shape[0] % n_ba:
        return False
    return True


def paged_prefill_chunk_sharded(q, new_k, new_v, cache, row, slot,
                                positions, n_valid, mesh, *,
                                n_slots: int, softcap: float = 0.0):
    """Chunked prefill for one slot under a batch-axes mesh.

    q/new_k/new_v: (1, H*, C, D) — one padded chunk; ``row``: (P,) the
    slot's page-table row (global ids); ``slot``/``n_valid``: traced
    scalars; ``positions``: (C,) absolute token positions; ``n_slots``:
    the engine's static ``max_batch`` (slot ``s`` lives on batch shard
    ``s // (n_slots / n_ba)``, the allocator's contiguous slot ranges).

    The slot's pages all live on the batch shard that owns the slot
    (per-shard id ranges), so the **owning shard runs the exact
    single-device chunk program on its local pool** — write the chunk
    K/V, gather the slot's history (local cold pages entropy-decoded),
    attend causally from ``q_offset = positions[0]``.  Non-owner shards
    park their writes out of range (dropped) and mask every key
    (``kv_len = 0`` → a zero partial), and one ``psum`` over the batch
    axes replicates the owner's output — bit-identical to the
    single-device chunk, like the sharded decode path.

    Returns (o (1, Hq, C, D), new_k_pool, new_v_pool)."""
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_ba = _axes_size(mesh, ba)
    b_ax = ba if len(ba) != 1 else ba[0]

    k_pool, v_pool = cache["k_pool"], cache["v_pool"]
    cold_k = paged_kv.cold_leaves(cache, "k")
    cold_v = paged_kv.cold_leaves(cache, "v")
    has_cold = cold_k is not None
    n_pool = k_pool.shape[0]
    n_cold = cold_k[0].shape[0] if has_cold else 0
    from .layers import blockwise_attention

    def body(q_l, nk, nv, kp, vp, row_g, pos, slot_g, nv_g, *cold_flat):
        d = jnp.int32(0)
        for a in ba:
            d = d * mesh.shape[a] + jax.lax.axis_index(a)
        L_loc = kp.shape[0]                     # n_pool // n_ba
        lo = d * L_loc
        c_loc = n_cold // n_ba
        cold_lo = d * c_loc
        ck = cold_flat[:4] if has_cold else None
        cv = cold_flat[4:] if has_cold else None
        # contiguous slot ranges per batch shard (PagedKVCache layout):
        # the owner holds every one of the slot's pages locally
        owned = (slot_g // (n_slots // n_ba)) == d
        is_cold = row_g >= n_pool
        raw_loc = row_g - lo
        loc = jnp.where(is_cold, L_loc + (row_g - n_pool - cold_lo),
                        raw_loc)
        wrow = jnp.where((row_g >= lo) & (row_g < lo + L_loc), raw_loc,
                         L_loc)
        nv_l = jnp.where(owned, nv_g, 0)        # park non-owner writes
        kp = paged_kv.page_write_chunk(kp, wrow, pos, nk, nv_l)
        vp = paged_kv.page_write_chunk(vp, wrow, pos, nv, nv_l)
        gtbl = jnp.clip(loc, 0, L_loc + c_loc - 1)
        k_hist = paged_kv.page_gather(kp, gtbl[None], cpool=ck)
        v_hist = paged_kv.page_gather(vp, gtbl[None], cpool=cv)
        o = blockwise_attention(
            q_l, k_hist, v_hist, causal=True, q_offset=pos[0],
            kv_len=jnp.where(owned, pos[0] + nv_g, 0),
            attn_softcap=softcap)
        o = jax.lax.psum(jnp.where(owned, o, jnp.zeros_like(o)), ba)
        return o, kp, vp

    pool_spec = P(b_ax, None, None, None)
    cold_specs = tuple(P(b_ax, *(None,) * (x.ndim - 1))
                       for x in ((*cold_k, *cold_v) if has_cold else ()))
    rep = P(None, None, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep,                 # q, new k, new v
                  pool_spec, pool_spec,          # k/v pool
                  P(None),                       # page-table row
                  P(None),                       # positions
                  P(), P(),                      # slot, n_valid
                  *cold_specs),
        out_specs=(rep, pool_spec, pool_spec),
        check_vma=False,
    )(q, new_k, new_v, k_pool, v_pool, row, positions, slot, n_valid,
      *((*cold_k, *cold_v) if has_cold else ()))


def paged_decode_attention_sharded(q, new_k, new_v, cache, page_table,
                                   cur_len, mesh, *, softcap: float = 0.0):
    """Sharded paged decode: page write + gather + attention, one shard_map.

    q/new_k/new_v: (B, H*, 1, D); ``cache`` is one attention group's leaf
    dict (``k_pool``/``v_pool`` (n_pages, Hkv, ps, hd) plus the cold-pool
    leaves when present); ``page_table``: (B, P) global page ids;
    ``cur_len``: (B,) per-slot write positions.

    Sharding invariants (see module docstring): pool page dim, cold-slot
    dim, page-table batch dim and ``cur_len`` shard over the batch axes;
    q/new K/V shard their batch dim likewise and replicate over ``model``.
    With no model axis each batch shard runs the exact single-device
    program on its local rows/pages (bit-identical outputs); with a model
    axis each model shard attends over logical pages ``p % n_model == m``
    and the shards merge softmax stats.

    Returns (o (B, Hq, 1, D), new_k_pool, new_v_pool).
    """
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_ba = _axes_size(mesh, ba) if ba else 1
    n_model = mesh.shape["model"] if "model" in mesh.axis_names else 1
    b_ax = (ba if len(ba) != 1 else ba[0]) if ba else None

    k_pool, v_pool = cache["k_pool"], cache["v_pool"]
    cold_k = paged_kv.cold_leaves(cache, "k")
    cold_v = paged_kv.cold_leaves(cache, "v")
    has_cold = cold_k is not None
    n_pool = k_pool.shape[0]
    ps = k_pool.shape[2]
    P_log = page_table.shape[1]
    n_cold = cold_k[0].shape[0] if has_cold else 0
    from .layers import decode_attention

    def body(q_l, nk, nv, kp, vp, tbl, clen, *cold_flat):
        # linear batch-shard index over the (possibly composite) batch axes
        d = jnp.int32(0)
        for a in ba:
            d = d * mesh.shape[a] + jax.lax.axis_index(a)
        L_loc = kp.shape[0]                     # n_pool // n_ba
        lo = d * L_loc
        c_loc = n_cold // n_ba
        cold_lo = d * c_loc
        ck = cold_flat[:4] if has_cold else None
        cv = cold_flat[4:] if has_cold else None

        # global -> local ids.  Raw local pages land in [0, L_loc); local
        # cold slots in [L_loc, L_loc + c_loc); anything else (another
        # shard's pages, or the garbage id 0 on shards with lo > 0) is
        # clamped/dropped and masked out of the attention below.
        is_cold = tbl >= n_pool
        raw_loc = tbl - lo
        loc = jnp.where(is_cold, L_loc + (tbl - n_pool - cold_lo), raw_loc)
        # writes: only raw local tail pages; everything else out of range
        # (mode="drop" in page_write) so non-owners never touch the pool
        wtbl = jnp.where((tbl >= lo) & (tbl < lo + L_loc), raw_loc, L_loc)
        kp = paged_kv.page_write(kp, wtbl, clen, nk)
        vp = paged_kv.page_write(vp, wtbl, clen, nv)

        if n_model == 1:
            # every page of a local slot is local: run the exact
            # single-device paged decode on the shard's rows
            gtbl = jnp.clip(loc, 0, L_loc + c_loc - 1)
            k_hist = paged_kv.page_gather(kp, gtbl, cpool=ck)
            v_hist = paged_kv.page_gather(vp, gtbl, cpool=cv)
            o = decode_attention(q_l, k_hist, v_hist, kv_len=clen + 1,
                                 attn_softcap=softcap)
            return o, kp, vp

        # model axis: logical page p belongs to model shard p % n_model
        m_idx = jax.lax.axis_index("model")
        P_m = -(-P_log // n_model)              # static ceil
        col = m_idx + n_model * jnp.arange(P_m)             # (P_m,)
        sub = jnp.take(jnp.clip(loc, 0, L_loc + c_loc - 1),
                       jnp.minimum(col, P_log - 1), axis=1)  # (B_loc, P_m)
        k_hist = paged_kv.page_gather(kp, sub, cpool=ck)
        v_hist = paged_kv.page_gather(vp, sub, cpool=cv)
        # validity of gathered position j*ps + t  <->  global position
        # col[j]*ps + t, masked by the slot's live length and col < P
        pos = (col[:, None] * ps + jnp.arange(ps)[None]).reshape(-1)
        valid = (pos[None, :] < (clen + 1)[:, None]) \
            & (col < P_log).repeat(ps)[None, :]
        acc, m, l = _attend_stats_masked(q_l, k_hist, v_hist, valid,
                                         softcap)
        o = _merge_stats(acc, m, l, "model").astype(vp.dtype)
        return o, kp, vp

    pool_spec = P(b_ax, None, None, None)
    cold_specs = tuple(P(b_ax, *(None,) * (x.ndim - 1))
                       for x in ((*cold_k, *cold_v) if has_cold else ()))
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(b_ax, None, None, None),            # q
                  P(b_ax, None, None, None),            # new k
                  P(b_ax, None, None, None),            # new v
                  pool_spec, pool_spec,                 # k/v pool
                  P(b_ax, None),                        # page table
                  P(b_ax),                              # cur_len
                  *cold_specs),
        out_specs=(P(b_ax, None, None, None), pool_spec, pool_spec),
        check_vma=False,
    )(q, new_k, new_v, k_pool, v_pool, page_table, cur_len,
      *((*cold_k, *cold_v) if has_cold else ()))
    return out
