"""Unified model: decoder-only LMs (dense / MoE / hybrid / ssm) and the
whisper encoder-decoder, built from ``ArchConfig``.

Layer stacking uses **scan-over-units**: one unit = one repetition of the
config's per-layer ``pattern`` (e.g. ("local","attn") for gemma2).  Units
with identical structure are stacked and run under ``lax.scan`` — one traced
copy regardless of depth, which bounds compile time for the 40-cell dry-run
and gives the remat boundary.  ``n_layers % unit`` leftover layers run
unrolled as the "tail".

Three entry points (all pure functions of (params, inputs)):
  forward(params, cfg, tokens [, frames])         -> logits       (train)
  prefill(params, cfg, tokens [, frames])         -> (logits, cache)
  decode_step(params, cfg, token, cache)          -> (logits, cache)

Caches are pytrees with static shapes (`init_cache`) so decode steps lower
with ``jax.jit`` + ShapeDtypeStructs in the multi-pod dry-run.
"""
from __future__ import annotations

from functools import partial, wraps

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.store import weights_replicated_over
from repro.kvcache import paged as paged_kv
from . import recurrent as rec
from .layers import (F32, apply_rope, blockwise_attention, decode_attention,
                     layer_norm, local_attention, mat, mlp_apply, mlp_init,
                     rms_norm)
from .moe import moe_apply, moe_init

ATTN_KINDS = ("attn", "local", "nope")


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _attn_init(rng, cfg: ArchConfig, dtype):
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(rng, 5)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, cfg.n_heads * hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, cfg.n_kv_heads * hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, cfg.n_kv_heads * hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (cfg.n_heads * hd, d), dtype) * s,
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _layer_init(rng, cfg: ArchConfig, kind: str, dtype):
    ks = jax.random.split(rng, 4)
    d = cfg.d_model
    p = {"norm1": jnp.zeros((d,), dtype)}
    if kind in ATTN_KINDS:
        p["attn"] = _attn_init(ks[0], cfg, dtype)
    elif kind == "rglru":
        p["rglru"] = rec.rglru_init(ks[0], d, d, dtype)
    elif kind == "slstm":
        p["cell"] = rec.slstm_init(ks[0], d, cfg.n_heads, dtype)
    elif kind == "mlstm":
        p["cell"] = rec.mlstm_init(ks[0], d, cfg.n_heads, dtype)
    else:
        raise ValueError(kind)
    has_ffn = cfg.d_ff > 0 or cfg.n_experts > 0
    if kind in ("slstm", "mlstm") and cfg.d_ff == 0:
        has_ffn = False
    if has_ffn:
        p["norm2"] = jnp.zeros((d,), dtype)
        if cfg.n_experts:
            p["moe"] = moe_init(ks[1], d, cfg.n_experts, cfg.moe_d_ff,
                                cfg.n_shared_experts, cfg.moe_d_ff,
                                cfg.top_k, dtype)
        else:
            p["mlp"] = mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_type, dtype)
    if cfg.post_norms:
        p["post_norm1"] = jnp.zeros((d,), dtype)
        if has_ffn:
            p["post_norm2"] = jnp.zeros((d,), dtype)
    if cfg.encoder_decoder:  # decoder cross-attention
        p["norm_x"] = jnp.zeros((d,), dtype)
        p["cross"] = _attn_init(ks[2], cfg, dtype)
    return p


def _enc_layer_init(rng, cfg: ArchConfig, dtype):
    ks = jax.random.split(rng, 2)
    d = cfg.d_model
    return {
        "norm1": jnp.zeros((d,), dtype),
        "attn": _attn_init(ks[0], cfg, dtype),
        "norm2": jnp.zeros((d,), dtype),
        "mlp": mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_type, dtype),
    }


def init_params(rng, cfg: ArchConfig, dtype=jnp.float32):
    ks = jax.random.split(rng, 8)
    d, V = cfg.d_model, cfg.vocab_size
    params = {
        "embed": jax.random.normal(ks[0], (V, d), dtype) * (d ** -0.5),
        "final_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = jax.random.normal(ks[1], (d, V), dtype) * (
            d ** -0.5)

    unit = cfg.unit
    n_units = cfg.n_layers // unit
    n_tail = cfg.n_layers - n_units * unit

    def unit_init(r):
        kr = jax.random.split(r, unit)
        return {f"pos{j}": _layer_init(kr[j], cfg, cfg.pattern[j], dtype)
                for j in range(unit)}

    unit_rngs = jax.random.split(ks[2], n_units)
    params["units"] = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[unit_init(r) for r in unit_rngs])
    params["tail"] = {
        f"layer{t}": _layer_init(jax.random.split(ks[3], max(n_tail, 1))[t],
                                 cfg, cfg.layer_kind(n_units * unit + t),
                                 dtype)
        for t in range(n_tail)
    }
    if cfg.encoder_decoder:
        enc_rngs = jax.random.split(ks[4], cfg.n_encoder_layers)
        params["encoder"] = {
            "layers": jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[_enc_layer_init(r, cfg, dtype) for r in enc_rngs]),
            "final_norm": jnp.zeros((d,), dtype),
            "pos_embed": jax.random.normal(
                ks[5], (cfg.encoder_frames, d), dtype) * 0.02,
        }
    return params


# --------------------------------------------------------------------------
# sub-blocks
# --------------------------------------------------------------------------

def _qkv(p, x, cfg: ArchConfig, dtype, rope: bool, positions):
    """positions: (T,) shared, or (B, T) per-slot (serving engine)."""
    B, T, _ = x.shape
    hd = cfg.hd
    q = (x @ mat(p["wq"], dtype)).reshape(B, T, cfg.n_heads, hd)
    k = (x @ mat(p["wk"], dtype)).reshape(B, T, cfg.n_kv_heads, hd)
    v = (x @ mat(p["wv"], dtype)).reshape(B, T, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if rope:
        pos_b = (positions[None, None, :] if positions.ndim == 1
                 else positions[:, None, :])
        q = apply_rope(q, pos_b, cfg.rope_theta)
        k = apply_rope(k, pos_b, cfg.rope_theta)
    return q, k, v


def _attn_out(p, o, dtype):
    B, H, T, hd = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    return o @ mat(p["wo"], dtype)


def _self_attention_full(p, x, cfg: ArchConfig, kind: str, dtype,
                         mesh=None):
    """Full-sequence causal self attention (train / prefill)."""
    from .flash_attention import flash_attention, flash_attention_sharded
    from .layers import get_attention_impl
    T = x.shape[1]
    positions = jnp.arange(T)
    q, k, v = _qkv(p, x, cfg, dtype, rope=(kind != "nope"), positions=positions)
    if kind == "local" and (cfg.local_window < T
                            or get_attention_impl() != "flash"):
        o = local_attention(q, k, v, window=cfg.local_window,
                            attn_softcap=cfg.attn_softcap)
    elif get_attention_impl() == "flash":
        if mesh is not None and "model" in mesh.axis_names:
            o = flash_attention_sharded(q, k, v, mesh,
                                        attn_softcap=cfg.attn_softcap)
        else:
            o = flash_attention(q, k, v, True, cfg.attn_softcap)
    else:
        o = blockwise_attention(q, k, v, causal=True,
                                attn_softcap=cfg.attn_softcap)
    return _attn_out(p, o, dtype), (k, v)


def _self_attention_decode(p, x, cfg: ArchConfig, kind: str, dtype, cache,
                           cur_len, mesh=None, page_table=None):
    """One-token decode with KV cache update.

    ``cur_len`` is a scalar (shared timeline) or (B,) per-slot positions
    (continuous-batching serving engine).  A paged cache (``k_pool``
    leaves + shared ``page_table``) routes through the page-scatter /
    page-gather path; cold pages are entropy-decoded in-graph."""
    per_slot = cur_len.ndim == 1
    q, k, v = _qkv(p, x, cfg, dtype, rope=(kind != "nope"),
                   positions=cur_len[:, None] if per_slot else cur_len[None])
    if "k_pool" in cache:
        from .decode_sharded import (paged_decode_attention_sharded,
                                     paged_shardable)
        # fault-before-gather: negative page-table entries are swap
        # sentinels (``kvcache.swap`` holds the page on the host).  The
        # engine faults every *active* slot fully resident before the
        # step, so a sentinel can only belong to a vacated slot whose
        # rows are never read — clamp it to the garbage page so the
        # unconditional scatter/gather below stays in bounds.
        page_table = jnp.maximum(page_table, paged_kv.GARBAGE_PAGE)
        if paged_shardable(cache, page_table, cur_len, mesh):
            # mesh-sharded paged path: pool/table shard over the batch
            # axes (per-shard page ranges, fully local scatter/gather);
            # a model axis splits each slot's pages and merges stats
            o, k_pool, v_pool = paged_decode_attention_sharded(
                q, k, v, cache, page_table, cur_len, mesh,
                softcap=cfg.attn_softcap)
            new_cache = {**cache, "k_pool": k_pool, "v_pool": v_pool}
            return _attn_out(p, o, dtype), new_cache
        k_pool = paged_kv.page_write(cache["k_pool"], page_table, cur_len, k)
        v_pool = paged_kv.page_write(cache["v_pool"], page_table, cur_len, v)
        k_hist = paged_kv.page_gather(k_pool, page_table,
                                      cpool=paged_kv.cold_leaves(cache, "k"))
        v_hist = paged_kv.page_gather(v_pool, page_table,
                                      cpool=paged_kv.cold_leaves(cache, "v"))
        o = decode_attention(q, k_hist, v_hist, kv_len=cur_len + 1,
                             attn_softcap=cfg.attn_softcap)
        new_cache = {**cache, "k_pool": k_pool, "v_pool": v_pool}
        return _attn_out(p, o, dtype), new_cache
    W = cache["k"].shape[2]
    slot = cur_len % W if kind == "local" else cur_len
    if (mesh is not None and not per_slot and "model" in mesh.axis_names
            and W % mesh.shape["model"] == 0 and mesh.shape["model"] > 1):
        # sequence-sharded cache + cross-shard stat merge (§Perf cell 3)
        from .decode_sharded import decode_attention_update_sharded
        vlen = jnp.minimum(cur_len + 1, W) if kind == "local" \
            else cur_len + 1
        o, k_cache, v_cache = decode_attention_update_sharded(
            q, cache["k"], cache["v"], k, v, vlen, slot, mesh,
            softcap=cfg.attn_softcap)
        return _attn_out(p, o, dtype), {"k": k_cache, "v": v_cache}
    if per_slot:
        upd = jax.vmap(lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(
            c, n, s, axis=1))
        k_cache = upd(cache["k"], k, slot)
        v_cache = upd(cache["v"], v, slot)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot,
                                                      axis=2)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot,
                                                      axis=2)
    if kind == "local":
        # ring buffer: all W slots may be valid once cur_len >= W
        kv_len = jnp.minimum(cur_len + 1, W)
        # mask by validity: slots with position > cur_len are stale only
        # before wrap; kv_len handles that case since slots fill in order.
        o = decode_attention(q, k_cache, v_cache, kv_len=kv_len,
                             attn_softcap=cfg.attn_softcap)
    else:
        o = decode_attention(q, k_cache, v_cache, kv_len=cur_len + 1,
                             attn_softcap=cfg.attn_softcap)
    new_cache = {"k": k_cache, "v": v_cache}
    return _attn_out(p, o, dtype), new_cache


def _self_attention_chunk(p, x, cfg: ArchConfig, kind: str, dtype, cache,
                          page_table, slot, start, n_valid, mesh=None):
    """One prefill **chunk** for a single slot of the paged cache.

    x: (1, C, d) — a fixed-size padded chunk of the slot's prompt;
    ``slot``/``start``/``n_valid`` are traced scalars (one compilation
    serves every prompt length and chunk position).  The chunk's K/V is
    scattered into the slot's pages (``page_write_chunk``), the slot's
    whole history (previous chunks included, cold pages entropy-decoded)
    is gathered back, and the chunk attends causally over it with
    ``q_offset=start`` — resuming prefill from the existing cache prefix.
    Only paged kinds are supported; the engine gates chunked prefill to
    architectures where every layer pages ('attn'/'nope')."""
    C = x.shape[1]
    positions = start + jnp.arange(C)
    q, k, v = _qkv(p, x, cfg, dtype, rope=(kind != "nope"),
                   positions=positions)
    row = jnp.maximum(page_table[slot], paged_kv.GARBAGE_PAGE)
    from .decode_sharded import chunk_shardable, paged_prefill_chunk_sharded
    if chunk_shardable(cache, mesh):
        o, k_pool, v_pool = paged_prefill_chunk_sharded(
            q, k, v, cache, row, slot, positions, n_valid, mesh,
            n_slots=page_table.shape[0], softcap=cfg.attn_softcap)
    else:
        k_pool = paged_kv.page_write_chunk(cache["k_pool"], row, positions,
                                           k, n_valid)
        v_pool = paged_kv.page_write_chunk(cache["v_pool"], row, positions,
                                           v, n_valid)
        k_hist = paged_kv.page_gather(k_pool, row[None],
                                      cpool=paged_kv.cold_leaves(cache, "k"))
        v_hist = paged_kv.page_gather(v_pool, row[None],
                                      cpool=paged_kv.cold_leaves(cache, "v"))
        o = blockwise_attention(q, k_hist, v_hist, causal=True,
                                q_offset=start, kv_len=start + n_valid,
                                attn_softcap=cfg.attn_softcap)
    new_cache = {**cache, "k_pool": k_pool, "v_pool": v_pool}
    return _attn_out(p, o, dtype), new_cache


def _layer_apply_chunk(p, x, cfg: ArchConfig, kind: str, dtype, mesh, cache,
                       page_table, slot, start, n_valid):
    """Chunk-mode layer: decode-layer residual structure at T=C."""
    if kind not in ATTN_KINDS or kind == "local":
        raise ValueError(
            f"chunked prefill only pages 'attn'/'nope' layers, got {kind}")
    h = rms_norm(x, p["norm1"])
    o, cache = _self_attention_chunk(p["attn"], h, cfg, kind, dtype, cache,
                                     page_table, slot, start, n_valid,
                                     mesh=mesh)
    if cfg.post_norms:
        o = rms_norm(o, p["post_norm1"])
    x = x + o
    if "mlp" in p or "moe" in p:
        h2 = rms_norm(x, p["norm2"])
        o2, _ = _ffn(p, h2, cfg, dtype, mesh)
        if cfg.post_norms:
            o2 = rms_norm(o2, p["post_norm2"])
        x = x + o2
    return x, cache


def _ffn(p, x, cfg: ArchConfig, dtype, mesh):
    if "moe" in p:
        y, aux = moe_apply(p["moe"], x, cfg, mesh=mesh, dtype=dtype)
        return y, aux
    return mlp_apply(p["mlp"], x, cfg.mlp_type, dtype), jnp.zeros((), F32)


def _layer_apply_full(p, x, cfg: ArchConfig, kind: str, dtype, mesh,
                      cross_ctx=None, constrain=None):
    """Full-sequence layer (train / prefill).  Returns (x, cache, aux).

    ``constrain`` re-pins the residual stream after every block output so
    GSPMD lowers the TP partial sums as reduce-scatters back to the
    sequence-sharded layout instead of full all-reduces (§Perf cell-1
    iteration 4)."""
    constrain = constrain or (lambda x: x)
    h = rms_norm(x, p["norm1"])
    cache = {}
    if kind in ATTN_KINDS:
        o, (k, v) = _self_attention_full(p["attn"], h, cfg, kind, dtype,
                                         mesh=mesh)
        cache = {"k": k, "v": v}
    elif kind == "rglru":
        o, st = rec.rglru_apply(p["rglru"], h, dtype=dtype)
        cache = st
    elif kind == "slstm":
        o, st = rec.slstm_apply(p["cell"], h, cfg.n_heads, dtype=dtype)
        cache = st
    elif kind == "mlstm":
        o, st = rec.mlstm_apply(p["cell"], h, cfg.n_heads, dtype=dtype,
                                chunk=min(128, h.shape[1]))
        cache = st
    if cfg.post_norms:
        o = rms_norm(o, p["post_norm1"])
    x = constrain(x + o)

    if cross_ctx is not None and "cross" in p:
        hx = rms_norm(x, p["norm_x"])
        o = _cross_attention(p["cross"], hx, cross_ctx, cfg, dtype)
        x = x + o

    aux = jnp.zeros((), F32)
    if "mlp" in p or "moe" in p:
        h2 = rms_norm(x, p["norm2"])
        o2, aux = _ffn(p, h2, cfg, dtype, mesh)
        if cfg.post_norms:
            o2 = rms_norm(o2, p["post_norm2"])
        x = constrain(x + o2)
    return x, cache, aux


def _layer_apply_decode(p, x, cfg: ArchConfig, kind: str, dtype, mesh, cache,
                        cur_len, cross_kv=None, page_table=None):
    h = rms_norm(x, p["norm1"])
    if kind in ATTN_KINDS:
        o, cache = _self_attention_decode(p["attn"], h, cfg, kind, dtype,
                                          cache, cur_len, mesh=mesh,
                                          page_table=page_table)
    elif kind == "rglru":
        o, cache = rec.rglru_step(p["rglru"], h[:, 0], cache, dtype=dtype)
        o = o[:, None, :]
    elif kind == "slstm":
        o, cache = rec.slstm_step(p["cell"], h[:, 0], cache, cfg.n_heads,
                                  dtype=dtype)
        o = o[:, None, :]
    elif kind == "mlstm":
        o, cache = rec.mlstm_step(p["cell"], h[:, 0], cache, cfg.n_heads,
                                  dtype=dtype)
        o = o[:, None, :]
    if cfg.post_norms:
        o = rms_norm(o, p["post_norm1"])
    x = x + o

    if cross_kv is not None and "cross" in p:
        hx = rms_norm(x, p["norm_x"])
        q, _, _ = _qkv(p["cross"], hx, cfg, dtype, rope=False,
                       positions=cur_len[None])
        o = decode_attention(q, cross_kv["k"], cross_kv["v"],
                             kv_len=cross_kv["k"].shape[2])
        x = x + _attn_out(p["cross"], o, dtype)

    if "mlp" in p or "moe" in p:
        h2 = rms_norm(x, p["norm2"])
        o2, _ = _ffn(p, h2, cfg, dtype, mesh)
        if cfg.post_norms:
            o2 = rms_norm(o2, p["post_norm2"])
        x = x + o2
    return x, cache


def _cross_attention(p, x, ctx, cfg: ArchConfig, dtype):
    """x: (B, T, d) queries; ctx: (B, F, d) encoder output."""
    B, T, _ = x.shape
    hd = cfg.hd
    q = (x @ mat(p["wq"], dtype)).reshape(B, T, cfg.n_heads, hd)
    k = (ctx @ mat(p["wk"], dtype)).reshape(B, -1, cfg.n_kv_heads, hd)
    v = (ctx @ mat(p["wv"], dtype)).reshape(B, -1, cfg.n_kv_heads, hd)
    o = blockwise_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=False)
    return _attn_out(p, o, dtype)


# --------------------------------------------------------------------------
# encoder (whisper)
# --------------------------------------------------------------------------

def encode_frames(params, cfg: ArchConfig, frames, dtype):
    """frames: (B, F, d) precomputed frontend embeddings (stub)."""
    enc = params["encoder"]
    x = frames.astype(dtype) + mat(enc["pos_embed"], dtype)[None]

    def enc_layer(x, p):
        h = rms_norm(x, p["norm1"])
        T = h.shape[1]
        q, k, v = _qkv(p["attn"], h, cfg, dtype, rope=False,
                       positions=jnp.arange(T))
        o = blockwise_attention(q, k, v, causal=False)
        x = x + _attn_out(p["attn"], o, dtype)
        h2 = rms_norm(x, p["norm2"])
        x = x + mlp_apply(p["mlp"], h2, cfg.mlp_type, dtype)
        return x, None

    x, _ = jax.lax.scan(enc_layer, x, enc["layers"])
    return rms_norm(x, enc["final_norm"])


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------

def _serving_entry(fn):
    """A serving entry point traces its weight decodes for its ``mesh``
    (the engine replicates the weights over it)."""
    @wraps(fn)
    def run(*args, mesh=None, **kw):
        with weights_replicated_over(mesh):
            return fn(*args, mesh=mesh, **kw)
    return run


def _decode_tied_embed(params, cfg: ArchConfig, dtype):
    """``params`` with a tied embedding decoded (or cast) once, so the
    token lookup and the unembedding of one call share it — one decode
    when compressed, and the same graph for ECF8 and FP8 weights."""
    if cfg.tie_embeddings:
        return {**params, "embed": mat(params["embed"], dtype)}
    return params


def _embed(params, cfg: ArchConfig, tokens, dtype):
    x = jnp.take(mat(params["embed"], dtype), tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dtype)
    return x


def _unembed(params, cfg: ArchConfig, x, dtype):
    x = rms_norm(x, params["final_norm"])
    if cfg.tie_embeddings:
        logits = x @ mat(params["embed"], dtype).T
    else:
        logits = x @ mat(params["unembed"], dtype)
    logits = logits.astype(F32)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def _run_stack(params, cfg: ArchConfig, x, dtype, mesh, mode: str,
               cache=None, cur_len=None, cross_ctx=None, remat: bool = False,
               constrain=None):
    """Run units (scan) + tail.  mode: 'full' or 'decode'.

    ``constrain``: optional residual-stream sharding constraint, applied at
    every unit boundary (GSPMD sequence-parallelism hook, runtime/sharding).
    """
    unit = cfg.unit
    n_units = cfg.n_layers // unit
    aux_total = jnp.zeros((), F32)
    constrain = constrain or (lambda x: x)

    if mode == "full":
        def unit_body(x, unit_p):
            x = constrain(x)
            aux = jnp.zeros((), F32)
            caches = {}
            for j in range(unit):
                x, c, a = _layer_apply_full(unit_p[f"pos{j}"], x, cfg,
                                            cfg.pattern[j], dtype, mesh,
                                            cross_ctx, constrain=constrain)
                caches[f"pos{j}"] = c
                aux = aux + a
            return x, (caches, aux)

        body = jax.checkpoint(unit_body) if remat else unit_body
        x, (unit_caches, auxes) = jax.lax.scan(body, x, params["units"])
        x = constrain(x)
        aux_total = aux_total + auxes.sum()
        tail_caches = {}
        for t, (name, p) in enumerate(sorted(params["tail"].items())):
            kind = cfg.layer_kind(n_units * unit + t)
            x, c, a = _layer_apply_full(p, x, cfg, kind, dtype, mesh,
                                        cross_ctx)
            tail_caches[name] = c
            aux_total = aux_total + a
        return x, {"units": unit_caches, "tail": tail_caches}, aux_total

    # decode
    page_table = cache.get("page_table")

    def unit_body(x, xs):
        unit_p, unit_c = xs
        new_c = {}
        for j in range(unit):
            x, c = _layer_apply_decode(unit_p[f"pos{j}"], x, cfg,
                                       cfg.pattern[j], dtype, mesh,
                                       unit_c[f"pos{j}"], cur_len,
                                       cross_kv=(unit_c.get("cross")
                                                 if cfg.encoder_decoder
                                                 else None),
                                       page_table=page_table)
            new_c[f"pos{j}"] = c
        if cfg.encoder_decoder and "cross" in unit_c:
            new_c["cross"] = unit_c["cross"]
        return x, new_c

    x, new_unit_caches = jax.lax.scan(unit_body, x,
                                      (params["units"], cache["units"]))
    new_tail = {}
    for t, (name, p) in enumerate(sorted(params["tail"].items())):
        kind = cfg.layer_kind(n_units * unit + t)
        tc = cache["tail"][name]
        x, c = _layer_apply_decode(p, x, cfg, kind, dtype, mesh, tc, cur_len,
                                   cross_kv=tc.get("cross"),
                                   page_table=page_table)
        if cfg.encoder_decoder and "cross" in tc:
            c["cross"] = tc["cross"]
        new_tail[name] = c
    return x, {"units": new_unit_caches, "tail": new_tail}, aux_total


def forward(params, cfg: ArchConfig, tokens, frames=None, mesh=None,
            remat: bool = False, constrain=None):
    """Training forward -> logits (B, T, V)."""
    dtype = jnp.dtype(cfg.dtype)
    params = _decode_tied_embed(params, cfg, dtype)
    x = _embed(params, cfg, tokens, dtype)
    cross_ctx = (encode_frames(params, cfg, frames, dtype)
                 if cfg.encoder_decoder else None)
    x, _, aux = _run_stack(params, cfg, x, dtype, mesh, "full",
                           cross_ctx=cross_ctx, remat=remat,
                           constrain=constrain)
    return _unembed(params, cfg, x, dtype), aux


def loss_fn(params, cfg: ArchConfig, tokens, labels, frames=None, mesh=None,
            remat: bool = False, aux_weight: float = 0.01, constrain=None):
    logits, aux = forward(params, cfg, tokens, frames=frames, mesh=mesh,
                          remat=remat, constrain=constrain)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = nll.mean() + aux_weight * aux
    return loss, {"nll": nll.mean(), "aux": aux}


# ---- caches ---------------------------------------------------------------

def _layer_cache_spec(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                      dtype):
    hd = cfg.hd
    if kind in ("attn", "nope"):
        s = (batch, cfg.n_kv_heads, max_len, hd)
        return {"k": jnp.zeros(s, dtype), "v": jnp.zeros(s, dtype)}
    if kind == "local":
        W = min(cfg.local_window, max_len)
        s = (batch, cfg.n_kv_heads, W, hd)
        return {"k": jnp.zeros(s, dtype), "v": jnp.zeros(s, dtype)}
    if kind == "rglru":
        return rec.rglru_init_state(batch, cfg.d_model)
    if kind == "slstm":
        return rec.slstm_init_state(batch, cfg.n_heads, cfg.d_model)
    if kind == "mlstm":
        return rec.mlstm_init_state(batch, cfg.n_heads, cfg.d_model)
    raise ValueError(kind)


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, per_slot: bool = False):
    """``per_slot=True`` makes ``cur_len`` a (B,) vector — every batch slot
    runs its own timeline (continuous-batching serving engine)."""
    unit = cfg.unit
    n_units = cfg.n_layers // unit
    n_tail = cfg.n_layers - n_units * unit

    def unit_cache():
        c = {f"pos{j}": _layer_cache_spec(cfg, cfg.pattern[j], batch,
                                          max_len, dtype)
             for j in range(unit)}
        if cfg.encoder_decoder:
            s = (batch, cfg.n_kv_heads, cfg.encoder_frames, cfg.hd)
            c["cross"] = {"k": jnp.zeros(s, dtype), "v": jnp.zeros(s, dtype)}
        return c

    units = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                   *[unit_cache() for _ in range(n_units)])
    tail = {}
    for t in range(n_tail):
        c = _layer_cache_spec(cfg, cfg.layer_kind(n_units * unit + t), batch,
                              max_len, dtype)
        if cfg.encoder_decoder:
            s = (batch, cfg.n_kv_heads, cfg.encoder_frames, cfg.hd)
            c["cross"] = {"k": jnp.zeros(s, dtype), "v": jnp.zeros(s, dtype)}
        tail[f"layer{t}"] = c
    cur = (jnp.zeros((batch,), jnp.int32) if per_slot
           else jnp.zeros((), jnp.int32))
    return {"units": units, "tail": tail, "cur_len": cur}


@_serving_entry
def prefill(params, cfg: ArchConfig, tokens, frames=None, mesh=None,
            max_len: int | None = None, constrain=None):
    """Process a prompt, build the cache -> (last-pos logits, cache)."""
    dtype = jnp.dtype(cfg.dtype)
    params = _decode_tied_embed(params, cfg, dtype)
    B, T = tokens.shape
    max_len = max_len or T
    x = _embed(params, cfg, tokens, dtype)
    cross_ctx = (encode_frames(params, cfg, frames, dtype)
                 if cfg.encoder_decoder else None)
    x, run_caches, _ = _run_stack(params, cfg, x, dtype, mesh, "full",
                                  cross_ctx=cross_ctx, constrain=constrain)
    logits = _unembed(params, cfg, x[:, -1:], dtype)

    cache = init_cache(cfg, B, max_len, dtype)

    def fill(spec, got, kind):
        if kind in ("attn", "nope"):
            return jax.lax.dynamic_update_slice_in_dim(
                spec, got.astype(spec.dtype), 0, axis=2)
        if kind == "local":
            W = spec.shape[2]
            if T >= W:
                # last W entries, aligned to ring slots (pos % W)
                tail_kv = got[:, :, T - W:]
                shift = T % W
                return jnp.roll(tail_kv.astype(spec.dtype), shift=shift,
                                axis=2)
            return jax.lax.dynamic_update_slice_in_dim(
                spec, got.astype(spec.dtype), 0, axis=2)
        return got  # recurrent states already final

    # units
    unit = cfg.unit
    new_units = {}
    for j in range(unit):
        kind = cfg.pattern[j]
        spec_c = cache["units"][f"pos{j}"]
        got_c = run_caches["units"][f"pos{j}"]
        if kind in ATTN_KINDS:
            new_units[f"pos{j}"] = {
                n: jax.vmap(lambda s, g, n=n: fill(s, g, kind))(spec_c[n],
                                                                got_c[n])
                for n in ("k", "v")
            }
        else:
            new_units[f"pos{j}"] = jax.tree_util.tree_map(
                lambda s, g: g.astype(s.dtype), spec_c, got_c)
    if cfg.encoder_decoder:
        new_units["cross"] = _make_cross_kv(params, cfg, cross_ctx, dtype)
    new_tail = {}
    for t, (name, _) in enumerate(sorted(params["tail"].items())):
        kind = cfg.layer_kind((cfg.n_layers // unit) * unit + t)
        spec_c = cache["tail"][name]
        got_c = run_caches["tail"][name]
        if kind in ATTN_KINDS:
            new_tail[name] = {n: fill(spec_c[n], got_c[n], kind)
                              for n in ("k", "v")}
        else:
            new_tail[name] = jax.tree_util.tree_map(
                lambda s, g: g.astype(s.dtype), spec_c, got_c)
        if cfg.encoder_decoder:
            new_tail[name]["cross"] = jax.tree_util.tree_map(
                lambda x: x[0], _make_cross_kv(params, cfg, cross_ctx, dtype))
    return logits, {"units": new_units, "tail": new_tail,
                    "cur_len": jnp.full((), T, jnp.int32)}


@_serving_entry
def prefill_chunk(params, cfg: ArchConfig, tokens, cache, slot, n_valid,
                  mesh=None):
    """Process one fixed-size prompt chunk for ``slot`` of a paged cache.

    tokens: (1, C) int32 — a chunk of the prompt padded to the engine's
    chunk size; ``slot`` and ``n_valid`` (the count of real tokens) are
    traced scalars, and the chunk's start position is read from
    ``cache["cur_len"][slot]`` — so **one compilation serves every prompt
    length, chunk index and slot** (the whole-prompt ``prefill`` retraces
    per prompt length).  K/V is appended straight into the slot's pages
    across chunk boundaries; the final chunk's last-position logits are
    where the request's first token is sampled from.

    Returns (logits (1, 1, V) at position ``n_valid - 1`` of the chunk,
    new cache with ``cur_len[slot] += n_valid``).  Requires a paged cache
    and an architecture whose every layer is a paged kind ('attn'/'nope');
    the serving engine falls back to whole-prompt prefill otherwise."""
    params = _decode_tied_embed(params, cfg, jnp.dtype(cfg.dtype))
    x, new_cache, n_valid = _chunk_stack(params, cfg, tokens, cache, slot,
                                         n_valid, mesh)
    last = jax.lax.dynamic_slice_in_dim(
        x, jnp.maximum(n_valid - 1, 0), 1, axis=1)
    logits = _unembed(params, cfg, last, jnp.dtype(cfg.dtype))
    return logits, new_cache


@_serving_entry
def verify_chunk(params, cfg: ArchConfig, tokens, cache, slot, n_valid,
                 mesh=None):
    """The speculative-decoding verify forward: :func:`prefill_chunk`'s
    chunk program, but unembedding **every** chunk position.

    tokens: (1, C) — the slot's last emitted token followed by the draft's
    proposals (padded to the engine's ``spec_k + 1`` verify width; one
    compilation serves every request/slot, like the prefill chunk).
    Returns (logits (1, C, V), new cache): row ``i`` of the logits
    conditions on the cache prefix plus ``tokens[:, :i+1]`` — the target
    distribution that proposal ``i+1`` is accepted against
    (``serving.spec.verify``), with row ``n_valid - 1`` scoring the bonus
    token.  K/V for all ``n_valid`` tokens lands in the slot's pages and
    ``cur_len[slot]`` advances by ``n_valid``; the engine rolls the
    rejected suffix back afterwards (``PagedKVCache.rollback``) — the
    same timeline-rollback discipline as the chunked-prefill masked
    rows."""
    params = _decode_tied_embed(params, cfg, jnp.dtype(cfg.dtype))
    x, new_cache, _ = _chunk_stack(params, cfg, tokens, cache, slot,
                                   n_valid, mesh)
    logits = _unembed(params, cfg, x, jnp.dtype(cfg.dtype))
    return logits, new_cache


def _chunk_stack(params, cfg: ArchConfig, tokens, cache, slot, n_valid,
                 mesh):
    """Shared chunk program of :func:`prefill_chunk` / :func:`verify_chunk`:
    embed, run every layer in chunk mode (page-append + causal attention
    over the gathered history), advance the slot's timeline.  Returns the
    residual stream ``x`` (1, C, d) before unembedding."""
    dtype = jnp.dtype(cfg.dtype)
    cur_len = cache["cur_len"]
    start = cur_len[slot]
    page_table = cache["page_table"]
    x = _embed(params, cfg, tokens, dtype)

    unit = cfg.unit
    n_units = cfg.n_layers // unit

    def unit_body(x, xs):
        unit_p, unit_c = xs
        new_c = {}
        for j in range(unit):
            x, c = _layer_apply_chunk(unit_p[f"pos{j}"], x, cfg,
                                      cfg.pattern[j], dtype, mesh,
                                      unit_c[f"pos{j}"], page_table, slot,
                                      start, n_valid)
            new_c[f"pos{j}"] = c
        return x, new_c

    x, new_units = jax.lax.scan(unit_body, x,
                                (params["units"], cache["units"]))
    new_tail = {}
    for t, (name, p) in enumerate(sorted(params["tail"].items())):
        kind = cfg.layer_kind(n_units * unit + t)
        x, c = _layer_apply_chunk(p, x, cfg, kind, dtype, mesh,
                                  cache["tail"][name], page_table, slot,
                                  start, n_valid)
        new_tail[name] = c
    new_cache = {"units": new_units, "tail": new_tail,
                 "cur_len": cur_len.at[slot].set(start + n_valid),
                 "page_table": page_table}
    return x, new_cache, n_valid


def _make_cross_kv(params, cfg, cross_ctx, dtype):
    """Precompute per-unit cross-attention K/V from encoder output."""
    def one_unit(unit_p):
        kvs = {}
        for j in range(cfg.unit):
            p = unit_p[f"pos{j}"]["cross"]
            B, Fr, _ = cross_ctx.shape
            k = (cross_ctx @ mat(p["wk"], dtype)).reshape(
                B, Fr, cfg.n_kv_heads, cfg.hd).transpose(0, 2, 1, 3)
            v = (cross_ctx @ mat(p["wv"], dtype)).reshape(
                B, Fr, cfg.n_kv_heads, cfg.hd).transpose(0, 2, 1, 3)
            kvs = {"k": k, "v": v}  # single pattern pos for whisper (unit=1)
        return kvs

    return jax.vmap(one_unit, in_axes=0)(params["units"])


@_serving_entry
def decode_step(params, cfg: ArchConfig, token, cache, mesh=None):
    """token: (B, 1) int32 -> (logits (B, 1, V), new cache)."""
    dtype = jnp.dtype(cfg.dtype)
    params = _decode_tied_embed(params, cfg, dtype)
    cur_len = cache["cur_len"]
    x = _embed(params, cfg, token, dtype)
    x, new_cache, _ = _run_stack(params, cfg, x, dtype, mesh, "decode",
                                 cache=cache, cur_len=cur_len)
    logits = _unembed(params, cfg, x, dtype)
    new_cache["cur_len"] = cur_len + 1
    if "page_table" in cache:
        new_cache["page_table"] = cache["page_table"]
    return logits, new_cache
