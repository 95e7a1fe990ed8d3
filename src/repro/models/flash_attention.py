"""Memory-efficient attention with a flash-style custom VJP.

The autodiff backward of the naive online-softmax attention saves the
(B, H, Tq, Tk) probability tensors for every (layer, q-block, kv-block) —
the dry-run's byte histogram shows those f32 stacks dominating the memory
roofline term (EXPERIMENTS.md §Perf, granite train_4k iteration 1).

This implementation:
  * forward: chunked online softmax (identical math/outputs to
    ``layers.blockwise_attention``) that additionally returns the row
    statistics (m, l);
  * backward: flash-style recompute — s/p are rebuilt per (q-block,
    kv-block) from q, k, v and never stored; residuals are only
    (q, k, v, o, m, l);
  * probabilities are materialized in the value dtype (bf16 on the full
    configs) for the dv/o dots, with f32 accumulation.

Handles GQA grouping, causality and gemma-style tanh softcap (whose
derivative is recomputed from the raw scores in the backward).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _softcap(x, cap: float):
    if cap and cap > 0:
        return jnp.tanh(x / cap) * cap
    return x


def _gqa_scores(q, k):
    B, Hq, Tq, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, Tq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                   preferred_element_type=F32)
    return s.reshape(B, Hq, Tq, k.shape[2])


def _gqa_combine(p, v):
    B, Hq, Tq, Tk = p.shape
    Hkv = v.shape[1]
    g = Hq // Hkv
    pg = p.reshape(B, Hkv, g, Tq, Tk)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", pg, v,
                   preferred_element_type=F32)
    return o.reshape(B, Hq, Tq, v.shape[3])


def _pad_to(x, n, axis):
    if x.shape[axis] == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pad)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    attn_softcap: float = 0.0, q_chunk: int = 512,
                    kv_chunk: int = 1024, q_base=0.0):
    """q: (B, Hq, Tq, D); k/v: (B, Hkv, Tk, D) -> (B, Hq, Tq, D).

    ``q_base``: global position of q[:, :, 0] for causal masking when the
    query sequence is a shard of a longer one (flash_attention_sharded).
    Passed as an f32 scalar so it threads through the custom VJP as a
    regular (zero-cotangent) argument.

    Full-sequence causal (or full bidirectional) attention; for cache
    decode with kv_len masks use ``layers.blockwise_attention`` (forward-
    only, no VJP needed)."""
    o, _, _ = _flash_fwd_impl(q, k, v, causal, attn_softcap, q_chunk,
                              kv_chunk, q_base)
    return o


def _flash_fwd_impl(q, k, v, causal, attn_softcap, q_chunk, kv_chunk,
                    q_base):
    B, Hq, Tq, D = q.shape
    Tk = k.shape[2]
    C = min(q_chunk, Tq)
    K = min(kv_chunk, Tk)
    n_q, n_kv = -(-Tq // C), -(-Tk // K)
    base = jnp.asarray(q_base).astype(jnp.int32)
    qp = _pad_to(q * (D ** -0.5), n_q * C, 2)
    kp = _pad_to(k, n_kv * K, 2)
    vp = _pad_to(v, n_kv * K, 2)

    def q_block(qi):
        q_blk = jax.lax.dynamic_slice_in_dim(qp, qi * C, C, 2)
        q_pos = base + qi * C + jnp.arange(C)

        def kv_step(carry, ki):
            acc, m, denom = carry
            k_blk = jax.lax.dynamic_slice_in_dim(kp, ki * K, K, 2)
            v_blk = jax.lax.dynamic_slice_in_dim(vp, ki * K, K, 2)
            kv_pos = ki * K + jnp.arange(K)
            s = _softcap(_gqa_scores(q_blk, k_blk), attn_softcap)
            mask = kv_pos[None, :] < Tk
            if causal:
                mask = mask & (kv_pos[None, :] <= q_pos[:, None])
            s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            denom = denom * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + _gqa_combine(
                p.astype(v.dtype), v_blk)
            return (acc, m_new, denom), None

        acc0 = jnp.zeros((B, Hq, C, D), F32)
        m0 = jnp.full((B, Hq, C), -1e30, F32)
        d0 = jnp.zeros((B, Hq, C), F32)
        (acc, m, denom), _ = jax.lax.scan(kv_step, (acc0, m0, d0),
                                          jnp.arange(n_kv))
        o = acc / jnp.maximum(denom[..., None], 1e-30)
        return o, m, denom

    if n_q == 1:
        o, m, l = q_block(0)
    else:
        o, m, l = jax.lax.map(q_block, jnp.arange(n_q))
        o = o.transpose(1, 2, 0, 3, 4).reshape(B, Hq, n_q * C, D)
        m = m.transpose(1, 2, 0, 3).reshape(B, Hq, n_q * C)
        l = l.transpose(1, 2, 0, 3).reshape(B, Hq, n_q * C)
    return o[:, :, :Tq].astype(v.dtype), m[:, :, :Tq], l[:, :, :Tq]


def _flash_fwd(q, k, v, causal, attn_softcap, q_chunk, kv_chunk, q_base):
    o, m, l = _flash_fwd_impl(q, k, v, causal, attn_softcap, q_chunk,
                              kv_chunk, q_base)
    return o, (q, k, v, o, m, l, q_base)


def _flash_bwd(causal, attn_softcap, q_chunk, kv_chunk, res, do):
    q, k, v, o, m, l, q_base = res
    base = jnp.asarray(q_base).astype(jnp.int32)
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    C = min(q_chunk, Tq)
    K = min(kv_chunk, Tk)
    n_q, n_kv = -(-Tq // C), -(-Tk // K)
    scale = D ** -0.5
    qp = _pad_to(q * scale, n_q * C, 2)    # everything below sees scaled q
    kp = _pad_to(k, n_kv * K, 2)
    vp = _pad_to(v, n_kv * K, 2)
    do_p = _pad_to(do.astype(F32), n_q * C, 2)
    op = _pad_to(o.astype(F32), n_q * C, 2)
    # D_i = sum_d do_i * o_i  (flash-2 delta), padded rows are zero
    delta = (do_p * op).sum(-1)                       # (B, Hq, Tq_p)
    m_p = _pad_to(m, n_q * C, 2)
    l_p = jnp.maximum(_pad_to(l, n_q * C, 2), 1e-30)  # pad rows stay finite

    def q_block(carry, qi):
        dk_acc, dv_acc = carry                        # (B,Hkv,Tk_p,D) f32
        q_blk = jax.lax.dynamic_slice_in_dim(qp, qi * C, C, 2)
        do_blk = jax.lax.dynamic_slice_in_dim(do_p, qi * C, C, 2)
        m_blk = jax.lax.dynamic_slice_in_dim(m_p, qi * C, C, 2)
        l_blk = jax.lax.dynamic_slice_in_dim(l_p, qi * C, C, 2)
        dl_blk = jax.lax.dynamic_slice_in_dim(delta, qi * C, C, 2)
        q_pos = base + qi * C + jnp.arange(C)

        def kv_step(carry, ki):
            dq_blk, dk_acc, dv_acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(kp, ki * K, K, 2)
            v_blk = jax.lax.dynamic_slice_in_dim(vp, ki * K, K, 2)
            kv_pos = ki * K + jnp.arange(K)
            s_raw = _gqa_scores(q_blk, k_blk)          # f32, pre-softcap
            s = _softcap(s_raw, attn_softcap)
            mask = kv_pos[None, :] < Tk
            if causal:
                mask = mask & (kv_pos[None, :] <= q_pos[:, None])
            s = jnp.where(mask[None, None], s, -1e30)
            # normalized probabilities, recomputed (never stored)
            p = jnp.exp(s - m_blk[..., None]) / l_blk[..., None]
            p16 = p.astype(v.dtype)
            # dv_k += p^T do   (sum over q rows and the GQA group)
            dv_k = jnp.einsum(
                "bhgqk,bhgqd->bhkd",
                p16.reshape(B, Hkv, g, C, K),
                do_blk.astype(v.dtype).reshape(B, Hkv, g, C, D),
                preferred_element_type=F32)
            # dp = do @ v^T
            dp = jnp.einsum(
                "bhgqd,bhkd->bhgqk",
                do_blk.astype(v.dtype).reshape(B, Hkv, g, C, D), v_blk,
                preferred_element_type=F32).reshape(B, Hq, C, K)
            ds = p * (dp - dl_blk[..., None])          # f32
            if attn_softcap:
                t = jnp.tanh(s_raw / attn_softcap)
                ds = ds * (1.0 - jnp.square(t))
            ds = jnp.where(mask[None, None], ds, 0.0)
            ds16 = ds.astype(v.dtype)
            dq_blk = dq_blk + jnp.einsum(
                "bhgqk,bhkd->bhgqd",
                ds16.reshape(B, Hkv, g, C, K), k_blk,
                preferred_element_type=F32).reshape(B, Hq, C, D)
            dk_k = jnp.einsum(
                "bhgqk,bhgqd->bhkd",
                ds16.reshape(B, Hkv, g, C, K),
                q_blk.astype(v.dtype).reshape(B, Hkv, g, C, D),
                preferred_element_type=F32)
            dk_acc = jax.lax.dynamic_update_slice_in_dim(
                dk_acc,
                jax.lax.dynamic_slice_in_dim(dk_acc, ki * K, K, 2) + dk_k,
                ki * K, axis=2)
            dv_acc = jax.lax.dynamic_update_slice_in_dim(
                dv_acc,
                jax.lax.dynamic_slice_in_dim(dv_acc, ki * K, K, 2) + dv_k,
                ki * K, axis=2)
            return (dq_blk, dk_acc, dv_acc), None

        dq0 = jnp.zeros((B, Hq, C, D), F32)
        (dq_blk, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_step, (dq0, dk_acc, dv_acc), jnp.arange(n_kv))
        return (dk_acc, dv_acc), dq_blk

    dk0 = jnp.zeros((B, Hkv, n_kv * K, D), F32)
    dv0 = jnp.zeros_like(dk0)
    (dk_f, dv_f), dq_blocks = jax.lax.scan(q_block, (dk0, dv0),
                                           jnp.arange(n_q))
    dq = dq_blocks.transpose(1, 2, 0, 3, 4).reshape(B, Hq, n_q * C, D)
    return ((dq[:, :, :Tq] * scale).astype(q.dtype),
            dk_f[:, :, :Tk].astype(k.dtype),
            dv_f[:, :, :Tk].astype(v.dtype),
            jnp.zeros_like(jnp.asarray(q_base, F32)))


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_sharded(q, k, v, mesh, *, causal: bool = True,
                            attn_softcap: float = 0.0, q_chunk: int = 512,
                            kv_chunk: int = 1024):
    """flash_attention under shard_map: batch -> data axes, the *query
    sequence* -> model (always divisible on the assigned shapes, and
    GQA-group-agnostic — head sharding breaks kv-group alignment for most
    archs).  k/v are replicated inside the model group; each shard masks
    with its global q positions via ``q_base``.

    Why shard_map: plain GSPMD propagation through the flash custom-VJP
    loops gives up and fully replicates dq/dk (25.8 GB all-gathers on the
    granite train cell — EXPERIMENTS.md §Perf cell-1 iteration 2);
    shard_map pins the layout so the backward stays local, and the dk/dv
    partial-sum over the model group comes from the shard_map transpose of
    the replicated k/v inputs.
    """
    from jax.sharding import PartitionSpec as P
    
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    ba = ba if len(ba) != 1 else ba[0]
    B, Hq, Tq, _ = q.shape
    ba_size = 1
    for a in (ba if isinstance(ba, tuple) else (ba,)):
        ba_size *= mesh.shape[a]
    b_ax = ba if B % ba_size == 0 else None
    n_model = mesh.shape["model"]
    t_ax = "model" if (Tq % n_model == 0 and Tq > 1) else None
    if t_ax is None:
        return flash_attention(q, k, v, causal, attn_softcap, q_chunk,
                               kv_chunk)
    t_loc = Tq // n_model

    def body(q_l, k_l, v_l):
        base = (jax.lax.axis_index("model") * t_loc).astype(F32)
        return flash_attention(q_l, k_l, v_l, causal, attn_softcap,
                               min(q_chunk, t_loc), kv_chunk, base)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(b_ax, None, t_ax, None), P(b_ax, None, None, None),
                  P(b_ax, None, None, None)),
        out_specs=P(b_ax, None, t_ax, None),
        check_vma=False,
    )(q, k, v)
