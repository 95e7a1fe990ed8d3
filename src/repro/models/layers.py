"""Core transformer layers: norms, RoPE, GQA attention (global / local /
decode), MLP variants, embeddings.  Pure JAX (pytrees of arrays, no flax).

Attention is implemented blockwise (online softmax over KV chunks) so that
32k-token prefill never materializes an (S, S) score matrix; local-window
attention slices only the in-window KV blocks (O(S * W) work), which is what
makes the `long_500k` shapes feasible for the hybrid/ssm architectures.

Weights may be `CompressedTensor`s (ECF8): every use site goes through
``mat`` = materialize-and-cast, the JAX-native version of the paper's
just-in-time per-layer decompression hooks (§3.3).
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.store import materialize

F32 = jnp.float32

# full-sequence attention implementation: "flash" (memory-efficient custom
# VJP, production default — EXPERIMENTS.md §Perf iteration 1) or
# "blockwise" (naive autodiff baseline; what the §Roofline baseline rows
# were lowered with).  Switched by the dry-run's --attn flag.
_ATTN_IMPL = {"full": "flash"}


def set_attention_impl(name: str):
    assert name in ("flash", "blockwise"), name
    _ATTN_IMPL["full"] = name


def get_attention_impl() -> str:
    return _ATTN_IMPL["full"]


def mat(w, dtype):
    """Materialize (decode if compressed) and cast a weight for use; the
    ops carry the scope ``weights.decode`` in their metadata."""
    with jax.named_scope("weights.decode"):
        return materialize(w, dtype=dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    y = x.astype(F32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(F32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(F32) + bias.astype(F32)).astype(x.dtype)


# --------------------------------------------------------------------------
# rotary position embeddings
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., T, head_dim); positions: (..., T) int32.

    Angles (position-dependent) are computed in f32; the rotation products
    run in the storage dtype.  Casting *x* to f32 here would promote the
    whole upstream QKV matmul to f32 under XLA's convert-hoisting, doubling
    the weight-gather wire bytes (§Perf cell-1 iteration 5)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), dtype=F32)
    ang = positions.astype(F32)[..., None] * freqs  # (..., T, hd/2)
    cos = jnp.cos(ang).astype(x.dtype)
    sin = jnp.sin(ang).astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


# --------------------------------------------------------------------------
# blockwise attention (online softmax)
# --------------------------------------------------------------------------

def _softcap(x, cap: float):
    if cap and cap > 0:
        return jnp.tanh(x / cap) * cap
    return x


def _gqa_scores(q, k):
    """q: (B, Hq, Tq, D), k: (B, Hkv, Tk, D) -> (B, Hq, Tq, Tk)."""
    B, Hq, Tq, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, Tq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k)
    return s.reshape(B, Hq, Tq, k.shape[2])


def _gqa_combine(p, v):
    """p: (B, Hq, Tq, Tk), v: (B, Hkv, Tk, D) -> (B, Hq, Tq, D)."""
    B, Hq, Tq, Tk = p.shape
    Hkv = v.shape[1]
    g = Hq // Hkv
    pg = p.reshape(B, Hkv, g, Tq, Tk)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", pg, v)
    return o.reshape(B, Hq, Tq, v.shape[3])


def blockwise_attention(q, k, v, *, causal: bool = True,
                        q_offset=0, attn_softcap: float = 0.0,
                        q_chunk: int = 512, kv_chunk: int = 1024,
                        kv_len=None):
    """Memory-safe attention.  q: (B, Hq, Tq, D), k/v: (B, Hkv, Tk, D).

    ``q_offset``: absolute position of q[0] (for decode / chunked prefill).
    ``kv_len``: actual valid KV length (int array ok) for cache decode.
    """
    B, Hq, Tq, D = q.shape
    Tk = k.shape[2]
    scale = D ** -0.5
    q = q * scale
    q_chunk = min(q_chunk, Tq)
    kv_chunk = min(kv_chunk, Tk)
    n_q = -(-Tq // q_chunk)
    n_kv = -(-Tk // kv_chunk)
    # pad to chunk multiples
    Tq_p, Tk_p = n_q * q_chunk, n_kv * kv_chunk
    if Tq_p != Tq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tq_p - Tq), (0, 0)))
    if Tk_p != Tk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Tk_p - Tk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Tk_p - Tk), (0, 0)))
    if kv_len is None:
        kv_len = Tk
    kv_len = jnp.asarray(kv_len)
    per_batch = kv_len.ndim == 1  # (B,) per-slot lengths (serving engine)

    def q_block(qi, q_blk):
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ki):
            acc, m, denom = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, 2)
            v_blk = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, 2)
            kv_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = _gqa_scores(q_blk, k_blk).astype(F32)
            s = _softcap(s, attn_softcap)
            if per_batch:
                # (B, 1, 1, Tk) validity x (1, 1, Tq, Tk) causality
                mask = (kv_pos[None, None, None, :]
                        < kv_len[:, None, None, None])
            else:
                mask = (kv_pos[None, :] < kv_len)[None, None]
            if causal:
                mask = mask & (kv_pos[None, :]
                               <= q_pos[:, None])[None, None]
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            denom = denom * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + _gqa_combine(p, v_blk).astype(F32)
            return (acc, m_new, denom), None

        acc0 = jnp.zeros((B, Hq, q_chunk, D), F32)
        m0 = jnp.full((B, Hq, q_chunk), -1e30, F32)
        d0 = jnp.zeros((B, Hq, q_chunk), F32)
        (acc, m, denom), _ = jax.lax.scan(kv_step, (acc0, m0, d0),
                                          jnp.arange(n_kv))
        return acc / jnp.maximum(denom[..., None], 1e-30)

    if n_q == 1:
        out = q_block(0, q)
    else:
        q_blocks = q.reshape(B, Hq, n_q, q_chunk, D).transpose(2, 0, 1, 3, 4)
        out = jax.lax.map(lambda args: q_block(args[0], args[1]),
                          (jnp.arange(n_q), q_blocks))
        out = out.transpose(1, 2, 0, 3, 4).reshape(B, Hq, Tq_p, D)
    return out[:, :, :Tq].astype(v.dtype)


def local_attention(q, k, v, *, window: int, attn_softcap: float = 0.0,
                    q_chunk: int = 1024):
    """Causal sliding-window attention, O(Tq * window).

    For each q chunk [i*C, (i+1)*C), attends to KV slice
    [i*C - window, (i+1)*C) with the window mask applied inside."""
    B, Hq, Tq, D = q.shape
    scale = D ** -0.5
    q = q * scale
    C = min(q_chunk, Tq)
    n_q = -(-Tq // C)
    Tq_p = n_q * C
    if Tq_p != Tq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tq_p - Tq), (0, 0)))
    W = min(window, k.shape[2])
    ctx = C + W  # kv context per q chunk
    k_pad = jnp.pad(k, ((0, 0), (0, 0), (W, Tq_p - Tq), (0, 0)))
    v_pad = jnp.pad(v, ((0, 0), (0, 0), (W, Tq_p - Tq), (0, 0)))

    def q_block(qi):
        q_blk = jax.lax.dynamic_slice_in_dim(q, qi * C, C, 2)
        k_blk = jax.lax.dynamic_slice_in_dim(k_pad, qi * C, ctx, 2)
        v_blk = jax.lax.dynamic_slice_in_dim(v_pad, qi * C, ctx, 2)
        s = _gqa_scores(q_blk, k_blk).astype(F32)
        s = _softcap(s, attn_softcap)
        q_pos = qi * C + jnp.arange(C)
        kv_pos = qi * C + jnp.arange(ctx) - W
        mask = (kv_pos[None, :] <= q_pos[:, None]) & (
            kv_pos[None, :] > q_pos[:, None] - W) & (kv_pos[None, :] >= 0)
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return _gqa_combine(p, v_blk)

    out = jax.lax.map(q_block, jnp.arange(n_q))  # (n_q, B, H, C, D)
    out = out.transpose(1, 2, 0, 3, 4).reshape(B, Hq, Tq_p, D)
    return out[:, :, :Tq].astype(v.dtype)


def decode_attention(q, k_cache, v_cache, *, kv_len,
                     attn_softcap: float = 0.0):
    """Single-token decode attention over a cache.

    q: (B, Hq, 1, D); caches: (B, Hkv, S, D); kv_len: scalar int array."""
    return blockwise_attention(
        q, k_cache, v_cache, causal=False, attn_softcap=attn_softcap,
        kv_len=kv_len, q_chunk=1, kv_chunk=min(2048, k_cache.shape[2]),
    )


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def mlp_apply(params, x, mlp_type: str, dtype):
    if mlp_type == "swiglu":
        g = x @ mat(params["wi_gate"], dtype)
        u = x @ mat(params["wi_up"], dtype)
        return (jax.nn.silu(g.astype(F32)).astype(dtype) * u) @ mat(
            params["wo"], dtype)
    if mlp_type == "gelu":
        h = jax.nn.gelu(x @ mat(params["wi"], dtype), approximate=True)
        return h @ mat(params["wo"], dtype)
    if mlp_type == "geglu":
        g = x @ mat(params["wi_gate"], dtype)
        u = x @ mat(params["wi_up"], dtype)
        return (jax.nn.gelu(g.astype(F32), approximate=True).astype(dtype)
                * u) @ mat(params["wo"], dtype)
    if mlp_type == "sqrelu":
        h = jax.nn.relu(x @ mat(params["wi"], dtype))
        return jnp.square(h) @ mat(params["wo"], dtype)
    raise ValueError(mlp_type)


def mlp_init(rng, d_model: int, d_ff: int, mlp_type: str, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = d_model ** -0.5
    s_ff = d_ff ** -0.5
    if mlp_type in ("swiglu", "geglu"):
        return {
            "wi_gate": jax.random.normal(k1, (d_model, d_ff), dtype) * s_in,
            "wi_up": jax.random.normal(k2, (d_model, d_ff), dtype) * s_in,
            "wo": jax.random.normal(k3, (d_ff, d_model), dtype) * s_ff,
        }
    return {
        "wi": jax.random.normal(k1, (d_model, d_ff), dtype) * s_in,
        "wo": jax.random.normal(k3, (d_ff, d_model), dtype) * s_ff,
    }
