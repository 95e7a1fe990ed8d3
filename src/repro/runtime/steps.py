"""Jit-able step functions: train / prefill / decode (serve).

Each ``make_*_step`` returns a pure function suitable for
``jax.jit(step, in_shardings=..., out_shardings=...)`` — the launcher and
the multi-pod dry-run both consume these.  ``input_specs`` provides
ShapeDtypeStruct stand-ins for every model input so the dry-run lowers
without allocating (the 40-cell x 2-mesh sweep).

Distributed-optimization features wired here:
  * gradient accumulation (microbatching) via ``lax.scan`` — the knob that
    trades HBM for step time at the 1000-node scale;
  * remat (activation checkpointing) at scan-unit granularity;
  * sequence-parallel residual constraint (``runtime.sharding``) so saved
    activations shard over the model axis;
  * optional gradient compression hook (1-bit-sign-like mean-abs scaling is
    NOT lossless and is deliberately absent: the repo's contribution is
    *lossless* compression — see ``runtime.collectives`` for the ECF8-FR
    compressed weight all-gather used on the serving path instead).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import model as M
from repro.optim.adamw import AdamWConfig, adamw, adamw_init
from repro.optim.schedules import cosine_schedule
from .sharding import ShardingRules, DEFAULT_RULES, make_constrainer

F32 = jnp.float32


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, shardable, no allocation)
# --------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStructs for every model input of this (arch, shape) cell.

    train:   {tokens (B, T) i32, labels (B, T) i32 [, frames (B, F, d)]}
    prefill: {tokens (B, T) i32 [, frames]}
    decode:  {token (B, 1) i32}  (the cache is built via cache_specs)
    """
    B, T = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        specs = {"tokens": sds((B, T), i32), "labels": sds((B, T), i32)}
    elif shape.kind == "prefill":
        specs = {"tokens": sds((B, T), i32)}
    else:  # decode
        specs = {"token": sds((B, 1), i32)}
    if cfg.encoder_decoder and shape.kind != "decode":
        specs["frames"] = sds((B, cfg.encoder_frames, cfg.d_model),
                              jnp.dtype(cfg.dtype))
    return specs


def param_specs(cfg: ArchConfig, dtype=None) -> dict:
    """ShapeDtypeStruct pytree of the parameters (eval_shape, no alloc)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    return jax.eval_shape(
        lambda k: M.init_params(k, cfg, dtype=dtype),
        jax.random.PRNGKey(0))


def cache_specs(cfg: ArchConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStruct pytree of the decode cache."""
    return jax.eval_shape(partial(M.init_cache, cfg, batch, max_len, dtype))


def compressed_param_specs(cfg: ArchConfig, bits_per_exp: float = 3.43,
                           min_elems: int = 65536,
                           out_dtype: str = "bfloat16") -> dict:
    """ShapeDtypeStruct stand-in for an ECF8-TPU-compressed param tree.

    The payload stride is data-dependent at encode time; for lowering we
    size it from the expected exponent code length (``bits_per_exp``, ~3.4
    bits at the trained-weight alpha~1.9 — table1_memory measures 3.2-3.5)
    plus lane-padding slack.  Dry-run only: real serving compresses real
    weights (launch/serve.py) and gets exact strides.
    """
    from repro.core.store import CompressedMeta, CompressedTensor
    from repro.core.tpu_format import DEFAULT_SYM_PER_LANE, LANES
    import numpy as np
    sds = jax.ShapeDtypeStruct
    S = DEFAULT_SYM_PER_LANE
    stride = int(np.ceil(S * (bits_per_exp * 1.06) / 8)) + 1
    base = param_specs(cfg)

    def visit(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", k)))
                 for k in path]
        stacked = int("units" in names or "layers" in names)
        n = int(np.prod(leaf.shape))
        per_layer = n // leaf.shape[0] if stacked else n
        if per_layer < min_elems or len(leaf.shape) < 2 + stacked:
            return leaf
        C = -(-per_layer // (LANES * S))
        lead = (leaf.shape[0],) if stacked else ()
        n_pad = C * LANES * S
        arrays = {
            "payload": sds(lead + (C, stride, LANES), jnp.uint8),
            "signmant": sds(lead + (C, -(-S // 2), LANES), jnp.uint8),
            "lj_limit": sds(lead + (8,), jnp.int32),
            "first_lj": sds(lead + (8,), jnp.int32),
            "offset": sds(lead + (8,), jnp.int32),
            "perm": sds(lead + (16,), jnp.int32),
        }
        meta = CompressedMeta(
            fmt="tpu", shape=tuple(leaf.shape[stacked:]),
            n_elem=per_layer, sym_per_lane=S, out_dtype=out_dtype)
        return CompressedTensor(arrays=arrays, meta=meta)

    return jax.tree_util.tree_map_with_path(visit, base)


def opt_specs(cfg: ArchConfig, dtype=None) -> dict:
    p = param_specs(cfg, dtype)
    return jax.eval_shape(adamw_init, p)


# --------------------------------------------------------------------------
# steps
# --------------------------------------------------------------------------

def make_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig | None = None,
                    mesh=None, rules: ShardingRules = DEFAULT_RULES,
                    remat: bool = True, grad_accum: int = 1,
                    warmup_steps: int = 100, total_steps: int = 10000):
    """(params, opt_state, batch, step) -> (params, opt_state, metrics)."""
    opt_cfg = opt_cfg or AdamWConfig()
    constrain = make_constrainer(mesh, rules) if mesh is not None else None

    def loss_of(params, tokens, labels, frames):
        loss, met = M.loss_fn(params, cfg, tokens, labels, frames=frames,
                              mesh=mesh, remat=remat, constrain=constrain)
        return loss, met

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def train_step(params, opt_state, batch, step):
        tokens, labels = batch["tokens"], batch["labels"]
        frames = batch.get("frames")
        if grad_accum > 1:
            B = tokens.shape[0]
            mb = B // grad_accum

            def micro(carry, i):
                g_acc, l_acc = carry
                sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * mb, mb, 0)
                (l, _), g = grad_fn(params, sl(tokens), sl(labels),
                                    sl(frames) if frames is not None
                                    else None)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, F32), params)
            (grads, loss_sum), _ = jax.lax.scan(
                micro, (g0, jnp.zeros((), F32)), jnp.arange(grad_accum))
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
            loss = loss_sum / grad_accum
            met = {"nll": loss, "aux": jnp.zeros((), F32)}
        else:
            (loss, met), grads = grad_fn(params, tokens, labels, frames)

        lr = cosine_schedule(step, warmup_steps, total_steps, opt_cfg.lr)
        params, opt_state, om = adamw(params, grads, opt_state, opt_cfg,
                                      lr=lr)
        metrics = {"loss": loss, "lr": lr, **met, **om}
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ArchConfig, mesh=None,
                      rules: ShardingRules = DEFAULT_RULES,
                      max_len: int | None = None):
    """(params, batch) -> (last-pos logits, cache)."""
    constrain = make_constrainer(mesh, rules) if mesh is not None else None

    def prefill_step(params, batch):
        return M.prefill(params, cfg, batch["tokens"],
                         frames=batch.get("frames"), mesh=mesh,
                         max_len=max_len, constrain=constrain)

    return prefill_step


def make_decode_step(cfg: ArchConfig, mesh=None,
                     rules: ShardingRules = DEFAULT_RULES):
    """(params, batch, cache) -> (logits, new cache) — one new token."""

    def decode_step(params, batch, cache):
        return M.decode_step(params, cfg, batch["token"], cache, mesh=mesh)

    return decode_step
