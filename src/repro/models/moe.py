"""Mixture-of-Experts layer with expert parallelism (EP) via shard_map.

Routing: softmax gate, top-k selection, per-expert capacity C = ceil(
T_local * k / E * capacity_factor).  Dispatch is *local-first*: each data
shard selects, for every expert, up to C of its own tokens (vmapped top_k —
static shapes, no global cumsum/sort, no cross-shard serialization).  When
experts are sharded over the ``model`` axis (EP), the (E, C, d) dispatch
buffer is exchanged with a single all_to_all so each shard computes only its
local experts, then a second all_to_all returns expert outputs — the
canonical token->expert->token exchange, expressed with jax-native
collectives instead of torch.distributed semantics (DESIGN.md §5).

Without a mesh (smoke tests, single host) the same code runs with the
all_to_all elided (E_local == E).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import mat, F32


def moe_init(rng, d_model: int, n_experts: int, moe_d_ff: int,
             n_shared: int, d_ff_shared: int, top_k: int,
             dtype=jnp.float32):
    ks = jax.random.split(rng, 5)
    s_in, s_ff = d_model ** -0.5, moe_d_ff ** -0.5
    p = {
        "gate": jax.random.normal(ks[0], (d_model, n_experts), dtype) * s_in,
        "wi_gate": jax.random.normal(
            ks[1], (n_experts, d_model, moe_d_ff), dtype) * s_in,
        "wi_up": jax.random.normal(
            ks[2], (n_experts, d_model, moe_d_ff), dtype) * s_in,
        "wo": jax.random.normal(
            ks[3], (n_experts, moe_d_ff, d_model), dtype) * s_ff,
    }
    if n_shared:
        from .layers import mlp_init
        p["shared"] = mlp_init(ks[4], d_model, d_ff_shared * n_shared,
                               "swiglu", dtype)
    return p


def _expert_ffn(wi_gate, wi_up, wo, x):
    """x: (E, C, d); weights: (E, d, ff) / (E, ff, d)."""
    g = jnp.einsum("ecd,edf->ecf", x, wi_gate)
    u = jnp.einsum("ecd,edf->ecf", x, wi_up)
    h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _route_local(x, gate_w, top_k: int, n_experts: int, capacity: int):
    """Local routing: x (T, d) -> dispatch buffer + combine metadata.

    Returns (buf (E, C, d), src_idx (E, C), src_w (E, C), aux_loss)."""
    T, d = x.shape
    logits = (x @ gate_w).astype(F32)                     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)            # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # per-expert weight of each token (0 if not routed there): (E, T)
    onehot = jax.nn.one_hot(top_i, n_experts, dtype=F32)  # (T, k, E)
    w_te = (onehot * top_p[..., None]).sum(axis=1)        # (T, E)
    w_et = w_te.T                                         # (E, T)

    # per-expert top-C token selection (static shapes, local)
    sel_w, sel_idx = jax.lax.top_k(w_et, min(capacity, T))  # (E, C)
    if capacity > T:
        pad = capacity - T
        sel_w = jnp.pad(sel_w, ((0, 0), (0, pad)))
        sel_idx = jnp.pad(sel_idx, ((0, 0), (0, pad)))
    buf = jnp.take(x, sel_idx, axis=0)                    # (E, C, d)
    buf = buf * (sel_w[..., None] > 0).astype(x.dtype)

    # load-balancing aux loss (Switch-style)
    frac_tokens = (w_te > 0).astype(F32).mean(axis=0)
    frac_probs = probs.mean(axis=0)
    aux = n_experts * jnp.sum(frac_tokens * frac_probs)
    return buf, sel_idx, sel_w, aux


def moe_apply(params, x, cfg, *, mesh=None, ep_axis: str = "model",
              dtype=jnp.bfloat16):
    """x: (B, T, d) -> (B, T, d), plus aux loss (returned via dict)."""
    B, T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    cf = getattr(cfg, "capacity_factor", 1.25)

    gate_w = mat(params["gate"], dtype)
    wi_gate = mat(params["wi_gate"], dtype)
    wi_up = mat(params["wi_up"], dtype)
    wo = mat(params["wo"], dtype)

    def local_moe(x_loc, gate_w, wi_gate, wi_up, wo):
        """Runs per data-shard; expert weights are per-model-shard (EP)."""
        Bl, Tl, _ = x_loc.shape
        xt = x_loc.reshape(Bl * Tl, d)
        E_loc = wi_gate.shape[0]
        n_ep = E // E_loc
        cap = max(8, int((Bl * Tl * k * cf) / E + 0.999))
        buf, sel_idx, sel_w, aux = _route_local(xt, gate_w, k, E, cap)

        if n_ep > 1:
            # (E, C, d) -> (n_ep, E_loc, C, d) -> a2a over expert shards
            buf = buf.reshape(n_ep, E_loc, cap, d)
            buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0,
                                     concat_axis=0, tiled=False)
            # now (n_ep, E_loc, C, d): rows = source shards, local experts
            y = _expert_ffn(
                wi_gate, wi_up, wo,
                buf.transpose(1, 0, 2, 3).reshape(E_loc, n_ep * cap, d))
            y = y.reshape(E_loc, n_ep, cap, d).transpose(1, 0, 2, 3)
            y = jax.lax.all_to_all(y, ep_axis, split_axis=0,
                                   concat_axis=0, tiled=False)
            y = y.reshape(E, cap, d)
        else:
            y = _expert_ffn(wi_gate, wi_up, wo, buf)

        # combine: scatter expert outputs back to tokens, weighted
        out = jnp.zeros((Bl * Tl, d), dtype=y.dtype)
        w = sel_w.astype(y.dtype)[..., None]              # (E, C, 1)
        out = out.at[sel_idx.reshape(-1)].add(
            (y * w).reshape(-1, d), mode="drop")
        return out.reshape(Bl, Tl, d), aux.reshape(1)

    if mesh is not None and ep_axis in mesh.axis_names and (
            mesh.shape[ep_axis] > 1):
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        out, aux = jax.shard_map(
            local_moe, mesh=mesh,
            in_specs=(P(batch_axes, None, None), P(None, None),
                      P(ep_axis, None, None), P(ep_axis, None, None),
                      P(ep_axis, None, None)),
            out_specs=(P(batch_axes, None, None), P(batch_axes)),
            check_vma=False,
        )(x.astype(dtype), gate_w, wi_gate, wi_up, wo)
        aux = aux.mean()
    else:
        out, aux = local_moe(x.astype(dtype), gate_w, wi_gate, wi_up, wo)
        aux = aux[0]

    if "shared" in params:
        from .layers import mlp_apply
        out = out + mlp_apply(params["shared"], x.astype(dtype), "swiglu",
                              dtype)
    return out.astype(x.dtype), aux
