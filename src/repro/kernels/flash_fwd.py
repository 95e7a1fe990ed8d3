"""Pallas TPU kernel: flash-attention forward (VMEM-resident s/p tiles).

After §Perf cell-1 iterations 1-5, the memory roofline term of the train
cells is dominated by the f32 (B, H, q_chunk, kv_chunk) score/probability
tiles that XLA materializes in HBM between fusions (the CPU-lowered HLO
cannot keep them in registers across the online-softmax steps).  On the
TPU target this traffic does not exist: this kernel computes the whole
online softmax for one (batch, head, q-block) grid cell with s/p living in
VMEM/VREGs, reading q/k/v tiles from HBM exactly once and writing o once.

Grid: (B * Hq, n_q_blocks).  Block shapes:
  q tile   (1, bq, D)    VMEM
  k/v      (1, Tk, D)    VMEM (whole per-head K/V — Tk*D*2B <= ~2 MB for
                          the assigned shapes at per-shard Tk)
  o tile   (1, bq, D)    VMEM
The kv loop runs in-kernel over Tk in bk-sized slices with VREG-resident
running max / denominator (the same math as models.flash_attention, which
is the validated jnp oracle).

GQA: the index_map routes q head h to kv head h // (Hq // Hkv).
The backward kernel follows the standard flash recompute scheme whose jnp
form is implemented and validated in ``models.flash_attention._flash_bwd``;
its Pallas port shares this kernel's tiling (DESIGN.md §Kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

F32 = jnp.float32
NEG = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, bk: int, causal: bool,
                      softcap: float, q_base: int, scale: float):
    bq, D = q_ref.shape[1], q_ref.shape[2]
    Tk = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0].astype(F32) * scale                 # (bq, D)
    q_pos = q_base + qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, 1), 0)

    def body(ki, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(ki * bk, bk), :].astype(F32)   # (bk, D)
        v_blk = v_ref[0, pl.ds(ki * bk, bk), :].astype(F32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)  # (bq, bk)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        kv_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = jnp.broadcast_to(kv_pos < Tk, (bq, bk))
        if causal:
            mask = mask & (kv_pos <= q_pos)
        s = jnp.where(mask, s, NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        return acc, m_new, l

    n_kv = Tk // bk
    acc0 = jnp.zeros((bq, D), F32)
    m0 = jnp.full((bq, 1), NEG, F32)
    l0 = jnp.zeros((bq, 1), F32)
    acc, m, l = jax.lax.fori_loop(0, n_kv, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "softcap", "bq", "bk", "q_base", "interpret"))
def flash_fwd_pallas(q, k, v, *, causal: bool = True, softcap: float = 0.0,
                     bq: int = 256, bk: int = 512, q_base: int = 0,
                     interpret: bool | None = None):
    """q: (B, Hq, Tq, D), k/v: (B, Hkv, Tk, D) -> o (B, Hq, Tq, D).

    Tq must divide by bq and Tk by bk (the model pads its inputs).
    ``interpret=None`` picks Pallas interpret mode from the backend
    (:func:`repro.kernels.interpret_mode`)."""
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    assert Tq % bq == 0 and Tk % bk == 0, (Tq, bq, Tk, bk)
    qf = q.reshape(B * Hq, Tq, D)
    kf = k.reshape(B * Hkv, Tk, D)
    vf = v.reshape(B * Hkv, Tk, D)

    kernel = functools.partial(_flash_fwd_kernel, bk=bk, causal=causal,
                               softcap=softcap, q_base=q_base,
                               scale=D ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, Tq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, Tk, D), lambda bh, qi, g=g, Hq=Hq, Hkv=Hkv:
                         ((bh // Hq) * Hkv + (bh % Hq) // g, 0, 0)),
            pl.BlockSpec((1, Tk, D), lambda bh, qi, g=g, Hq=Hq, Hkv=Hkv:
                         ((bh // Hq) * Hkv + (bh % Hq) // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Tq, D), v.dtype),
        interpret=interpret_mode() if interpret is None else interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, Tq, D)
