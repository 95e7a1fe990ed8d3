"""Pallas TPU kernel: fused ECF8 decode + GEMM  (y = x @ decode(W)).

The paper's throughput story is that compressed weights stream from memory
and are decompressed *just before* the GEMM.  On TPU we go one step further
and fuse the two: the ECF8-TPU chunk geometry (128 lanes x ``sym_per_lane``
slots) is chosen so **one chunk decodes to exactly one (bk=S, bn=128) weight
tile**, which is fed straight to the MXU from VMEM — compressed bytes are
the only weight traffic that ever touches HBM.

Weight layout: W (K, N) is tiled into (TK, TN) tiles of (S, 128); tile
(tk, tn) is encoded as chunk index ``tk * TN + tn`` with element (k, n) at
slot ``s = k``, lane ``l = n``.  The kernel grid is (TN, TK) with TK
innermost: the fp32 out block (M, 128) for column tn accumulates over tk.

This kernel targets the *decode/serving* GEMM shape (M = batch <= 512, one
M block — the paper's regime: weight-streaming-bound batched token decode).
For prefill-sized M, decode standalone (``ecf8_decode``) + regular GEMM is
the right structure; see DESIGN.md.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tpu_format
from repro.core.tpu_format import LANES, MAX_CODE_LEN
from repro.kernels import interpret_mode
from repro.kernels.ecf8_decode import decode_tables


@dataclass
class TiledECF8Weight:
    """(K, N) fp8 weight in fused-GEMM tile order (host-side arrays)."""

    payload: np.ndarray   # (TK, TN, stride, LANES) uint8
    signmant: np.ndarray  # (TK, TN, ceil(S / 2), LANES) uint8 nibble rows
    lj_limit: np.ndarray  # (8,) int32
    first_lj: np.ndarray
    offset: np.ndarray
    perm: np.ndarray      # (16,) int32
    k: int
    n: int
    sym_per_lane: int

    @property
    def nbytes(self) -> int:
        return (self.payload.nbytes + self.signmant.nbytes + 4 * (8 * 3 + 16))


def encode_tiled(w_bits: np.ndarray,
                 sym_per_lane: int = 256) -> TiledECF8Weight:
    """Pack a (K, N) fp8 weight (uint8 bit view) into fused-GEMM tile order."""
    K, N = w_bits.shape
    S = sym_per_lane
    assert K % S == 0 and N % LANES == 0, (K, N, S, LANES)
    TK, TN = K // S, N // LANES
    # tile (tk, tn), element (k=s, n=l)  ->  chunk tk*TN+tn, slot s, lane l
    perm_elems = (
        w_bits.reshape(TK, S, TN, LANES).transpose(0, 2, 1, 3).reshape(-1)
    )
    c = tpu_format.encode(perm_elems, sym_per_lane=S)
    C, stride, _ = c.payload.shape
    assert C == TK * TN
    rows = tpu_format.signmant_rows(c.signmant, C, S)
    return TiledECF8Weight(
        payload=np.asarray(c.payload).reshape(TK, TN, stride, LANES),
        signmant=rows.reshape(TK, TN, -1, LANES),
        lj_limit=c.lj_limit, first_lj=c.first_lj, offset=c.offset,
        perm=c.perm, k=K, n=N, sym_per_lane=S,
    )


def _fp8_row_f32(sym, sm):
    """Decoded e4m3fn fields -> exact float32 values, by bit assembly
    (the TPU has no fp8 unit on v5e): ``sym`` the 4-bit exponent, ``sm``
    the sign/mantissa nibble, both int32 of one shape."""
    sign = (sm & 8) << 28
    mant = sm & 7
    normal = sign | ((sym + 120) << 23) | (mant << 20)
    subnormal = sign | jax.lax.bitcast_convert_type(
        mant.astype(jnp.float32) * (2.0 ** -9), jnp.int32)
    nan = sign | 0x7FC00000
    bits = jnp.where(sym == 0, subnormal,
                     jnp.where((sym == 15) & (mant == 7), nan, normal))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _fused_kernel(tab_ref, x_ref, payload_ref, sm_ref, out_ref, sm_scr,
                  w_scratch, *, sym_per_lane: int, stride: int):
    S = sym_per_lane
    tk = pl.program_id(1)
    lims = [tab_ref[i] for i in range(MAX_CODE_LEN)]
    firsts = [tab_ref[MAX_CODE_LEN + i] for i in range(MAX_CODE_LEN)]
    offs = [tab_ref[2 * MAX_CODE_LEN + i] for i in range(MAX_CODE_LEN)]
    perm = [tab_ref[3 * MAX_CODE_LEN + k] for k in range(16)]
    payload = payload_ref[0, 0].astype(jnp.int32)     # (stride, L)

    win = ((payload[0:1, :] << 24) | (payload[1:2, :] << 16)
           | (payload[2:3, :] << 8) | payload[3:4, :]).astype(jnp.uint32)
    byteptr = jnp.full((1, LANES), 4, dtype=jnp.int32)
    bits_valid = jnp.full((1, LANES), 32, dtype=jnp.int32)
    sm_scr[...] = sm_ref[0, 0].astype(jnp.int32)      # (ceil(S/2), L)

    row_iota = jax.lax.broadcasted_iota(jnp.int32, (stride, LANES), 0)

    def round_fn(s, carry):
        win, byteptr, bits_valid = carry
        peek = (win >> 24).astype(jnp.int32)
        length = jnp.zeros((1, LANES), jnp.int32)
        sym_idx = jnp.zeros((1, LANES), jnp.int32)
        found = jnp.zeros((1, LANES), jnp.bool_)
        for l in range(1, MAX_CODE_LEN + 1):
            cond = jnp.logical_and(peek < lims[l - 1], jnp.logical_not(found))
            idx_l = offs[l - 1] + ((peek - firsts[l - 1]) >> (8 - l))
            length = jnp.where(cond, l, length)
            sym_idx = jnp.where(cond, idx_l, sym_idx)
            found = jnp.logical_or(found, cond)
        sym = jnp.zeros((1, LANES), jnp.int32)
        for k in range(16):
            sym = jnp.where(sym_idx == k, perm[k], sym)

        sm_row = sm_scr[pl.ds(s // 2, 1), :]
        sm_s = (sm_row >> (4 * (1 - s % 2))) & 0xF
        w_scratch[pl.ds(s, 1), :] = _fp8_row_f32(sym, sm_s)

        win = win << length.astype(jnp.uint32)
        bits_valid = bits_valid - length
        need = bits_valid <= 24
        safe_ptr = jnp.minimum(byteptr, stride - 1)
        nb = jnp.sum(jnp.where(row_iota == safe_ptr, payload, 0),
                     axis=0, keepdims=True).astype(jnp.uint32)
        win = jnp.where(need,
                        win | (nb << (24 - bits_valid).astype(jnp.uint32)),
                        win)
        byteptr = byteptr + need.astype(jnp.int32)
        bits_valid = bits_valid + 8 * need.astype(jnp.int32)
        return win, byteptr, bits_valid

    jax.lax.fori_loop(0, S, round_fn, (win, byteptr, bits_valid))

    @pl.when(tk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.bfloat16), w_scratch[...].astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit,
                   static_argnames=("sym_per_lane", "k", "n", "interpret",
                                    "out_dtype"))
def _matmul_impl(x, payload, signmant, lj_limit, first_lj, offset, perm, *,
                 sym_per_lane: int, k: int, n: int, interpret: bool,
                 out_dtype):
    M = x.shape[0]
    S = sym_per_lane
    TK, TN, stride, _ = payload.shape
    R = signmant.shape[2]
    kernel = functools.partial(_fused_kernel, sym_per_lane=S, stride=stride)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(TN, TK),
            in_specs=[
                pl.BlockSpec((M, S), lambda tn, tk, tab: (0, tk)),     # x
                pl.BlockSpec((1, 1, stride, LANES),
                             lambda tn, tk, tab: (tk, tn, 0, 0)),     # payload
                pl.BlockSpec((1, 1, R, LANES),
                             lambda tn, tk, tab: (tk, tn, 0, 0)),     # sm rows
            ],
            out_specs=pl.BlockSpec((M, LANES), lambda tn, tk, tab: (0, tn)),
            scratch_shapes=[pltpu.VMEM((R, LANES), jnp.int32),
                            pltpu.VMEM((S, LANES), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, n), jnp.float32),
        interpret=interpret,
    )(decode_tables(lj_limit, first_lj, offset, perm), x, payload, signmant)
    return out.astype(out_dtype)


def matmul_pallas(x, w: TiledECF8Weight, *, out_dtype=jnp.float32,
                  interpret: bool | None = None):
    """y = x @ decode(W); x: (M, K) with M <= 512 (decode-GEMM regime).

    ``interpret=None`` picks Pallas interpret mode from the backend
    (:func:`repro.kernels.interpret_mode`)."""
    assert x.shape[1] == w.k, (x.shape, w.k)
    return _matmul_impl(
        jnp.asarray(x), jnp.asarray(w.payload), jnp.asarray(w.signmant),
        jnp.asarray(w.lj_limit), jnp.asarray(w.first_lj),
        jnp.asarray(w.offset), jnp.asarray(w.perm),
        sym_per_lane=w.sym_per_lane, k=w.k, n=w.n,
        interpret=interpret_mode() if interpret is None else interpret,
        out_dtype=out_dtype,
    )
