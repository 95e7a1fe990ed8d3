"""Pallas TPU kernel: ECF8-TPU interleaved Huffman decode (DESIGN.md §3).

One grid cell decodes ``GROUP`` = 8 chunks; a chunk is 128 interleaved
lane streams x ``sym_per_lane`` symbols.  The kernel is the TPU-native
replacement for the paper's CUDA Algorithm 1:

  * the 8x128 VPU holds one bit window **per lane stream**, chunk ``g`` of
    the cell in sublane ``g``: every operation works on whole (8, 128)
    vregs and all 1024 streams decode one symbol per loop round in
    lockstep;
  * canonical max-8-bit codes are decoded by an unrolled compare/select
    chain against the per-length canonical limits (scalar reads of the
    40-entry decode table, scalar-prefetched into SMEM) — no gathers;
  * the window is 64 bits (two uint32 halves) refilled a 32-bit word at a
    time: a round consumes at most 8 bits, so a refill whenever at most
    32 bits remain keeps the 8-bit peek valid.  The refill selects "word
    j of every stream" rows of the cell's (words, 8, 128) block — a
    broadcast-compare + select per word row, all vector ops;
  * the sign/mantissa nibbles are fused into the final fp8 byte
    in-register (the paper's phase-2 "decode and assemble").

The wrapper regroups the uniform layout's (C, stride, 128) payload into
big-endian words in the (C / 8, words, 8, 128) cell layout (and the
sign/mantissa rows alike), and the decoded (C / 8, S, 8, 128) bytes back —
XLA transposes over the compressed tensor, small beside the decode.

VMEM per cell: payload words (stride x 1 KiB) + sign/mantissa rows
(S / 2 x 1 KiB) + output (S x 1 KiB) + int32 staging of the last two
(6 x S x 1 KiB): under 2 MiB at S = 256.  Decoded rows are staged in int32
(a dynamic row store is a 32-bit operation on the TPU) and cast to bytes
once per cell.

Validated in interpret mode against ``core.tpu_format`` oracles (tests sweep
shapes and code distributions).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tpu_format import LANES, MAX_CODE_LEN
from repro.kernels import interpret_mode

GROUP = 8       # chunks per grid cell: one per sublane of a vreg


def _decode_group_kernel(tab_ref, words_ref, sm_ref, out_ref, sm_scr,
                         sym_scr, *, sym_per_lane: int, n_words: int):
    S, shape = sym_per_lane, (GROUP, LANES)
    lims = [tab_ref[i] for i in range(MAX_CODE_LEN)]
    firsts = [tab_ref[MAX_CODE_LEN + i] for i in range(MAX_CODE_LEN)]
    offs = [tab_ref[2 * MAX_CODE_LEN + i] for i in range(MAX_CODE_LEN)]
    perm = [tab_ref[3 * MAX_CODE_LEN + k] for k in range(16)]
    # sign/mantissa nibbles, two slots per byte row (signmant_rows layout)
    sm_scr[...] = sm_ref[0].astype(jnp.int32)       # (ceil(S/2), 8, L)

    def round_fn(s, carry):
        hi, lo, wordptr, bits_valid = carry
        peek = (hi >> 24).astype(jnp.int32)          # (8, L) in [0, 256)

        # the shortest length l whose limit exceeds the peek (a valid
        # stream always has one; unrolled, static)
        top = MAX_CODE_LEN
        length = jnp.full(shape, top, jnp.int32)
        sym_idx = offs[top - 1] + (peek - firsts[top - 1])
        for l in range(top - 1, 0, -1):
            cond = peek < lims[l - 1]
            length = jnp.where(cond, l, length)
            sym_idx = jnp.where(
                cond, offs[l - 1] + ((peek - firsts[l - 1]) >> (top - l)),
                sym_idx)
        sym = jnp.zeros(shape, jnp.int32)
        for k in range(16):                           # canonical perm, static
            sym = jnp.where(sym_idx == k, perm[k], sym)

        # emit fp8 byte = sign | exponent | mantissa
        sm_s = (sm_scr[s // 2] >> (4 * (1 - s % 2))) & 0xF
        sym_scr[s] = ((sm_s & 8) << 4) | (sym << 3) | (sm_s & 7)

        # shift the 64-bit window; at most 32 valid bits left -> append
        # the stream's next word right behind them (1 <= length <= 8)
        n = length.astype(jnp.uint32)
        hi = (hi << n) | (lo >> (32 - n))
        lo = lo << n
        bits_valid = bits_valid - length
        need = bits_valid <= 32
        ptr = jnp.minimum(wordptr, n_words - 1)
        word = jnp.zeros(shape, jnp.uint32)
        for j in range(n_words):
            word = jnp.where(ptr == j, words_ref[0, j], word)
        sh_hi = jnp.minimum(bits_valid, 31).astype(jnp.uint32)
        sh_lo = jnp.maximum(32 - bits_valid, 0).astype(jnp.uint32)
        hi = jnp.where(need & (bits_valid < 32), hi | (word >> sh_hi), hi)
        lo = jnp.where(need, lo | (word << sh_lo), lo)
        wordptr = wordptr + need.astype(jnp.int32)
        bits_valid = bits_valid + 32 * need.astype(jnp.int32)
        return hi, lo, wordptr, bits_valid

    init = (words_ref[0, 0], words_ref[0, 1],
            jnp.full(shape, 2, jnp.int32), jnp.full(shape, 64, jnp.int32))
    jax.lax.fori_loop(0, S, round_fn, init)
    out_ref[0] = sym_scr[...].astype(jnp.uint8)


def _to_cells(x, n_cells: int):
    """(C, R, L) -> (n_cells, R, GROUP, L): chunk ``GROUP * i + g`` goes to
    sublane ``g`` of cell ``i`` (zero chunks pad the last cell)."""
    C, R, L = x.shape
    x = jnp.pad(x, ((0, n_cells * GROUP - C), (0, 0), (0, 0)))
    return x.reshape(n_cells, GROUP, R, L).transpose(0, 2, 1, 3)


def _payload_words(payload):
    """(C, stride, L) uint8 lane streams -> (C, ceil(stride / 4), L)
    uint32 big-endian words (zero-padded to at least two words)."""
    C, stride, L = payload.shape
    n_words = max(-(-stride // 4), 2)
    p = jnp.pad(payload, ((0, 0), (0, 4 * n_words - stride), (0, 0)))
    p = p.reshape(C, n_words, 4, L).astype(jnp.uint32)
    return ((p[:, :, 0] << 24) | (p[:, :, 1] << 16) | (p[:, :, 2] << 8)
            | p[:, :, 3])


def decode_tables(lj_limit, first_lj, offset, perm):
    """The 40 canonical-decode scalars (limits, firsts, offsets, perm) as
    one int32 vector — the kernels' scalar-prefetch (SMEM) operand."""
    return jnp.concatenate([jnp.asarray(t, jnp.int32).reshape(-1)
                            for t in (lj_limit, first_lj, offset, perm)])


@functools.partial(jax.jit, static_argnames=("sym_per_lane", "interpret"))
def decode_pallas(payload, sm_rows, lj_limit, first_lj, offset,
                  perm, *, sym_per_lane: int, interpret: bool | None = None):
    """Decode all chunks -> fp8 bytes (C, S, LANES) uint8.

    Args:
      payload: (C, stride, LANES) uint8 uniform-layout payload.
      sm_rows: (C, ceil(S / 2), LANES) uint8 sign/mantissa nibbles in
        ``tpu_format.signmant_rows`` layout.
      lj_limit / first_lj / offset: (8,) int32 canonical decode tables.
      perm: (16,) int32 canonical symbol permutation.
      interpret: Pallas interpret mode; ``None`` picks it from the
        backend (:func:`repro.kernels.interpret_mode`).
    """
    C = payload.shape[0]
    S = sym_per_lane
    n_cells = -(-C // GROUP)
    words = _to_cells(_payload_words(payload), n_cells)
    sm = _to_cells(sm_rows, n_cells)
    W, R = words.shape[1], sm.shape[1]
    kernel = functools.partial(_decode_group_kernel, sym_per_lane=S,
                               n_words=W)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_cells,),
            in_specs=[
                pl.BlockSpec((1, W, GROUP, LANES),
                             lambda c, tab: (c, 0, 0, 0)),
                pl.BlockSpec((1, R, GROUP, LANES),
                             lambda c, tab: (c, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, S, GROUP, LANES),
                                   lambda c, tab: (c, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((R, GROUP, LANES), jnp.int32),
                            pltpu.VMEM((S, GROUP, LANES), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_cells, S, GROUP, LANES),
                                       jnp.uint8),
        interpret=interpret_mode() if interpret is None else interpret,
    )(decode_tables(lj_limit, first_lj, offset, perm), words, sm)
    return out.transpose(0, 2, 1, 3).reshape(-1, S, LANES)[:C]
