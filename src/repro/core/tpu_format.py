"""ECF8-TPU: the TPU-native adaptation of the paper's compressed container.

Design (DESIGN.md §3): instead of one sequential bitstream + per-thread bit
gaps (a GPU-warp construct), weights are encoded into **128 interleaved lane
streams per chunk** so an 8x128 TPU vector unit decodes 128 streams in
lockstep:

  * element ``i`` of chunk ``c`` maps to lane ``i % 128``, slot ``i // 128``;
  * every lane of every chunk carries exactly ``sym_per_lane`` symbols, so
    output positions are static (no counting phase / prefix sum needed);
  * codes are canonical Huffman with max length 8 (package-merge), decoded by
    comparing the 8-bit peek against per-length canonical limits — 8
    vectorized compare/selects, no table gathers;
  * chunk payloads are stored transposed ``(stride, 128)`` so "byte j of all
    lanes" is one contiguous vector row.

Two payload layouts:
  * ``uniform``: all chunks padded to the tensor-wide max lane stride —
    shape ``(C, stride, 128)``; decoded in-graph by serve steps, on a TPU
    by the Pallas kernel (``kernels/ecf8_decode.py``, one chunk per grid
    cell), elsewhere fully in parallel with plain jnp;
  * ``ragged``: per-chunk strides + offsets — denser; consumed by the Pallas
    kernel via scalar-prefetch indexed blocks.

Both are bit-exact; the uniform padding tax is reported by benchmarks.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from . import fp8
from .huffman import Codebook, pack_codes

LANES = 128
DEFAULT_SYM_PER_LANE = 256
MAX_CODE_LEN = 8
MIN_STRIDE = 4  # decode window preloads 4 bytes
_ENCODE_GROUP = 32           # chunks per host encode task (1M symbols)
_ENCODE_THREADS = min(8, os.cpu_count() or 1)


@dataclass
class TpuECF8:
    """ECF8-TPU compressed tensor (host-side numpy arrays)."""

    payload: np.ndarray        # uniform: (C, stride, LANES) uint8
    payload_ragged: np.ndarray  # flat uint8, per-chunk (stride_c, LANES) blocks
    chunk_offsets: np.ndarray  # (C+1,) int32 byte offsets into payload_ragged
    chunk_strides: np.ndarray  # (C,) int32
    signmant: np.ndarray       # (ceil(N/2),) uint8 nibble-packed
    # canonical decode tables (all small)
    lj_limit: np.ndarray       # (8,) int32, exclusive, left-justified to 8 bits
    first_lj: np.ndarray       # (8,) int32
    offset: np.ndarray         # (8,) int32
    perm: np.ndarray           # (16,) int32 canonical-order symbol values
    lengths: np.ndarray        # (16,) int32 code length per symbol (encode side)
    n_elem: int
    shape: tuple
    sym_per_lane: int

    @property
    def num_chunks(self) -> int:
        return self.payload.shape[0]

    @property
    def stride(self) -> int:
        return self.payload.shape[1]

    def nbytes(self, layout: str = "ragged") -> int:
        tables = (
            self.lj_limit.nbytes + self.first_lj.nbytes + self.offset.nbytes
            + self.perm.nbytes
        )
        if layout == "uniform":
            return self.payload.nbytes + self.signmant.nbytes + tables
        return (
            self.payload_ragged.nbytes + self.chunk_offsets.nbytes
            + self.signmant.nbytes + tables
        )

    def ratio(self, layout: str = "ragged") -> float:
        return self.nbytes(layout) / max(self.n_elem, 1)


def encode(weight_bits: np.ndarray,
           sym_per_lane: int = DEFAULT_SYM_PER_LANE) -> TpuECF8:
    """Compress an fp8 tensor (uint8 bit view) into ECF8-TPU."""
    orig_shape = tuple(weight_bits.shape)
    flat = np.asarray(weight_bits, dtype=np.uint8).reshape(-1)
    n = flat.shape[0]
    exps = fp8.exponent_field(flat, xp=np).astype(np.uint8)
    signmant = fp8.signmant_nibble(flat, xp=np)

    freqs = np.bincount(exps, minlength=16)
    cb = Codebook.from_freqs(freqs, max_len=MAX_CODE_LEN)

    # auto-cap the chunk so tensors smaller than one full chunk don't pay
    # a whole chunk of padding (small norm/bias tensors, smoke configs)
    S = min(sym_per_lane, max(-(-n // LANES), MIN_STRIDE))
    chunk_sym = LANES * S
    n_pad = -n % chunk_sym
    pad_sym = int(np.argmax(freqs))
    exps_p = np.concatenate([exps, np.full(n_pad, pad_sym, dtype=np.uint8)])
    C = exps_p.shape[0] // chunk_sym

    # chunks code independently under the tensor's one codebook: pack
    # groups of them on a thread pool (numpy releases the GIL)
    groups = [(c0, min(c0 + _ENCODE_GROUP, C))
              for c0 in range(0, C, _ENCODE_GROUP)]
    pack = partial(_encode_chunks, exps_p, cb=cb, sym_per_lane=S)
    if len(groups) > 1:
        with ThreadPoolExecutor(min(len(groups), _ENCODE_THREADS)) as ex:
            parts = list(ex.map(pack, groups))
    else:
        parts = [pack(groups[0])]
    stride_per_chunk = np.concatenate([sc for _, sc in parts])
    stride = int(stride_per_chunk.max())
    payload = np.zeros((C, stride, LANES), np.uint8)
    for (c0, c1), (pay, _) in zip(groups, parts):
        payload[c0:c1, : pay.shape[1]] = pay

    # ragged layout: per-chunk stride_c slices
    offsets = np.zeros(C + 1, dtype=np.int64)
    offsets[1:] = np.cumsum(stride_per_chunk * LANES)
    payload_ragged = payload[
        np.arange(stride)[None, :] < stride_per_chunk[:, None]].reshape(-1)

    return TpuECF8(
        payload=payload,
        payload_ragged=payload_ragged,
        chunk_offsets=offsets.astype(np.int32),
        chunk_strides=stride_per_chunk.astype(np.int32),
        signmant=fp8.pack_nibbles(signmant, xp=np),
        lj_limit=cb.lj_limit.astype(np.int32),
        first_lj=cb.first_lj.astype(np.int32),
        offset=cb.offset.astype(np.int32),
        perm=cb.sorted_syms.astype(np.int32),
        lengths=cb.lengths.astype(np.int32),
        n_elem=n,
        shape=orig_shape,
        sym_per_lane=S,
    )


def decode_ref(c: TpuECF8) -> np.ndarray:
    """Readable per-lane numpy oracle -> original uint8 fp8 bit view."""
    C, stride, L = c.payload.shape
    S = c.sym_per_lane
    syms = np.zeros((C, S, L), dtype=np.uint8)
    cb = _codebook_view(c)
    for ci in range(C):
        for l in range(L):
            stream = c.payload[ci, :, l]
            bitpos = 0
            for s in range(S):
                peek = 0
                for b in range(MAX_CODE_LEN):
                    p = bitpos + b
                    bit = (int(stream[p // 8]) >> (7 - p % 8)) & 1 \
                        if p // 8 < stride else 0
                    peek = (peek << 1) | bit
                sym, ln = cb.decode_peek(peek)
                syms[ci, s, l] = sym
                bitpos += ln
    return _assemble(c, syms.reshape(-1)[: c.n_elem])


def signmant_rows(signmant: np.ndarray, n_chunks: int,
                  sym_per_lane: int) -> np.ndarray:
    """Nibble-packed sign/mantissa plane in element order (two elements
    per byte) -> (C, ceil(S / 2), LANES) uint8 *row layout*: row ``r`` of
    chunk ``c`` holds slot ``2r`` in the high and slot ``2r + 1`` in the
    low nibble, lane by lane.

    Host-side (numpy).  Both on-device decoders read this layout — one
    row per two decode rounds, lane-dense — so no decode ever spreads
    bytes across lanes (on a TPU a trailing dim of 2 pads to 128 lanes,
    a 64x blow-up at checkpoint sizes)."""
    C, S = n_chunks, sym_per_lane
    b = np.zeros(C * S * LANES // 2, np.uint8)
    b[: signmant.shape[0]] = signmant
    b = b.reshape(C, S, LANES // 2)
    nib = np.stack([b >> 4, b & 0xF], axis=-1).reshape(C, S, LANES)
    if S % 2:
        nib = np.pad(nib, ((0, 0), (0, 1), (0, 0)))
    nib = nib.reshape(C, -1, 2, LANES)
    return (nib[:, :, 0] << 4) | nib[:, :, 1]


@partial(jax.jit, static_argnames=("sym_per_lane", "n_elem"))
def _decode_jnp_impl(payload, sm_rows, lj_limit, first_lj, offset, perm,
                     sym_per_lane: int, n_elem: int):
    """Vectorized decode of the uniform layout; all chunks in parallel.

    ``sm_rows`` is the sign/mantissa plane in :func:`signmant_rows`
    layout.  Maintains a per-lane left-aligned uint32 bit window; each
    round does the canonical compare/select decode on the top 8 bits,
    fuses the slot's sign/mantissa nibble into the fp8 byte, shifts, and
    refills at most one byte via a per-lane gather (take_along_axis).
    Invariant: at the top of each round ``bits_valid >= 24 >= 8``.
    Returns the (n_elem,) uint8 fp8 bit view.
    """
    C, stride, L = payload.shape
    S = sym_per_lane
    p32 = payload.astype(jnp.uint32)
    win = (
        (p32[:, 0, :] << 24) | (p32[:, 1, :] << 16)
        | (p32[:, 2, :] << 8) | p32[:, 3, :]
    )                                           # (C, L)
    byteptr = jnp.full((C, L), 4, dtype=jnp.int32)
    bits_valid = jnp.full((C, L), 32, dtype=jnp.int32)

    lj_limit_i = lj_limit.astype(jnp.int32)
    first_lj_i = first_lj.astype(jnp.int32)
    offset_i = offset.astype(jnp.int32)
    perm_i = perm.astype(jnp.int32)

    def round_fn(s, carry):
        win, byteptr, bits_valid, outs = carry
        peek = (win >> 24).astype(jnp.int32)    # (C, L) in [0, 256)
        lt = peek[..., None] < lj_limit_i[None, None, :]   # (C, L, 8)
        length = jnp.argmax(lt, axis=-1).astype(jnp.int32) + 1
        fl = jnp.take(first_lj_i, length - 1)
        off = jnp.take(offset_i, length - 1)
        sym_idx = off + ((peek - fl) >> (8 - length))
        sym = jnp.take(perm_i, sym_idx)
        row = jax.lax.dynamic_index_in_dim(sm_rows, s // 2, axis=1,
                                           keepdims=False).astype(jnp.int32)
        sm = (row >> (4 * (1 - s % 2))) & 0xF
        byte = fp8.assemble(sym, sm, xp=jnp)
        outs = jax.lax.dynamic_update_index_in_dim(outs, byte, s, axis=1)

        win = win << length.astype(jnp.uint32)
        bits_valid = bits_valid - length
        need = bits_valid <= 24
        safe_ptr = jnp.minimum(byteptr, stride - 1)
        nb = jnp.take_along_axis(
            payload, safe_ptr[:, None, :], axis=1
        )[:, 0, :].astype(jnp.uint32)
        win = jnp.where(
            need, win | (nb << (24 - bits_valid).astype(jnp.uint32)), win
        )
        byteptr = byteptr + need.astype(jnp.int32)
        bits_valid = bits_valid + 8 * need.astype(jnp.int32)
        return win, byteptr, bits_valid, outs

    outs = jnp.zeros((C, S, L), dtype=jnp.uint8)
    _, _, _, outs = jax.lax.fori_loop(
        0, S, round_fn, (win, byteptr, bits_valid, outs)
    )
    return outs.reshape(-1)[:n_elem]


def decode_jnp(c: TpuECF8) -> jnp.ndarray:
    """In-graph decode of the uniform layout -> uint8 fp8 bits (n_elem,)."""
    return _decode_jnp_impl(
        jnp.asarray(c.payload),
        jnp.asarray(signmant_rows(c.signmant, c.num_chunks, c.sym_per_lane)),
        jnp.asarray(c.lj_limit), jnp.asarray(c.first_lj),
        jnp.asarray(c.offset), jnp.asarray(c.perm),
        sym_per_lane=c.sym_per_lane, n_elem=c.n_elem,
    )


def _codebook_view(c: TpuECF8) -> Codebook:
    cb = Codebook(lengths=np.asarray(c.lengths), codes=None,  # type: ignore
                  max_len=MAX_CODE_LEN)
    cb.sorted_syms = np.asarray(c.perm)
    cb.lj_limit = np.asarray(c.lj_limit, dtype=np.int64)
    cb.first_lj = np.asarray(c.first_lj, dtype=np.int64)
    cb.offset = np.asarray(c.offset, dtype=np.int64)
    return cb


def _assemble(c: TpuECF8, syms: np.ndarray) -> np.ndarray:
    sm = np.asarray(fp8.unpack_nibbles(c.signmant, c.n_elem, xp=np))
    return fp8.assemble(syms.astype(np.uint8), sm, xp=np).reshape(c.shape)


def _encode_chunks(exps_p: np.ndarray, group: tuple[int, int], *,
                   cb: Codebook, sym_per_lane: int):
    """Code chunks ``[c0, c1)`` of the padded exponent plane ->
    ((c1 - c0, stride_g, LANES) payload at the group's own max stride,
    (c1 - c0,) per-chunk strides)."""
    c0, c1 = group
    S = sym_per_lane
    G = c1 - c0
    # element (c, s, l) -> index c*chunk_sym + s*LANES + l; per-lane
    # streams: rows = (c, l), S symbols each
    exps_r = exps_p[c0 * S * LANES: c1 * S * LANES].reshape(
        G, S, LANES).transpose(0, 2, 1).reshape(G * LANES, S)
    codes_r = cb.codes.astype(np.int32)[exps_r]           # (G*L, S)
    lens_r = cb.lengths.astype(np.int32)[exps_r]
    ends_r = np.cumsum(lens_r, axis=1, dtype=np.int32)
    lane_bytes = (ends_r[:, -1] + 7) // 8                 # (G*L,)
    stride_per_chunk = np.maximum(
        lane_bytes.reshape(G, LANES).max(axis=1), MIN_STRIDE
    ).astype(np.int64)
    stride = int(stride_per_chunk.max())
    bytemat = pack_codes(codes_r, lens_r, ends_r - lens_r, stride,
                         MAX_CODE_LEN)
    return (bytemat.reshape(G, LANES, stride).transpose(0, 2, 1),
            stride_per_chunk)
