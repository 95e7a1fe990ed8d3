"""Lossless ECF8 page codec: exponent-plane entropy coding for K/V pages.

The paper's exponent-concentration law is a statement about *trained
tensors*; Heilper & Singer (2025) show the same low-entropy exponent
structure holds for K/V caches, and ZipNN confirms exponent-grouped
entropy coding is the winning layout.  This module extends the repo's
weight container (``core.tpu_format``) from the fp8 4-bit exponent field
to the 8-bit exponent field of bf16/f32 cache pages:

  * each element is split into an **exponent symbol** (4 bits for fp8,
    8 bits for bf16/f32) and a raw **sign+mantissa plane** (packed
    nibbles / 1 byte / 3 bytes per element);
  * the exponent plane is canonical-Huffman coded per page
    (``core.huffman.Codebook``, package-merge length-limited) into 128
    interleaved lane streams — the same TPU-native layout the weight
    decode kernel consumes, so ``kvcache.kernels`` reuses the
    window-refill idiom of ``kernels/ecf8_decode.py``;
  * round-trips are bit-exact for *any* bit content (NaNs included):
    encode/decode only ever touch integer bit views.

Layout per page: payload ``(stride, 128)`` uint8 (byte j of all lanes is
one contiguous row), every lane carries ``ceil(n_elem / 128)`` symbols,
short pages are padded with the page's modal symbol.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import fp8
from repro.core.huffman import Codebook, pack_codes

LANES = 128
MIN_STRIDE = 4          # decode window preloads 4 bytes
EXP4_MAX_LEN = 8        # fp8: 16 symbols, single-byte peek
EXP8_MAX_LEN = 12       # bf16/f32: 256 symbols, 12-bit peek (<= 16)

# dtype name -> (exponent bits, sign+mantissa bytes per element * 2)
# sm bytes are stored as numerator/2 so fp8's packed nibble (half a byte
# per element) stays integral.
_PLANES = {
    "float8_e4m3fn": (4, 1),
    "bfloat16": (8, 2),
    "float32": (8, 6),
}


def plane_spec(dtype_name: str) -> tuple[int, int, int]:
    """(exp_bits, max_code_len, sm_halfbytes_per_elem) for a cache dtype."""
    if dtype_name not in _PLANES:
        raise ValueError(f"unsupported page dtype {dtype_name!r}; "
                         f"supported: {sorted(_PLANES)}")
    exp_bits, sm_half = _PLANES[dtype_name]
    max_len = EXP4_MAX_LEN if exp_bits == 4 else EXP8_MAX_LEN
    return exp_bits, max_len, sm_half


def sm_bytes(dtype_name: str, n_elem: int) -> int:
    """Raw sign+mantissa plane bytes for ``n_elem`` elements."""
    _, _, sm_half = plane_spec(dtype_name)
    return (n_elem * sm_half + 1) // 2


def sym_per_lane(n_elem: int) -> int:
    """Symbols each of the 128 lane streams carries for an
    ``n_elem``-element page (``ceil(n_elem / LANES)``; short pages are
    padded to this with the page's modal symbol)."""
    return -(-n_elem // LANES)


# --------------------------------------------------------------------------
# bit-plane split / assemble (host numpy, pure integer ops)
# --------------------------------------------------------------------------

def split_planes(values: np.ndarray) -> tuple[np.ndarray, np.ndarray, str]:
    """Split a page into (exponent symbols, raw sign+mantissa bytes).

    Accepts fp8 / bf16 / f32 arrays (or a raw uint8 fp8 bit view)."""
    values = np.asarray(values)
    name = str(values.dtype)
    if name == "uint8":
        name = "float8_e4m3fn"
    if name == "float8_e4m3fn":
        bits = values.view(np.uint8).reshape(-1)
        exp = fp8.exponent_field(bits, xp=np)
        sm = fp8.pack_nibbles(fp8.signmant_nibble(bits, xp=np), xp=np)
        return exp.astype(np.int64), sm, name
    if name == "bfloat16":
        u = values.view(np.uint16).reshape(-1)
        exp = (u >> 7) & np.uint16(0xFF)
        sm = (((u >> 8) & np.uint16(0x80)) | (u & np.uint16(0x7F)))
        return exp.astype(np.int64), sm.astype(np.uint8), name
    if name == "float32":
        u = values.view(np.uint32).reshape(-1)
        exp = (u >> 23) & np.uint32(0xFF)
        sm24 = ((u >> 8) & np.uint32(0x800000)) | (u & np.uint32(0x7FFFFF))
        smb = np.stack([(sm24 >> 16) & 0xFF, (sm24 >> 8) & 0xFF,
                        sm24 & 0xFF], axis=-1).astype(np.uint8).reshape(-1)
        return exp.astype(np.int64), smb, name
    raise ValueError(f"unsupported page dtype {name!r}")


def assemble_planes(exp: np.ndarray, sm: np.ndarray, dtype_name: str,
                    n_elem: int) -> np.ndarray:
    """Inverse of :func:`split_planes` -> raw bit view (uint8/16/32)."""
    exp = np.asarray(exp, dtype=np.uint32)[:n_elem]
    if dtype_name == "float8_e4m3fn":
        nib = np.asarray(fp8.unpack_nibbles(sm, n_elem, xp=np))
        return fp8.assemble(exp.astype(np.uint8), nib, xp=np)
    if dtype_name == "bfloat16":
        sm = sm.astype(np.uint16)[:n_elem]
        u = ((sm & 0x80) << 8) | (exp.astype(np.uint16) << 7) | (sm & 0x7F)
        return u.astype(np.uint16)
    if dtype_name == "float32":
        b = sm.reshape(-1, 3).astype(np.uint32)[:n_elem]
        sm24 = (b[:, 0] << 16) | (b[:, 1] << 8) | b[:, 2]
        u = ((sm24 & 0x800000) << 8) | (exp << 23) | (sm24 & 0x7FFFFF)
        return u.astype(np.uint32)
    raise ValueError(dtype_name)


_BITVIEW = {"float8_e4m3fn": np.uint8, "bfloat16": np.uint16,
            "float32": np.uint32}


# --------------------------------------------------------------------------
# encode (host)
# --------------------------------------------------------------------------

@dataclass
class CompressedPage:
    """One entropy-coded cache page (host-side numpy arrays)."""

    payload: np.ndarray    # (stride, LANES) uint8 interleaved lane streams
    signmant: np.ndarray   # raw sign+mantissa plane, uint8
    lj_limit: np.ndarray   # (max_len,) int32 canonical decode tables
    first_lj: np.ndarray   # (max_len,) int32
    offset: np.ndarray     # (max_len,) int32
    perm: np.ndarray       # (n_symbols,) int32 canonical-order symbols
    n_elem: int
    n_active: int          # symbols with nonzero frequency
    dtype_name: str
    shape: tuple

    @property
    def stride(self) -> int:
        return self.payload.shape[0]

    def nbytes(self) -> int:
        """True (ragged) compressed bytes, codebook included.

        A canonical codebook serializes as the active-symbol list in
        canonical order (1 byte each) plus a count per code length
        (2 bytes each); the int32 decode tables are derived from that on
        load, they are a decode-speed representation, not payload."""
        header = self.n_active + 2 * len(self.lj_limit)
        return self.payload.nbytes + self.signmant.nbytes + header

    def ratio(self) -> float:
        itemsize = np.dtype(_BITVIEW[self.dtype_name]).itemsize
        return self.nbytes() / max(self.n_elem * itemsize, 1)

    def tables(self) -> np.ndarray:
        """(3, max_len) int32 stack consumed by the decode paths."""
        return np.stack([self.lj_limit, self.first_lj, self.offset])


def encode_page(values: np.ndarray) -> CompressedPage:
    """Compress one page losslessly (exponent plane entropy-coded)."""
    values = np.asarray(values)
    orig_shape = tuple(values.shape)
    exp, sm, dtype_name = split_planes(values)
    n = exp.shape[0]
    if n == 0:
        raise ValueError("empty page")
    exp_bits, max_len, _ = plane_spec(dtype_name)
    n_sym = 1 << exp_bits

    freqs = np.bincount(exp, minlength=n_sym)
    cb = Codebook.from_freqs(freqs, max_len=max_len)

    S = sym_per_lane(n)
    pad_sym = int(np.argmax(freqs))
    exp_p = np.concatenate(
        [exp, np.full(S * LANES - n, pad_sym, dtype=np.int64)])
    payload = _encode_lanes(exp_p.reshape(S, LANES), cb)
    return CompressedPage(
        payload=payload, signmant=sm,
        lj_limit=cb.lj_limit.astype(np.int32),
        first_lj=cb.first_lj.astype(np.int32),
        offset=cb.offset.astype(np.int32),
        perm=cb.sorted_syms.astype(np.int32),
        n_elem=n, n_active=int((freqs > 0).sum()),
        dtype_name=dtype_name, shape=orig_shape,
    )


def _encode_lanes(syms: np.ndarray, cb: Codebook) -> np.ndarray:
    """(S, LANES) symbols -> (stride, LANES) uint8 interleaved payload.

    Element ``i`` maps to lane ``i % LANES``, slot ``i // LANES`` — the
    layout of ``core.tpu_format`` with a single chunk per page."""
    codes_r = cb.codes[syms].T                        # (LANES, S)
    lens_r = cb.lengths[syms].T.astype(np.int64)      # (LANES, S)
    ends = np.cumsum(lens_r, axis=1)
    stride = max(int(-(-int(ends[:, -1].max()) // 8)), MIN_STRIDE)
    bytemat = pack_codes(codes_r, lens_r, ends - lens_r, stride,
                         cb.max_len)                  # (LANES, stride)
    return bytemat.T.copy()


# --------------------------------------------------------------------------
# decode (host oracle)
# --------------------------------------------------------------------------

def decode_page(cp: CompressedPage) -> np.ndarray:
    """Readable per-lane oracle -> original values (bit-exact)."""
    _, max_len, _ = plane_spec(cp.dtype_name)
    S = sym_per_lane(cp.n_elem)
    cb = Codebook(lengths=np.zeros(len(cp.perm), np.int32), codes=None,
                  max_len=max_len)  # type: ignore[arg-type]
    cb.sorted_syms = np.asarray(cp.perm)
    cb.lj_limit = np.asarray(cp.lj_limit, dtype=np.int64)
    cb.first_lj = np.asarray(cp.first_lj, dtype=np.int64)
    cb.offset = np.asarray(cp.offset, dtype=np.int64)

    stride = cp.stride
    syms = np.zeros((S, LANES), dtype=np.int64)
    for lane in range(LANES):
        stream = cp.payload[:, lane]
        bitpos = 0
        for s in range(S):
            peek = 0
            for b in range(max_len):
                p = bitpos + b
                bit = ((int(stream[p // 8]) >> (7 - p % 8)) & 1
                       if p // 8 < stride else 0)
                peek = (peek << 1) | bit
            sym, ln = cb.decode_peek(peek)
            syms[s, lane] = sym
            bitpos += ln
    exp = syms.reshape(-1)[: cp.n_elem]
    bits = assemble_planes(exp, cp.signmant, cp.dtype_name, cp.n_elem)
    view = {"float8_e4m3fn": jnp.float8_e4m3fn, "bfloat16": jnp.bfloat16,
            "float32": np.float32}[cp.dtype_name]
    return bits.view(view).reshape(cp.shape)


# --------------------------------------------------------------------------
# decode (in-graph, vectorized over pages — the serving hot path)
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_elem", "dtype_name"))
def decode_pages_jnp(payload, signmant, tables, perm, *, n_elem: int,
                     dtype_name: str):
    """Decode N compressed pages in parallel -> (N, n_elem) values.

    Args:
      payload:  (N, stride, LANES) uint8, zero-padded lane streams.
      signmant: (N, sm_bytes) uint8 raw sign+mantissa plane.
      tables:   (N, 3, max_len) int32 — lj_limit / first_lj / offset.
      perm:     (N, n_symbols) int32 canonical symbol permutation.

    Per-lane uint32 bit window, ``max_len``-bit peek, <= 2 refill bytes
    per round (codes can span two bytes once ``max_len > 8``); invariant:
    ``bits_valid >= 16 >= max_len`` at the top of every round.
    """
    sym_idx = _decode_indices_jnp(payload, tables, n_elem=n_elem)
    return finish_pages_jnp(sym_idx, signmant, perm, n_elem=n_elem,
                            dtype_name=dtype_name)


def finish_pages_jnp(sym_idx, signmant, perm, *, n_elem: int,
                     dtype_name: str):
    """Canonical indices (N, S, LANES) -> (N, n_elem) values.

    The shared tail of both entropy-decode paths (pure-jnp and Pallas):
    canonical permutation, then the sign/mantissa fuse."""
    syms = jnp.take_along_axis(
        perm.astype(jnp.int32), sym_idx.reshape(sym_idx.shape[0], -1),
        axis=1, mode="clip")[:, :n_elem]
    return assemble_pages_jnp(syms, signmant, n_elem=n_elem,
                              dtype_name=dtype_name)


def _decode_indices_jnp(payload, tables, *, n_elem: int):
    """Canonical-index decode of all pages -> (N, S, LANES) int32."""
    N, stride, _ = payload.shape
    S = sym_per_lane(n_elem)
    L = tables.shape[-1]
    p32 = payload.astype(jnp.uint32)
    win = ((p32[:, 0, :] << 24) | (p32[:, 1, :] << 16)
           | (p32[:, 2, :] << 8) | p32[:, 3, :])       # (N, LANES)
    byteptr = jnp.full((N, LANES), 4, dtype=jnp.int32)
    bits_valid = jnp.full((N, LANES), 32, dtype=jnp.int32)
    lj = tables[:, 0].astype(jnp.int32)                # (N, L)
    fl_t = tables[:, 1].astype(jnp.int32)
    off_t = tables[:, 2].astype(jnp.int32)

    def round_fn(s, carry):
        win, byteptr, bits_valid, outs = carry
        peek = (win >> (32 - L)).astype(jnp.int32)     # (N, LANES)
        lt = peek[..., None] < lj[:, None, :]          # (N, LANES, L)
        length = jnp.argmax(lt, axis=-1).astype(jnp.int32) + 1
        fl = jnp.take_along_axis(fl_t, length - 1, axis=1, mode="clip")
        off = jnp.take_along_axis(off_t, length - 1, axis=1, mode="clip")
        sym_idx = off + ((peek - fl) >> (L - length))
        outs = jax.lax.dynamic_update_index_in_dim(outs, sym_idx, s, axis=1)

        win = win << length.astype(jnp.uint32)
        bits_valid = bits_valid - length
        for _ in range(2):                             # <= 2 bytes/round
            need = bits_valid <= 24
            safe_ptr = jnp.minimum(byteptr, stride - 1)
            nb = jnp.take_along_axis(
                payload, safe_ptr[:, None, :], axis=1)[:, 0, :] \
                .astype(jnp.uint32)
            shift = jnp.maximum(24 - bits_valid, 0).astype(jnp.uint32)
            win = jnp.where(need, win | (nb << shift), win)
            byteptr = byteptr + need.astype(jnp.int32)
            bits_valid = bits_valid + 8 * need.astype(jnp.int32)
        return win, byteptr, bits_valid, outs

    outs = jnp.zeros((N, S, LANES), dtype=jnp.int32)
    _, _, _, outs = jax.lax.fori_loop(
        0, S, round_fn, (win, byteptr, bits_valid, outs))
    return outs


def assemble_pages_jnp(syms, signmant, *, n_elem: int, dtype_name: str):
    """(N, n_elem) exponent symbols + raw sm plane -> (N, n_elem) values."""
    syms = syms.astype(jnp.uint32)
    if dtype_name == "float8_e4m3fn":
        hi = (signmant >> 4) & jnp.uint8(0x0F)
        lo = signmant & jnp.uint8(0x0F)
        nib = jnp.stack([hi, lo], axis=-1).reshape(
            signmant.shape[0], -1)[:, :n_elem]
        bits = fp8.assemble(syms.astype(jnp.uint8), nib, xp=jnp)
        return jax.lax.bitcast_convert_type(bits, jnp.float8_e4m3fn)
    if dtype_name == "bfloat16":
        sm = signmant[:, :n_elem].astype(jnp.uint16)
        u = (((sm & 0x80) << 8) | (syms.astype(jnp.uint16) << 7)
             | (sm & 0x7F))
        return jax.lax.bitcast_convert_type(u, jnp.bfloat16)
    if dtype_name == "float32":
        b = signmant.reshape(signmant.shape[0], -1, 3).astype(jnp.uint32)
        b = b[:, :n_elem]
        sm24 = (b[..., 0] << 16) | (b[..., 1] << 8) | b[..., 2]
        u = ((sm24 & 0x800000) << 8) | (syms << 23) | (sm24 & 0x7FFFFF)
        return jax.lax.bitcast_convert_type(u, jnp.float32)
    raise ValueError(dtype_name)
