"""Pallas TPU kernel: interleaved Huffman decode of compressed KV pages.

One grid cell decodes one page = 128 interleaved lane streams x
``sym_per_lane`` symbols — the same window-refill idiom as the weight
kernel (``kernels/ecf8_decode.py``) generalized for cache pages:

  * codes may be up to 12 bits (bf16/f32 pages code the full 8-bit
    exponent field), so the peek is ``max_len`` bits and the window
    refills **up to two bytes** per round (vs one for 8-bit codes);
  * decode tables are **per page** (every page carries its own canonical
    codebook) — all pages' (3, L) tables and canonical permutations are
    scalar-prefetched into SMEM and each grid cell reads its own rows as
    scalars; the permutation travels packed four 8-bit symbols to an
    int32 word, so a 256-symbol page costs 64 words;
  * once the rounds have filled the page's (S, 128) block of canonical
    indices, the kernel maps the whole block to exponent symbols with one
    compare/select per permutation entry (16 for fp8 pages, 256 for
    bf16/f32), as the weight kernel applies its 16-entry permutation —
    vector selects, where an XLA gather would run element by element.
    The caller only merges the sign/mantissa plane
    (``codec.assemble_pages_jnp``).

VMEM per cell: payload (stride x 128) + output (S x 128 x 4B), both far
inside budget for realistic page sizes (<= 64K elements).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels import interpret_mode
from . import codec
from .codec import LANES


def _decode_page_kernel(tab_ref, perm_ref, payload_ref, out_ref, *,
                        sym_per_lane: int, stride: int, max_len: int,
                        n_sym: int):
    S, L = sym_per_lane, max_len
    # this page's (3, L) decode table rows, as SMEM scalars
    base = pl.program_id(0) * (3 * L)
    lims = [tab_ref[base + i] for i in range(L)]
    firsts = [tab_ref[base + L + i] for i in range(L)]
    offs = [tab_ref[base + 2 * L + i] for i in range(L)]
    payload = payload_ref[0].astype(jnp.int32)         # (stride, LANES)

    win = ((payload[0:1, :] << 24) | (payload[1:2, :] << 16)
           | (payload[2:3, :] << 8) | payload[3:4, :]).astype(jnp.uint32)
    byteptr = jnp.full((1, LANES), 4, dtype=jnp.int32)
    bits_valid = jnp.full((1, LANES), 32, dtype=jnp.int32)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (stride, LANES), 0)

    def round_fn(s, carry):
        win, byteptr, bits_valid = carry
        peek = (win >> (32 - L)).astype(jnp.int32)      # (1, LANES)

        length = jnp.zeros((1, LANES), jnp.int32)
        sym_idx = jnp.zeros((1, LANES), jnp.int32)
        found = jnp.zeros((1, LANES), jnp.bool_)
        for l in range(1, L + 1):                      # unrolled, static
            cond = jnp.logical_and(peek < lims[l - 1], jnp.logical_not(found))
            idx_l = offs[l - 1] + ((peek - firsts[l - 1]) >> (L - l))
            length = jnp.where(cond, l, length)
            sym_idx = jnp.where(cond, idx_l, sym_idx)
            found = jnp.logical_or(found, cond)

        out_ref[0, pl.ds(s, 1), :] = sym_idx

        win = win << length.astype(jnp.uint32)
        bits_valid = bits_valid - length
        for _ in range(2):   # <= 2 refill bytes/round for max_len <= 16
            need = bits_valid <= 24
            safe_ptr = jnp.minimum(byteptr, stride - 1)
            mask = row_iota == safe_ptr                # (stride, LANES)
            nb = jnp.sum(jnp.where(mask, payload, 0), axis=0,
                         keepdims=True).astype(jnp.uint32)  # (1, LANES)
            shift = jnp.maximum(24 - bits_valid, 0).astype(jnp.uint32)
            win = jnp.where(need, win | (nb << shift), win)
            byteptr = byteptr + need.astype(jnp.int32)
            bits_valid = bits_valid + 8 * need.astype(jnp.int32)
        return win, byteptr, bits_valid

    jax.lax.fori_loop(0, S, round_fn, (win, byteptr, bits_valid))

    # canonical index -> exponent symbol over the whole (S, LANES) block:
    # one select per permutation entry, indices clipped as the reference
    # gather's mode="clip" does; word j of the page's packed permutation
    # holds entries 4j .. 4j + 3, one byte each
    idx = jnp.clip(out_ref[0], 0, n_sym - 1)
    n_words = -(-n_sym // 4)
    pbase = pl.program_id(0) * n_words

    def perm_fn(j, sym):
        word = perm_ref[pbase + j]
        for b in range(4):
            sym = jnp.where(idx == 4 * j + b, (word >> (8 * b)) & 0xFF, sym)
        return sym

    out_ref[0] = jax.lax.fori_loop(0, n_words, perm_fn, jnp.zeros_like(idx))


def _pack_perm(perm):
    """(N, n_sym) int32 permutations -> (N * ceil(n_sym / 4),) int32,
    entry ``4j + b`` of a page in byte ``b`` of its word ``j``."""
    N, n_sym = perm.shape
    p = jnp.pad(perm.astype(jnp.int32) & 0xFF,
                ((0, 0), (0, -n_sym % 4))).reshape(N, -1, 4)
    return (p[..., 0] | (p[..., 1] << 8) | (p[..., 2] << 16)
            | (p[..., 3] << 24)).reshape(-1)


# pages per kernel call: the SMEM operands take (3, 12) int32 tables +
# 64 packed permutation words = 400 B a bf16 page, 800 KiB at 2048 pages
# (of 1 MiB)
_MAX_PAGES_PER_CALL = 2048


@functools.partial(jax.jit, static_argnames=("n_elem", "interpret"))
def decode_page_indices_pallas(payload, tables, perm, *, n_elem: int,
                               interpret: bool | None = None):
    """Decode the exponent symbols of N pages.

    Args:
      payload: (N, stride, LANES) uint8 zero-padded lane streams.
      tables:  (N, 3, L) int32 — lj_limit / first_lj / offset per page.
      perm:    (N, n_symbols) int32 canonical symbol permutation per page.
        Tables and permutations (packed four symbols a word) reach the
        kernel through scalar prefetch (SMEM).
      interpret: Pallas interpret mode; ``None`` picks it from the
        backend (:func:`repro.kernels.interpret_mode`).

    Returns (N, S, LANES) int32 exponent symbols.
    """
    N, stride, _ = payload.shape
    if N > _MAX_PAGES_PER_CALL:   # the SMEM operands must fit (1 MiB)
        return jnp.concatenate([
            decode_page_indices_pallas(
                payload[i: i + _MAX_PAGES_PER_CALL],
                tables[i: i + _MAX_PAGES_PER_CALL],
                perm[i: i + _MAX_PAGES_PER_CALL], n_elem=n_elem,
                interpret=interpret)
            for i in range(0, N, _MAX_PAGES_PER_CALL)])
    L = tables.shape[-1]
    S = codec.sym_per_lane(n_elem)
    kernel = functools.partial(_decode_page_kernel, sym_per_lane=S,
                               stride=stride, max_len=L,
                               n_sym=perm.shape[-1])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N,),
            in_specs=[pl.BlockSpec((1, stride, LANES),
                                   lambda c, tab, prm: (c, 0, 0))],
            out_specs=pl.BlockSpec((1, S, LANES),
                                   lambda c, tab, prm: (c, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, S, LANES), jnp.int32),
        interpret=interpret_mode() if interpret is None else interpret,
    )(tables.reshape(-1).astype(jnp.int32), _pack_perm(perm), payload)


def decode_pages(payload, signmant, tables, perm, *, n_elem: int,
                 dtype_name: str, interpret: bool | None = None):
    """Full page decode via the Pallas kernel -> (N, n_elem) values.

    Same contract as ``codec.decode_pages_jnp`` (the pure-XLA oracle the
    serving engine uses in-graph) and bit-identical to it; the kernel
    decodes exponent symbols, permutation included, and the
    sign/mantissa merge follows in XLA.  The two parts carry the scopes
    ``kv.cold_decode`` and ``kv.cold_permute`` in their ops' metadata, so
    a profiler trace names them."""
    with jax.named_scope("kv.cold_decode"):
        syms = decode_page_indices_pallas(payload, tables, perm,
                                          n_elem=n_elem, interpret=interpret)
    with jax.named_scope("kv.cold_permute"):
        return codec.assemble_pages_jnp(
            syms.reshape(syms.shape[0], -1)[:, :n_elem], signmant,
            n_elem=n_elem, dtype_name=dtype_name)


# (mesh, batch axes, n_elem, dtype, interpret) -> shard_map'ed decode;
# shared across callers so repeated cold-pool decodes reuse one program
_SHARDED_DECODE_CACHE: dict = {}


def decode_pages_sharded(payload, signmant, tables, perm, mesh, *,
                         n_elem: int, dtype_name: str,
                         interpret: bool | None = None):
    """Decode a cold pool whose page dim shards over the mesh batch axes.

    The serving cache shards cold-pool leaves over the batch axes
    (``runtime.sharding.cache_pspecs``); each shard's Pallas grid covers
    only its local ``N / n_shards`` pages — no page crosses a device to be
    decoded.  Same contract as :func:`decode_pages` otherwise; the page
    dim (and so the output's) must divide by the batch-axes size.
    """
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not ba:
        return decode_pages(payload, signmant, tables, perm, n_elem=n_elem,
                            dtype_name=dtype_name, interpret=interpret)
    b_ax = ba if len(ba) != 1 else ba[0]

    # cache the shard_map'ed callable: a fresh closure per call would
    # re-trace (and, eagerly, re-compile) the whole sharded decode every
    # time — the repeat-compile hazard the jit-cache-discipline lint flags
    key = (mesh, b_ax, n_elem, dtype_name, interpret)
    fn = _SHARDED_DECODE_CACHE.get(key)
    if fn is None:
        def body(pay, sm, tab, prm):
            return decode_pages(pay, sm, tab, prm, n_elem=n_elem,
                                dtype_name=dtype_name, interpret=interpret)

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(b_ax, None, None), P(b_ax, None),
                      P(b_ax, None, None), P(b_ax, None)),
            out_specs=P(b_ax, None),
            check_vma=False,
        )
        _SHARDED_DECODE_CACHE[key] = fn
    return fn(payload, signmant, tables, perm)
