"""Compressed collectives: ECF8-FR weight all-gather (beyond-paper).

The paper compresses weights at rest (HBM).  At 1000+ node scale the same
statistical law (exponent concentration) applies to the *interconnect*: an
FSDP weight all-gather moves the same exponent-redundant bytes every step.
ECF8-FR (fixed-rate, static shapes — ``core.fixedrate``) is losslessly
codable *inside* a jitted collective, unlike Huffman whose output length is
data-dependent.

Pipeline (per shard, inside shard_map):
    fp8 bit view -> encode_jnp (codes 2 b/elem + escapes + signmant 4 b/elem)
    -> all_gather the three byte arrays -> vmapped decode -> concat shards.

Wire bytes per element: 0.25 (codes) + 0.5 (signmant) + 0.5 * esc_frac
vs 1.0 for a raw fp8 gather and 2.0 for bf16 — a 25-40 % collective-term
reduction measured in the §Perf hillclimb (serving weight-streaming path).

Escape capacity is static: chosen offline per tensor from the calibration
histogram with a safety margin; ``overflow`` is returned as a metric and
triggers recalibration (weights drift slowly, so this is rare — DESIGN.md).
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import fixedrate, fp8


def calibrate(w8_bits: np.ndarray, margin: float = 1.25):
    """Offline: pick the top-3 exponent table + escape capacity per tensor."""
    flat = np.asarray(w8_bits, np.uint8).reshape(-1)
    exps = fp8.exponent_field(flat, xp=np)
    freqs = np.bincount(exps, minlength=16)
    table = np.argsort(-freqs, kind="stable")[:3].astype(np.uint8)
    esc = int(flat.size - freqs[table].sum())
    cap = max(1, int(np.ceil(esc * margin)))
    # nibble packing works on even counts
    cap += cap % 2
    return jnp.asarray(table), cap


def _gather_decode(w8_shard_bits, table, axis: str, esc_capacity: int):
    """shard_map body: encode local shard, gather bytes, decode all shards."""
    n_local = w8_shard_bits.size
    flat = w8_shard_bits.reshape(-1)
    codes, escapes, signmant, overflow = fixedrate.encode_jnp(
        flat, table, esc_capacity)
    esc_packed = fp8.pack_nibbles(escapes, xp=jnp)
    sm_packed = fp8.pack_nibbles(signmant, xp=jnp)

    codes_g = jax.lax.all_gather(codes, axis)          # (S, n/4)
    esc_g = jax.lax.all_gather(esc_packed, axis)       # (S, cap/2)
    sm_g = jax.lax.all_gather(sm_packed, axis)         # (S, n/2)

    dec = jax.vmap(lambda c, e, s: fixedrate._decode_jnp_impl(
        c, e, table, s, n_elem=n_local))
    bits = dec(codes_g, esc_g, sm_g)                   # (S, n)
    return bits.reshape(-1), jax.lax.all_gather(overflow, axis).any()


def compressed_all_gather(mesh: Mesh, axis: str = "data"):
    """Build a jitted ``(w8_bits_sharded, table) -> (full bits, overflow)``.

    ``w8_bits`` is the uint8 bit view of an fp8 weight, sharded over ``axis``
    on its leading dim.  The gathered result is bit-exact (tested) — the
    collective just moves ~0.8 bytes/elem instead of 1 (fp8) or 2 (bf16).
    """

    def fn(w8_bits, table, esc_capacity: int):
        n = w8_bits.shape[0]
        body = partial(_gather_decode, axis=axis, esc_capacity=esc_capacity)
        out, overflow = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis, *(None,) * (w8_bits.ndim - 1)), P(None)),
            out_specs=(P(None), P()),
            check_vma=False,
        )(w8_bits, table)
        return out.reshape(n, *w8_bits.shape[1:]), overflow

    return fn


def wire_bytes_per_elem(esc_frac: float) -> float:
    """Analytic wire cost of the compressed gather (bytes/element)."""
    return 0.25 + 0.5 + 0.5 * esc_frac


def raw_wire_bytes_per_elem(dtype: str = "float8") -> float:
    return {"float8": 1.0, "bfloat16": 2.0, "float32": 4.0}[dtype]
