"""Compressed parameter store — the paper's §3.3 tensor manager, JAX-native.

The paper intercepts PyTorch forward hooks and decompresses each layer into a
single pre-allocated GPU buffer.  The JAX-native equivalent: parameters are a
pytree in which large weights are ``CompressedTensor`` leaves; model code
calls :func:`materialize` at the point of use, *inside* the jitted step.
Under scan-over-layers, XLA's buffer allocator reuses one decode buffer
across layers — the same constant-overhead property, with no host round-trip.

``CompressedTensor`` is a registered pytree, so it passes transparently
through ``jax.jit`` / ``lax.scan`` (stacked layer compression: every child
array carries a leading layer dim and scan slices it per step).
"""
from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import fixedrate, fp8, tpu_format

FORMAT_NONE = "none"
FORMAT_TPU = "tpu"          # ECF8-TPU interleaved Huffman (uniform layout)
FORMAT_FIXEDRATE = "fixedrate"  # ECF8-FR 2-bit + escapes


@dataclass(frozen=True)
class CompressedMeta:
    fmt: str
    shape: tuple
    n_elem: int
    sym_per_lane: int = 0
    esc_capacity: int = 0
    out_dtype: str = "bfloat16"


@jax.tree_util.register_pytree_node_class
@dataclass
class CompressedTensor:
    """A compressed fp8 weight; decodes on use inside the jitted step."""

    arrays: dict  # name -> jnp.ndarray (pytree children)
    meta: CompressedMeta  # static

    def tree_flatten(self):
        names = tuple(sorted(self.arrays))
        return tuple(self.arrays[k] for k in names), (names, self.meta)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, meta = aux
        return cls(arrays=dict(zip(names, children)), meta=meta)

    @property
    def shape(self):  # so shape-inspecting model code keeps working
        return self.meta.shape

    @property
    def ndim(self):
        return len(self.meta.shape)

    def nbytes_compressed(self) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in self.arrays.values())


def is_compressed(x: Any) -> bool:
    return isinstance(x, CompressedTensor)


# the mesh that the weights being traced are replicated over (None: one
# device); set by :func:`weights_replicated_over`
_WEIGHT_MESH = contextvars.ContextVar("weight_mesh", default=None)


@contextlib.contextmanager
def weights_replicated_over(mesh):
    """Trace-time context for a step whose weights are replicated over
    ``mesh``: :func:`materialize` then runs the compiled decode kernel on
    every device under ``jax.shard_map`` (XLA cannot partition a Mosaic
    kernel itself).  ``mesh=None`` is one device."""
    token = _WEIGHT_MESH.set(mesh)
    try:
        yield
    finally:
        _WEIGHT_MESH.reset(token)


def _fp8_operand(w8):
    """An fp8 weight as an opaque operand of its cast and matmul: XLA then
    compiles the consumer the same way whether the bits come from a
    parameter (the FP8 baseline) or from the decoder (ECF8), so the two
    stay bit-identical on a TPU, where fusing the cast differently changes
    the matmul."""
    return jax.lax.optimization_barrier(w8)


def materialize(x, dtype=None):
    """Decode a CompressedTensor to a dense array (identity for arrays)."""
    if not is_compressed(x):
        if dtype is not None and hasattr(x, "astype"):
            if x.dtype == fp8.FP8_DTYPE:
                x = _fp8_operand(x)
            return x.astype(dtype)
        return x
    m = x.meta
    a = x.arrays
    if m.fmt == FORMAT_TPU:     # signmant in tpu_format.signmant_rows layout
        # on a TPU the compiled Pallas kernel decodes (the jnp decoder's
        # per-lane refill is an XLA gather, element by element on the
        # chip); elsewhere the jnp decoder, its bit-identical reference
        from repro.kernels import ecf8_decode, interpret_mode
        args = (a["payload"], a["signmant"], a["lj_limit"], a["first_lj"],
                a["offset"], a["perm"])
        if interpret_mode():
            bits = tpu_format._decode_jnp_impl(
                *args, sym_per_lane=m.sym_per_lane, n_elem=m.n_elem)
        else:
            decode = partial(ecf8_decode.decode_pallas,
                             sym_per_lane=m.sym_per_lane, interpret=False)
            mesh = _WEIGHT_MESH.get()
            if mesh is not None:
                decode = jax.shard_map(decode, mesh=mesh, in_specs=P(),
                                       out_specs=P(), check_vma=False)
            bits = decode(*args).reshape(-1)[: m.n_elem]
    elif m.fmt == FORMAT_FIXEDRATE:
        bits = fixedrate._decode_jnp_impl(
            a["codes"], a["escapes"], a["table"], a["signmant"],
            n_elem=m.n_elem,
        )
    else:
        raise ValueError(f"unknown format {m.fmt}")
    w8 = _fp8_operand(bits.view(fp8.FP8_DTYPE).reshape(m.shape))
    out_dtype = dtype if dtype is not None else m.out_dtype
    return w8.astype(out_dtype)


# --------------------------------------------------------------------------
# encoding (host side, numpy)
# --------------------------------------------------------------------------

def compress_array(w8_bits: np.ndarray, fmt: str = FORMAT_TPU,
                   out_dtype: str = "bfloat16",
                   sym_per_lane: int = tpu_format.DEFAULT_SYM_PER_LANE,
                   ) -> CompressedTensor:
    """Compress one fp8 tensor (uint8 bit view, any shape).

    The container's arrays stay on the host (numpy); ``jax.device_put``
    of the tree moves them to a device in one transfer per array."""
    if fmt == FORMAT_TPU:
        c = tpu_format.encode(w8_bits, sym_per_lane=sym_per_lane)
        arrays = {"payload": c.payload,
                  "signmant": tpu_format.signmant_rows(
                      c.signmant, c.num_chunks, c.sym_per_lane),
                  "lj_limit": c.lj_limit, "first_lj": c.first_lj,
                  "offset": c.offset, "perm": c.perm}
        meta = CompressedMeta(fmt=fmt, shape=tuple(c.shape), n_elem=c.n_elem,
                              sym_per_lane=c.sym_per_lane, out_dtype=out_dtype)
    elif fmt == FORMAT_FIXEDRATE:
        c = fixedrate.encode(w8_bits)
        arrays = {"codes": c.codes, "escapes": c.escapes,
                  "table": c.table, "signmant": c.signmant}
        meta = CompressedMeta(fmt=fmt, shape=tuple(c.shape), n_elem=c.n_elem,
                              esc_capacity=c.esc_capacity, out_dtype=out_dtype)
    else:
        raise ValueError(f"unknown format {fmt}")
    return CompressedTensor(
        arrays={k: np.asarray(v) for k, v in arrays.items()}, meta=meta)


def compress_stacked(w8_bits_stack: np.ndarray, fmt: str = FORMAT_TPU,
                     out_dtype: str = "bfloat16",
                     sym_per_lane: int = tpu_format.DEFAULT_SYM_PER_LANE,
                     ) -> CompressedTensor:
    """Compress a (layers, ...) stacked fp8 tensor layer-by-layer.

    Each child array gains a leading ``layers`` dim; ``lax.scan`` slices it
    so :func:`materialize` inside the scan body sees one layer's container.
    Per-layer codebooks are kept (entropy varies per layer, paper Fig. 1);
    payload strides / escape capacities are padded to the per-stack max so
    the stack is rectangular.
    """
    L = w8_bits_stack.shape[0]
    per_layer = [
        compress_array(np.asarray(w8_bits_stack[i]), fmt=fmt,
                       out_dtype=out_dtype, sym_per_lane=sym_per_lane)
        for i in range(L)
    ]
    # pad the payload (tpu) / escape (fixedrate) dim to the stack's max
    name, axis = ("payload", 1) if fmt == FORMAT_TPU else ("escapes", 0)
    width = max(ct.arrays[name].shape[axis] for ct in per_layer)
    for ct in per_layer:
        a = ct.arrays[name]
        pad = [(0, 0)] * a.ndim
        pad[axis] = (0, width - a.shape[axis])
        ct.arrays[name] = np.pad(a, pad)
    arrays = {k: np.stack([ct.arrays[k] for ct in per_layer])
              for k in per_layer[0].arrays}
    return CompressedTensor(arrays=arrays, meta=per_layer[0].meta)


def weight_leaf(path, shape, min_elems: int, stacked_axes="auto"
                ) -> tuple[bool, int]:
    """The leaf-selection rule shared by :func:`compress_tree`,
    :func:`fp8_cast_tree` and host weight synthesis: ``(is_weight,
    stacked)`` for a parameter leaf at pytree ``path`` with ``shape``.

    ``stacked`` (0/1) says whether the leading dim is a scan (layer) axis:
    ``stacked_axes="auto"`` stacks leaves whose path goes through a scan
    collection ("units" / "layers"), the model's parameter layout.  A
    weight is a matrix (per layer) of at least ``min_elems`` elements;
    everything else (norm scales, biases) stays in its own dtype — the
    paper's policy, which compresses only weight matrices."""
    if stacked_axes == "auto":
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        stacked = int("units" in names or "layers" in names)
    else:
        stacked = int(stacked_axes)
    n = int(np.prod(shape)) if len(shape) else 1
    per_layer = n // shape[0] if (stacked and len(shape)) else n
    return per_layer >= min_elems and len(shape) >= 2 + stacked, stacked


def _fp8_bits(x) -> np.ndarray:
    """uint8 bit view of ``x`` cast to fp8: on the host for host arrays,
    where the array lives otherwise (the cast :func:`fp8_cast_tree`
    makes)."""
    if isinstance(x, np.ndarray):
        return x.astype(fp8.FP8_DTYPE).view(np.uint8)
    return np.asarray(jnp.asarray(x).astype(fp8.FP8_DTYPE)).view(np.uint8)


def compress_tree(params, fmt: str = FORMAT_TPU, min_elems: int = 65536,
                  out_dtype: str = "bfloat16", stacked_axes="auto",
                  predicate: Callable[[Any], bool] | None = None):
    """Cast a parameter pytree to fp8 and compress the large leaves.

    Leaves are chosen by :func:`weight_leaf`; the compressed containers
    hold host (numpy) arrays.  Returns (compressed_tree, report dict).
    """
    report = {"raw_bytes": 0, "fp8_bytes": 0, "compressed_bytes": 0,
              "n_compressed": 0, "n_kept": 0}

    def visit(path, x):
        if not hasattr(x, "shape") or (predicate and not predicate(x)):
            report["n_kept"] += 1
            return x
        n = int(np.prod(x.shape)) if x.ndim else 1
        report["raw_bytes"] += n * x.dtype.itemsize
        is_weight, stacked = weight_leaf(path, x.shape, min_elems,
                                         stacked_axes)
        if not is_weight:
            report["n_kept"] += 1
            return x
        w8 = _fp8_bits(x)
        report["fp8_bytes"] += n
        if stacked:
            ct = compress_stacked(w8, fmt=fmt, out_dtype=out_dtype)
        else:
            ct = compress_array(w8, fmt=fmt, out_dtype=out_dtype)
        report["compressed_bytes"] += ct.nbytes_compressed()
        report["n_compressed"] += 1
        return ct

    tree = jax.tree_util.tree_map_with_path(visit, params)
    return tree, report


def fp8_cast_tree(params, min_elems: int = 65536, stacked_axes="auto"):
    """The FP8 *baseline*: cast large weights to fp8, keep the rest.

    This is what ECF8 is compared against (the paper compresses released FP8
    checkpoints; the fp8 cast itself defines the baseline bits).  The leaf
    selection rule is :func:`compress_tree`'s (:func:`weight_leaf`), so the
    two trees are bit-comparable."""
    def visit(path, x):
        if not hasattr(x, "shape"):
            return x
        if not weight_leaf(path, x.shape, min_elems, stacked_axes)[0]:
            return x
        return jnp.asarray(x).astype(fp8.FP8_DTYPE)
    return jax.tree_util.tree_map_with_path(visit, params)
