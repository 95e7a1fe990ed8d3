"""Jitted entry points for the Pallas kernels, with backend dispatch.

On TPU the compiled Pallas kernels run natively; on any other backend the
same kernel bodies execute in Pallas interpret mode
(:func:`repro.kernels.interpret_mode` decides, for every entry point).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import tpu_format
from . import ecf8_decode as _dec
from . import fused_decode_matmul as _fused


def decode_tpu_format(container: tpu_format.TpuECF8,
                      interpret: bool | None = None) -> np.ndarray:
    """Decode an ECF8-TPU container with the Pallas kernel -> fp8 bits (N,).

    ``interpret=None`` picks compiled Pallas on TPU, interpret elsewhere.
    """
    C = container.num_chunks
    S = container.sym_per_lane
    out = _dec.decode_pallas(
        jnp.asarray(container.payload),
        jnp.asarray(tpu_format.signmant_rows(container.signmant, C, S)),
        jnp.asarray(container.lj_limit), jnp.asarray(container.first_lj),
        jnp.asarray(container.offset), jnp.asarray(container.perm),
        sym_per_lane=S, interpret=interpret,
    )
    return np.asarray(out).reshape(-1)[: container.n_elem]


def fused_decode_matmul(x, tiled, *, interpret: bool | None = None,
                        out_dtype=jnp.float32):
    """``x @ decode(W)`` with W in tiled ECF8 form (see the kernel)."""
    return _fused.matmul_pallas(x, tiled, out_dtype=out_dtype,
                                interpret=interpret)
