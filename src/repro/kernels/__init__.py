"""Pallas TPU kernels of the ECF8 decode family, with jnp oracles
(``ref.py``) and backend-dispatching entry points (``ops.py``)."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run in interpret mode: compiled (Mosaic) on a
    TPU backend, interpreted on every other backend.

    The one place every kernel entry point resolves ``interpret=None``."""
    return jax.default_backend() != "tpu"
