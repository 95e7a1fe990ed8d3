"""The synthetic FP8 checkpoint: values of matrices and per-seed 1-D
parameters.

Nothing here imports the program, and nothing here names a tensor: the
configuration's reference (``bench/reference/<reference>.py``) lists
them, ``tensor_specs(model)`` the fp8 matrices and ``param_specs(model)``
the 1-D parameters with their kind.  Both the checkpoint loader
(``lib/checkpoint.py``) and the plain reference read weights through this
module, so they agree on every value by construction.

* Matrices (:func:`synth`): normal with a 1/sqrt(fan-in) scale
  (1/sqrt(d) for the embedding), cast to float8_e4m3fn, drawn from the
  configuration's ``weight_seed`` in row blocks that each have their own
  generator, so a value depends on (seed, name, position) only.
* 1-D parameters (:func:`vector`) come from ``--seed``: a ``gain``
  (:func:`gains`) is per channel a sign and a magnitude of 0.75, 1 or
  1.25, a ``bias`` (:func:`biases`) one of -0.25, 0 and 0.25.  So the
  model differs from seed to seed while the fp8 matrices, and so the
  encoded checkpoint, stay those of the base checkpoint.  Every gain,
  every gain less one and every bias is exact in bfloat16.
"""
from __future__ import annotations

import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

FP8 = "float8_e4m3fn"
# elements per synthesis block (one generator each)
BLOCK_ELEMS = 1 << 22
_THREADS = 8


def fp8_dtype():
    import ml_dtypes
    return np.dtype(getattr(ml_dtypes, FP8))


def name_crc(name: str) -> int:
    return zlib.crc32(name.encode())


def _std(name: str, shape) -> float:
    return (shape[1] if name == "embed" else shape[0]) ** -0.5


def _fill_block(out2d, r0, r1, std, seed_words):
    g = np.random.default_rng(seed_words)
    x = g.standard_normal((r1 - r0, out2d.shape[1]), dtype=np.float32)
    x *= np.float32(std)
    out2d[r0:r1] = x.astype(out2d.dtype)


def synth(specs, weight_seed: int, out=None) -> dict:
    """Base fp8 values of ``specs`` [(name, shape)] -> {name: array}.

    ``out`` maps a name to a preallocated fp8 array to fill in place (the
    loader passes views into its stacked leaves)."""
    out = {} if out is None else out
    jobs = []
    for name, shape in specs:
        arr = out.setdefault(name, np.empty(shape, fp8_dtype()))
        rows = max(1, BLOCK_ELEMS // shape[1])
        std = _std(name, shape)
        crc = name_crc(name)
        jobs += [(arr, r0, min(r0 + rows, shape[0]), std,
                  [weight_seed, crc, b])
                 for b, r0 in enumerate(range(0, shape[0], rows))]
    with ThreadPoolExecutor(_THREADS) as ex:
        list(ex.map(lambda j: _fill_block(*j), jobs))
    return out


GAIN_MAGNITUDES = (0.75, 1.0, 1.25)


def gains(seed: int, name: str, n: int) -> np.ndarray:
    """(n,) float32 gains of ``name`` under run seed ``seed``."""
    from .traffic import seed_words
    g = np.random.default_rng(seed_words(seed) + [name_crc(name)])
    mag = np.asarray(GAIN_MAGNITUDES, np.float32)[g.integers(0, 3, n)]
    return np.where(g.integers(0, 2, n) == 1, -mag, mag)


BIAS_VALUES = (-0.25, 0.0, 0.25)


def biases(seed: int, name: str, n: int) -> np.ndarray:
    """(n,) float32 biases of ``name`` under run seed ``seed``."""
    from .traffic import seed_words
    g = np.random.default_rng(seed_words(seed) + [name_crc(name)])
    return np.asarray(BIAS_VALUES, np.float32)[g.integers(0, 3, n)]


# kind of 1-D parameter -> (how it is drawn, the form the program stores
# it in): a norm's gain as its scale less one (the program's norms
# multiply by ``1 + scale``), a bias as drawn
KINDS = {"gain": (gains, lambda g: g - 1), "bias": (biases, lambda b: b)}


def _kind(name: str, kind: str) -> tuple:
    if kind not in KINDS:
        raise ValueError(f"parameter {name}: unknown kind {kind!r}, "
                         f"known: {sorted(KINDS)}")
    return KINDS[kind]


def vector(seed: int, name: str, n: int, kind: str) -> np.ndarray:
    """(n,) float32 values of the 1-D parameter ``name`` of ``kind``
    (a key of ``KINDS``) under run seed ``seed``."""
    return _kind(name, kind)[0](seed, name, n)


def program_form(seed: int, name: str, n: int, kind: str) -> np.ndarray:
    """:func:`vector` in the form the program stores a ``kind``."""
    return _kind(name, kind)[1](vector(seed, name, n, kind))
