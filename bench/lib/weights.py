"""The synthetic FP8 checkpoint: names, shapes, values and per-seed gains.

Nothing here imports the program.  Both the checkpoint loader
(``lib/checkpoint.py``) and the plain reference read weights through this
module, so they agree on every value by construction.

* A configuration's ``model`` section fixes the tensors: ``embed``
  (vocab, d), ``unembed`` (d, vocab) when untied, and per layer ``l``
  ``layer{l}.attn.w{q,k,v,o}`` and ``layer{l}.mlp.*`` in the ``x @ W``
  orientation (input rows, output columns).
* Base values: normal with a 1/sqrt(fan-in) scale (1/sqrt(d) for the
  embedding), cast to float8_e4m3fn, drawn from the configuration's
  ``weight_seed`` in row blocks that each have their own generator, so a
  value depends on (seed, name, position) only.
* The norm gains (:func:`gain_specs`: per layer ``layer{l}.norm1``
  before attention, ``layer{l}.norm2`` before the MLP, with QK-norm
  ``layer{l}.attn.q_norm`` and ``layer{l}.attn.k_norm``; then
  ``final_norm``) come from ``--seed`` (:func:`gains`): every channel a
  sign and a magnitude of 0.75, 1 or 1.25.  So the model differs from
  seed to seed while the fp8 matrices, and so the encoded checkpoint,
  stay those of the base checkpoint.  Every gain and every gain less one
  is exact in bfloat16.
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


def tensor_specs(model: dict) -> list:
    """[(name, shape)] of every weight matrix of a dense decoder LM with
    a SwiGLU MLP."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    hd = model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    V = model["vocab_size"]
    specs = [("embed", (V, d))]
    if not model["tie_word_embeddings"]:
        specs.append(("unembed", (d, V)))
    for l in range(model["num_hidden_layers"]):
        p = f"layer{l}."
        specs += [(p + "attn.wq", (d, hq * hd)),
                  (p + "attn.wk", (d, hkv * hd)),
                  (p + "attn.wv", (d, hkv * hd)),
                  (p + "attn.wo", (hq * hd, d))]
        specs += [(p + "mlp.wi_gate", (d, ff)), (p + "mlp.wi_up", (d, ff)),
                  (p + "mlp.wo", (ff, d))]
    return specs


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


def gain_specs(model: dict) -> list:
    """[(name, length)] of every norm gain of a dense decoder LM."""
    d, hd = model["hidden_size"], model["head_dim"]
    specs = []
    for l in range(model["num_hidden_layers"]):
        p = f"layer{l}."
        specs += [(p + "norm1", d), (p + "norm2", d)]
        if model["qk_norm"]:
            specs += [(p + "attn.q_norm", hd), (p + "attn.k_norm", hd)]
    return specs + [("final_norm", d)]


def gains(seed: int, name: str, n: int) -> np.ndarray:
    """(n,) float32 gains of ``name`` under run seed ``seed``."""
    from .traffic import seed_words
    g = np.random.default_rng(seed_words(seed) + [name_crc(name)])
    mag = np.asarray(GAIN_MAGNITUDES, np.float32)[g.integers(0, 3, n)]
    return np.where(g.integers(0, 2, n) == 1, -mag, mag)
