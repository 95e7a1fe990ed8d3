"""Model FLOPs the work requires, from a configuration's ``model``
section and its reference's ``tensor_specs``: matmul weights of the kept
layers, attention over the context, and the unembedding only where
logits are needed.  Multiply-adds count two FLOPs; norms, rotary and
softmax are left out."""
from __future__ import annotations

import math


def layer_matmul(ref, model: dict) -> int:
    """Per-token matmul FLOPs of the kept layers: two for each element of
    every layer's matrices in the reference's ``tensor_specs``."""
    return 2 * sum(math.prod(shape) for name, shape in ref.tensor_specs(model)
                   if name.startswith("layer"))


def attention(model: dict, n_keys: int) -> int:
    """One layer's QK^T and PV FLOPs for one query over ``n_keys``."""
    return 4 * model["num_attention_heads"] * model["head_dim"] * n_keys


def unembed(model: dict) -> int:
    return 2 * model["hidden_size"] * model["vocab_size"]


def tokens(model: dict, matmul: int, start: int, n: int,
           logits: int = 0) -> int:
    """FLOPs of ``n`` consecutive tokens at positions ``start ..
    start + n - 1`` (each attends to every earlier position and itself),
    plus ``logits`` rows of unembedding; ``matmul`` is
    :func:`layer_matmul`."""
    L = model["num_hidden_layers"]
    keys = n * start + n * (n + 1) // 2
    return (n * matmul + L * attention(model, 1) * keys
            + logits * unembed(model))
