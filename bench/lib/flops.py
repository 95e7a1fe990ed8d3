"""Model FLOPs the work requires, from a configuration's ``model``
section: matmul weights of the kept layers, attention over the context,
and the unembedding only where logits are needed.  Multiply-adds count
two FLOPs; norms, rotary and softmax are left out."""
from __future__ import annotations

MLP_MATRICES = {"swiglu": 3, "sqrelu": 2}


def layer_matmul(model: dict) -> int:
    """Per-token matmul FLOPs of one layer."""
    d, hd = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    ff = model["intermediate_size"]
    return 2 * (d * hq * hd * 2 + d * hkv * hd * 2
                + MLP_MATRICES[model["mlp"]] * d * ff)


def attention(model: dict, n_keys: int) -> int:
    """One layer's QK^T and PV FLOPs for one query over ``n_keys``."""
    return 4 * model["num_attention_heads"] * model["head_dim"] * n_keys


def unembed(model: dict) -> int:
    return 2 * model["hidden_size"] * model["vocab_size"]


def tokens(model: dict, start: int, n: int, logits: int = 0) -> int:
    """FLOPs of ``n`` consecutive tokens at positions ``start ..
    start + n - 1`` (each attends to every earlier position and itself),
    plus ``logits`` rows of unembedding."""
    L = model["num_hidden_layers"]
    keys = n * start + n * (n + 1) // 2
    return (L * (n * layer_matmul(model) + attention(model, 1) * keys)
            + logits * unembed(model))
