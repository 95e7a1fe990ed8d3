"""Test fixture: a dense decoder LM whose MLP is a squared ReLU,
``relu(x Wi)^2 Wo`` (tensors ``mlp.wi``, ``mlp.wo``), a layer that
``bench/lib`` knows nothing of.  Everything else, attention, RMSNorm and
the unembedding, is ``bench/reference/dense_lm.py``'s."""
import jax
import jax.numpy as jnp

from lib import spec

dense_lm = spec.module("reference", "dense_lm")


def tensor_specs(model: dict) -> list:
    d, ff = model["hidden_size"], model["intermediate_size"]
    specs = [s for s in dense_lm.tensor_specs(model) if ".mlp." not in s[0]]
    for l in range(model["num_hidden_layers"]):
        specs += [(f"layer{l}.mlp.wi", (d, ff)), (f"layer{l}.mlp.wo", (ff, d))]
    return specs


param_specs = dense_lm.param_specs
PROGRAM_FIELDS = dense_lm.PROGRAM_FIELDS
# RMSNorm at 1e-6, the squared ReLU
PROGRAM_FIXED = {"rms_norm_eps": 1e-6, "hidden_act": "relu2"}
PROGRAM_CONTEXT = dense_lm.PROGRAM_CONTEXT
PROGRAM_LAYER = dense_lm.PROGRAM_LAYER


def sqrelu(h, w, r):
    a = jnp.square(jax.nn.relu(h @ w["mlp.wi"].astype(jnp.float32)))
    return r(a) @ w["mlp.wo"].astype(jnp.float32)


def logits(model: dict, weights, tokens, sel, precision: str = "float32"):
    if model["mlp"] != "sqrelu":
        raise ValueError(f"unknown mlp {model['mlp']!r}")
    return dense_lm.forward(model, weights, tokens, sel, precision, sqrelu)
