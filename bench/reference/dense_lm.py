"""Plain reference of a dense decoder-only LM (pre-norm, GQA, RoPE), and
the declaration of its layer: the checkpoint's tensors
(:func:`tensor_specs`, :func:`param_specs`) and what the program has to
run for it (``PROGRAM_FIELDS``, ``PROGRAM_FIXED``, ``PROGRAM_CONTEXT``,
``PROGRAM_LAYER``).
``bench/lib`` reads the layer from here and knows no tensor, norm or MLP
kind of its own.

Written from the model descriptions, not from the program: straight
``jax.numpy`` in float32 at ``highest`` matmul precision, no kernels, no
cache, no batching.  One call runs one sequence through every layer, one
layer at a time, and returns the logits at the positions asked for.

Per layer: ``x += attn(rms_norm(x) * g1)`` then
``x += mlp(rms_norm(x) * g2)``; optional RMSNorm of each query and key
head, times its own gain (Qwen3's QK-norm); rotary embedding over the
whole head, the two halves rotated as pairs; causal softmax attention
with ``1/sqrt(head_dim)`` scaling, ``g = heads / kv_heads`` query heads
per key/value head; MLP ``swiglu`` (``silu(x Wg) * (x Wu) Wo``); a final
RMSNorm times its gain before the (tied or untied) unembedding.
:func:`forward` takes the MLP as an argument, so a reference whose layer
differs only there reuses the rest.

``precision="fp8"`` is the control: every matmul operand that is not a
weight (the weights are fp8 already) is rounded to float8_e4m3fn first.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512          # query rows per attention block; sequences pad to it
VOCAB_BLOCK = 16384    # unembedding columns per block


# -- the layer's declaration ---------------------------------------------------

def tensor_specs(model: dict) -> list:
    """[(name, shape)] of every weight matrix of a dense decoder LM with
    a SwiGLU MLP, in the ``x @ W`` orientation (input rows, output
    columns)."""
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


def param_specs(model: dict) -> list:
    """[(name, length, kind)] of every 1-D parameter: the RMSNorm gains
    (kind ``gain``), per layer ``norm1`` before attention and ``norm2``
    before the MLP, with QK-norm ``attn.q_norm`` and ``attn.k_norm``;
    then ``final_norm``."""
    d, hd = model["hidden_size"], model["head_dim"]
    specs = []
    for l in range(model["num_hidden_layers"]):
        p = f"layer{l}."
        specs += [(p + "norm1", d), (p + "norm2", d)]
        if model["qk_norm"]:
            specs += [(p + "attn.q_norm", hd), (p + "attn.k_norm", hd)]
    return [(n, k, "gain") for n, k in specs + [("final_norm", d)]]


# ``model`` key -> the program's ``ArchConfig`` attribute that must equal it
PROGRAM_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                  "num_attention_heads": "n_heads",
                  "num_key_value_heads": "n_kv_heads", "head_dim": "hd",
                  "num_hidden_layers": "n_layers",
                  "vocab_size": "vocab_size",
                  "tie_word_embeddings": "tie_embeddings",
                  "rope_theta": "rope_theta", "mlp": "mlp_type",
                  "qk_norm": "qk_norm", "activation_dtype": "dtype"}
# ``model`` key -> what the program computes with no option to do
# otherwise: RMSNorm at 1e-6, SiLU in the gate
PROGRAM_FIXED = {"rms_norm_eps": 1e-6, "hidden_act": "silu"}
# ``model`` key of the longest sequence the model takes: the program
# computes rotary angles at every position (no table to outgrow), so
# only the traffic's longest sequence is held to it
PROGRAM_CONTEXT = "max_position_embeddings"
# ``ArchConfig`` attribute -> the value this layer needs: one attention
# layer repeated, no experts, post-norms, softcaps or embedding scale
PROGRAM_LAYER = {"pattern": ("attn",), "n_experts": 0, "post_norms": False,
                 "logit_softcap": 0.0, "attn_softcap": 0.0,
                 "embed_scale": False}


# -- the forward pass ------------------------------------------------------------


def _round(x, precision: str):
    if precision == "fp8":
        x = jnp.clip(x, -448.0, 448.0)
        return x.astype(jnp.float8_e4m3fn).astype(F32)
    return x


def _weight(w8):
    return w8.astype(F32)


def rms_norm(x, eps, gain=None):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if gain is None else y * gain


def rope(x, pos, theta):
    """x: (T, H, hd); the first and second halves of each head rotate
    together."""
    hd = x.shape[-1]
    freqs = jnp.asarray(1.0 / theta ** (np.arange(0, hd, 2) / hd), F32)
    ang = pos.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, precision):
    """Causal attention; q (T, Hq, hd), k/v (T, Hkv, hd), T a multiple of
    Q_BLOCK.  Padded positions come last, so no real query sees them."""
    T, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qb = _round(q, precision).reshape(T // Q_BLOCK, Q_BLOCK, hkv, g, hd)
    k, v = _round(k, precision), _round(v, precision)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = _round(jax.nn.softmax(s, axis=-1), precision)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    o = jax.lax.map(block, (jnp.arange(T // Q_BLOCK), qb))
    return o.reshape(T, hq, hd)


def swiglu(h, w, r):
    """``silu(h Wg) * (h Wu) Wo``; ``r`` rounds a matmul operand."""
    a = jax.nn.silu(h @ _weight(w["mlp.wi_gate"])) * (
        h @ _weight(w["mlp.wi_up"]))
    return r(a) @ _weight(w["mlp.wo"])


@partial(jax.jit, static_argnames=("dims", "mlp", "precision"))
def _layer(x, pos, w, dims, mlp, precision):
    hq, hkv, hd, qk_norm, theta, eps = dims
    T = x.shape[0]
    r = partial(_round, precision=precision)
    h = r(rms_norm(x, eps, w["norm1"]))
    q = (h @ _weight(w["attn.wq"])).reshape(T, hq, hd)
    k = (h @ _weight(w["attn.wk"])).reshape(T, hkv, hd)
    v = (h @ _weight(w["attn.wv"])).reshape(T, hkv, hd)
    if qk_norm:
        q = rms_norm(q, eps, w["attn.q_norm"])
        k = rms_norm(k, eps, w["attn.k_norm"])
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    o = _attention(q, k, v, precision).reshape(T, hq * hd)
    x = x + r(o) @ _weight(w["attn.wo"])
    h = r(rms_norm(x, eps, w["norm2"]))
    return x + mlp(h, w, r)


@partial(jax.jit, static_argnames=("tied", "precision"))
def _logits_block(h, w8, tied, precision):
    if tied:        # embed rows [c0, c1) are the block's vocabulary
        return _round(h, precision) @ _weight(w8).T
    return _round(h, precision) @ _weight(w8)


@jax.jit
def _embed_rows(w8, tokens):
    return _weight(w8)[tokens]


def logits(model: dict, weights, tokens, sel, precision: str = "float32"):
    """Logits (len(sel), vocab) float32 of the sequence ``tokens`` at
    positions ``sel``, with the SwiGLU MLP."""
    if model["mlp"] != "swiglu":
        raise ValueError(f"unknown mlp {model['mlp']!r}")
    return forward(model, weights, tokens, sel, precision, swiglu)


def forward(model: dict, weights, tokens, sel, precision: str, mlp):
    """:func:`logits` with the MLP ``mlp(h, w, r)`` of a layer whose
    tensors and parameters ``w`` holds by their names after
    ``layer{l}.``.  ``weights(name)`` gives the fp8 array on the device of
    every tensor of the reference's ``tensor_specs``,
    ``weights.param(name)`` every parameter of its ``param_specs`` and
    ``weights.layer(l)`` both of layer ``l``."""
    dims = (model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], bool(model["qk_norm"]),
            float(model["rope_theta"]), float(model["rms_norm_eps"]))
    T = len(tokens)
    Tp = -(-T // Q_BLOCK) * Q_BLOCK
    toks = np.zeros(Tp, np.int32)
    toks[:T] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed_rows(weights("embed"), jnp.asarray(toks))
        pos = jnp.arange(Tp)
        for l in range(model["num_hidden_layers"]):
            x = _layer(x, pos, weights.layer(l), dims=dims, mlp=mlp,
                       precision=precision)
        h = rms_norm(x[jnp.asarray(np.asarray(sel, np.int32))],
                     dims[-1], weights.param("final_norm"))
        tied = bool(model["tie_word_embeddings"])
        w8 = weights("embed" if tied else "unembed")
        V = model["vocab_size"]
        out = []
        for c0 in range(0, V, VOCAB_BLOCK):
            c1 = min(c0 + VOCAB_BLOCK, V)
            blk = _logits_block(h, w8[c0:c1] if tied else w8[:, c0:c1],
                                tied=tied, precision=precision)
            out.append(np.asarray(blk))
    return np.concatenate(out, axis=1)
