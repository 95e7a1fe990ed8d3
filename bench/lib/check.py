"""How ``correct`` is decided: served tokens against the plain reference,
and the decoded weights against the checkpoint.

After the window closes, a sample of the requests that served tokens,
drawn from the seed and always holding the one with the longest sequence,
goes through the configuration's plain reference (``bench/reference``)
over its prompt and served tokens.  At every served position the reading
is how far the served token's reference logit lies below the reference's
best; the number compared is the widest such gap, against the
configuration's ``check.max_logit_gap``.

The control reads the same gap for the token that the reference computed
in the next lower precision (fp8 operands) puts first, on the same
sequences, and is judged by the same limit: it has to come out not
correct.

The weight decode is lossless by the system's guarantee, so every
compressed weight the engine served is also decoded on the device by the
program's ``store.materialize`` and compared bit for bit with the
checkpoint's fp8 values (:func:`weight_mismatches`, limit 0).
"""
from __future__ import annotations

import numpy as np

from . import weights as W
from .traffic import seed_words


def sample(records, seed: int, n: int) -> list:
    """Up to ``n`` records (each ``(prompt, served tokens)``) with at least
    one served token: the longest sequence, then a seeded draw."""
    pool = [r for r in records if r[1]]
    if not pool:
        return []
    longest = max(range(len(pool)),
                  key=lambda i: len(pool[i][0]) + len(pool[i][1]))
    rest = [i for i in range(len(pool)) if i != longest]
    rng = np.random.default_rng(seed_words(seed) + [0xC4EC])
    pick = [longest] + list(rng.permutation(rest)[: max(n - 1, 0)])
    return [pool[i] for i in pick]


class ReferenceWeights:
    """The checkpoint's fp8 matrices and ``--seed``'s 1-D parameters on
    the device, made by ``lib/weights`` from the configuration's weight
    seed and the run seed, under the names of the reference ``ref``
    (``tensor_specs``, ``param_specs``): nothing the program made.
    ``weights(name)`` is a matrix, ``weights.param(name)`` a 1-D
    parameter as drawn (a gain itself, not less one), and
    ``weights.layer(l)`` both of layer ``l`` by their names after
    ``layer{l}.``."""

    def __init__(self, ref, model: dict, weight_seed: int, seed: int):
        import jax
        host = W.synth(ref.tensor_specs(model), weight_seed)
        self.arrays = {n: jax.device_put(host.pop(n)) for n in list(host)}
        self.params = {n: jax.device_put(W.vector(seed, n, k, kind))
                       for n, k, kind in ref.param_specs(model)}

    def __call__(self, name: str):
        return self.arrays[name]

    def param(self, name: str):
        return self.params[name]

    def layer(self, l: int) -> dict:
        p = f"layer{l}."
        return {n[len(p):]: x for d in (self.arrays, self.params)
                for n, x in d.items() if n.startswith(p)}

    def delete(self):
        for x in [*self.arrays.values(), *self.params.values()]:
            x.delete()
        self.arrays, self.params = {}, {}


def weight_mismatches(params, lay, weights) -> int:
    """Elements of the compressed weights in ``params`` whose decode on
    the device differs in any bit from the checkpoint's fp8 value.
    ``lay`` is ``checkpoint.layout``'s list; a stacked leaf decodes layer
    by layer, as the program's layer scan does."""
    import jax
    import jax.numpy as jnp
    from repro.core.store import is_compressed, materialize
    fp8 = jnp.dtype(W.FP8)

    def one(ct, want):
        got = materialize(ct, dtype=fp8)
        return jnp.sum(jax.lax.bitcast_convert_type(got, jnp.uint8)
                       != jax.lax.bitcast_convert_type(want, jnp.uint8))

    @jax.jit
    def count(ct, want):
        if want.ndim == 3:
            return jnp.sum(jax.lax.map(lambda a: one(*a), (ct, want)))
        return one(ct, want)

    leaves = jax.tree_util.tree_leaves(params, is_leaf=is_compressed)
    bad = 0
    for x, (names, shape, ck, weight) in zip(leaves, lay):
        if not weight:
            continue
        if not is_compressed(x):
            raise ValueError(f"weight {'/'.join(names)} is not compressed")
        want = (jnp.stack([weights(n) for n in ck]) if len(shape) == 3
                else weights(ck[0]))
        bad += int(count(x, want))
    return bad


def gaps(reference, model: dict, weights, samples,
         precisions=("float32",)) -> dict:
    """Per precision, the gaps at every served position.  ``float32``:
    reference best minus the served token's reference logit; any other
    precision (the control): reference best minus the reference logit of
    that precision's first choice, in place of the served token."""
    out = {p: [] for p in precisions}
    for prompt, served in samples:
        toks = list(prompt) + list(served[:-1])
        sel = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        ref = reference.logits(model, weights, toks, sel, "float32")
        best = ref.max(axis=1)
        rows = np.arange(len(served))
        for p in precisions:
            if p == "float32":
                pick = np.asarray(served)
            else:
                pick = reference.logits(model, weights, toks, sel,
                                        p).argmax(axis=1)
            out[p].extend((best - ref[rows, pick]).tolist())
    return out
