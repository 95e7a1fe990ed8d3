"""The encoded-checkpoint cache key, the per-seed 1-D parameters, the
reference's declaration of its layer and the bit-for-bit check of the
weight decode."""
import json
import os
import shutil
import sys
from types import SimpleNamespace

import numpy as np
import pytest

# bench/run.py's import paths: the program's src/ and bench/ itself
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import check, checkpoint, harness, spec
from lib import weights as W
from lib.traffic import longest

DATA = os.path.join(os.path.dirname(__file__), "data")
CONFIG = json.load(open(os.path.join(DATA, "configs", "tiny.json")))
REF = spec.module("reference", CONFIG["reference"])
MAX_LEN = longest(json.load(open(os.path.join(DATA, "traffic", "tiny.json"))))


def test_cache_key_follows_the_encoder_sources(tmp_path):
    src = checkpoint._src_root()
    for rel in checkpoint.ENCODER_SOURCES:
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src / rel, tmp_path / rel)
    key = checkpoint.cache_key(CONFIG, REF, tmp_path)
    assert key == checkpoint.cache_key(CONFIG, REF, src)
    for rel in checkpoint.ENCODER_SOURCES:
        p = tmp_path / rel
        text = p.read_text()
        p.write_text(text + "\n# changed\n")
        assert checkpoint.cache_key(CONFIG, REF, tmp_path) != key
        p.write_text(text)
    other = dict(CONFIG, weight_seed=CONFIG["weight_seed"] + 1)
    assert checkpoint.cache_key(other, REF, tmp_path) != key


def test_cache_key_follows_the_reference_files(tmp_path, monkeypatch):
    """A fixture's reference takes part of its layer from
    ``bench/reference/dense_lm.py``: a change there re-encodes too."""
    config = json.load(open(os.path.join(DATA, "configs", "tiny-sqrelu.json")))
    ref = spec.module("reference", config["reference"], spec.BENCH / "tests"
                      / "data")
    key = checkpoint.cache_key(config, ref)
    shutil.copytree(spec.BENCH / "reference", tmp_path / "reference")
    monkeypatch.setattr(spec, "BENCH", tmp_path)
    assert checkpoint.cache_key(config, ref) == key
    dense = tmp_path / "reference" / "dense_lm.py"
    dense.write_text(dense.read_text() + "\n# changed\n")
    assert checkpoint.cache_key(config, ref) != key


def _tree(tmp_path, monkeypatch, seed):
    monkeypatch.setattr(checkpoint, "CACHE_DIR", tmp_path)
    cfg = harness.arch_config(CONFIG, REF, MAX_LEN)
    tree, info = checkpoint.encoded_tree(cfg, REF, CONFIG, seed,
                                         log=lambda m: None)
    return cfg, tree, info


def test_gains_follow_the_seed_and_the_cache_does_not(tmp_path,
                                                      monkeypatch):
    import jax
    from repro.core.store import is_compressed
    seed = 2 ** 33 + 7
    cfg, tree, info = _tree(tmp_path, monkeypatch, seed)
    assert info["cache"] == "miss"
    _, again, info = _tree(tmp_path, monkeypatch, seed + 1)
    assert info["cache"] == "hit"
    lay, _ = checkpoint.layout(cfg)
    leaves = jax.tree_util.tree_leaves(tree, is_leaf=is_compressed)
    others = jax.tree_util.tree_leaves(again, is_leaf=is_compressed)
    n = 0
    for x, y, (names, shape, ck, weight) in zip(leaves, others, lay):
        if weight:
            assert is_compressed(x)
            continue
        want = np.stack([W.gains(seed, c, shape[-1]) for c in ck])
        np.testing.assert_array_equal(np.asarray(x).reshape(want.shape) + 1,
                                      want)
        assert set(np.abs(want).ravel()) <= set(W.GAIN_MAGNITUDES)
        assert (want < 0).any() and (want > 0).any()
        assert not np.array_equal(x, y)
        n += len(ck)
    assert n == len(REF.param_specs(CONFIG["model"]))


def test_weight_decode_is_checked_bit_for_bit(tmp_path, monkeypatch):
    """The program's decode of the cached containers equals the
    checkpoint's fp8 values in every bit; a decoder that errs in one
    element's last mantissa bit is caught."""
    import jax
    import jax.numpy as jnp
    from repro.core import tpu_format
    cfg, tree, _ = _tree(tmp_path, monkeypatch, 5)
    params = jax.device_put(tree)
    lay, _ = checkpoint.layout(cfg)
    weights = check.ReferenceWeights(REF, CONFIG["model"],
                                     CONFIG["weight_seed"], 5)
    assert check.weight_mismatches(params, lay, weights) == 0
    decode = tpu_format._decode_jnp_impl

    def lossy(*args, **kw):
        bits = decode(*args, **kw)
        return bits.at[0].set(bits[0] ^ jnp.asarray(1, bits.dtype))

    monkeypatch.setattr(tpu_format, "_decode_jnp_impl", lossy)
    n_weights = sum(len(ck) for _, _, ck, w in lay if w)
    assert check.weight_mismatches(params, lay, weights) == n_weights
    weights.delete()


def test_params_take_the_program_form_of_their_kind():
    """A gain goes to the program less one, a bias as drawn: by the kind
    the reference declares, not by the leaf's shape."""
    model = CONFIG["model"]
    # the same layer with its ``norm2`` declared a bias
    ref = SimpleNamespace(
        tensor_specs=REF.tensor_specs,
        param_specs=lambda m: [(n, k, "bias" if n.endswith("norm2") else
                                kind) for n, k, kind in REF.param_specs(m)])
    cfg = harness.arch_config(CONFIG, REF, MAX_LEN)
    lay, _ = checkpoint.layout(cfg)
    seed = 2 ** 33 + 9
    got = checkpoint.seeded_params(cfg, ref, model, seed)
    kinds = set()
    for i, (names, shape, ck, weight) in enumerate(lay):
        if weight:
            continue
        for j, n in enumerate(ck):
            row = got[i].reshape(len(ck), -1)[j]
            if n.endswith("norm2"):
                want = W.biases(seed, n, shape[-1])
                assert set(want) <= set(W.BIAS_VALUES) and len(set(want)) == 3
                kinds.add("bias")
            else:
                want = W.gains(seed, n, shape[-1]) - 1
                kinds.add("gain")
            np.testing.assert_array_equal(row, want)
    assert kinds == {"gain", "bias"}
    # every bias is exact in bfloat16
    import ml_dtypes
    b = W.biases(seed, "x", 64)
    np.testing.assert_array_equal(b.astype(ml_dtypes.bfloat16).astype(
        np.float32), b)
    with pytest.raises(ValueError, match="kind"):
        W.vector(seed, "x", 4, "scale")


def test_a_model_key_the_reference_does_not_cover_is_an_error():
    extra = dict(CONFIG, model=dict(CONFIG["model"], rope_scaling=2.0))
    with pytest.raises(ValueError, match="rope_scaling"):
        harness.arch_config(extra, REF, MAX_LEN)
    # a covered key that the program does not run as stated
    wrong = dict(CONFIG, model=dict(CONFIG["model"], rms_norm_eps=1e-5))
    with pytest.raises(ValueError, match="rms_norm_eps"):
        harness.arch_config(wrong, REF, MAX_LEN)


def test_the_traffic_must_fit_the_model_context():
    """The context key is held to the traffic's longest sequence, not to
    one model's published number."""
    for n, ok in ((MAX_LEN, True), (MAX_LEN - 1, False), (10 ** 6, True)):
        c = dict(CONFIG, model=dict(CONFIG["model"],
                                    max_position_embeddings=n))
        if ok:
            harness.arch_config(c, REF, MAX_LEN)
        else:
            with pytest.raises(ValueError, match="max_position_embeddings"):
                harness.arch_config(c, REF, MAX_LEN)
