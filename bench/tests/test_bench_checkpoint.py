"""The encoded-checkpoint cache key, the per-seed norm gains and the
bit-for-bit check of the weight decode."""
import json
import os
import shutil
import sys

import numpy as np

# bench/run.py's import paths: the program's src/ and bench/ itself
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import check, checkpoint, harness
from lib import weights as W

DATA = os.path.join(os.path.dirname(__file__), "data")
CONFIG = json.load(open(os.path.join(DATA, "configs", "tiny.json")))


def test_cache_key_follows_the_encoder_sources(tmp_path):
    src = checkpoint._src_root()
    for rel in checkpoint.ENCODER_SOURCES:
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src / rel, tmp_path / rel)
    key = checkpoint.cache_key(CONFIG, tmp_path)
    assert key == checkpoint.cache_key(CONFIG, src)
    for rel in checkpoint.ENCODER_SOURCES:
        p = tmp_path / rel
        text = p.read_text()
        p.write_text(text + "\n# changed\n")
        assert checkpoint.cache_key(CONFIG, tmp_path) != key
        p.write_text(text)
    other = dict(CONFIG, weight_seed=CONFIG["weight_seed"] + 1)
    assert checkpoint.cache_key(other, tmp_path) != key


def _tree(tmp_path, monkeypatch, seed):
    monkeypatch.setattr(checkpoint, "CACHE_DIR", tmp_path)
    cfg = harness.arch_config(CONFIG)
    tree, info = checkpoint.encoded_tree(cfg, CONFIG, seed,
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
    assert n == len(W.gain_specs(CONFIG["model"]))


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
    weights = check.ReferenceWeights(CONFIG["model"], CONFIG["weight_seed"], 5)
    assert check.weight_mismatches(params, lay, weights) == 0
    decode = tpu_format._decode_jnp_impl

    def lossy(*args, **kw):
        bits = decode(*args, **kw)
        return bits.at[0].set(bits[0] ^ jnp.asarray(1, bits.dtype))

    monkeypatch.setattr(tpu_format, "_decode_jnp_impl", lossy)
    n_weights = sum(len(ck) for _, _, ck, w in lay if w)
    assert check.weight_mismatches(params, lay, weights) == n_weights
    weights.delete()
