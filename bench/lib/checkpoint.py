"""The encoded checkpoint the engine serves, and its on-disk cache.

ECF8 is compressed offline, so a deployment loads a checkpoint that is
already encoded.  The harness keeps that checkpoint in
``bench/.ckpt/<key>/`` (git-ignored): written on a configuration's first
run in a checkout, loaded by every later run.  The key covers the
configuration's model and program sections, its ``weight_seed`` and the
bytes of the encoder's source files and of this module, so a change to
the format re-encodes and is never served a stale container.

The cache holds the fp8 matrices only.  On every run the loader adds
``--seed``'s norm gains (``lib/weights.py``) on the host, in the form the
program's norms take (``1 + scale``), and puts the tree on the device:
the containers are never touched, so no run re-encodes.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from pathlib import Path

import numpy as np

from . import weights as W

CACHE_DIR = Path(__file__).resolve().parents[1] / ".ckpt"
# the program files whose change changes the encoded bytes
ENCODER_SOURCES = ("core/tpu_format.py", "core/huffman.py", "core/store.py",
                   "core/fp8.py")
# leaves with fewer elements per layer stay uncompressed
MIN_ELEMS = 4096


def _src_root() -> Path:
    from repro.core import store
    return Path(store.__file__).resolve().parents[1]


def cache_key(config: dict, src_root: Path | None = None) -> str:
    src_root = _src_root() if src_root is None else src_root
    h = hashlib.sha256()
    h.update(json.dumps({k: config[k] for k in ("model", "program",
                                                "weight_seed")},
                        sort_keys=True).encode())
    h.update(str(MIN_ELEMS).encode())
    for rel in ENCODER_SOURCES:
        h.update(rel.encode())
        h.update((src_root / rel).read_bytes())
    h.update(Path(__file__).read_bytes())
    h.update(Path(W.__file__).read_bytes())
    return f"{config['name']}-{h.hexdigest()[:16]}"


# -- the program's parameter layout -----------------------------------------

def _path_names(path) -> list:
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


def layout(cfg):
    """[(path names, shape, per-layer ckpt names, is weight)] for every
    leaf of the program's parameter tree, plus its treedef.  A leaf maps
    to one checkpoint name per layer it stacks: a weight matrix of
    ``weights.tensor_specs`` or a norm gain of ``weights.gain_specs``."""
    import jax
    from repro.models import model as M
    shapes = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    n_units = cfg.n_layers // cfg.unit
    for path, sds in flat:
        names = _path_names(path)
        if names[0] == "units":
            j = int(names[1][3:])           # "pos{j}"
            sub = ".".join(names[2:])
            per = sds.shape[1:]
            ck = [f"layer{u * cfg.unit + j}.{sub}" for u in range(n_units)]
        elif names[0] == "tail":
            t = int(names[1][5:])           # "layer{t}"
            per = sds.shape
            ck = [f"layer{n_units * cfg.unit + t}.{'.'.join(names[2:])}"]
        else:
            per = sds.shape
            ck = [names[0]]
        out.append((names, tuple(sds.shape), ck, len(per) == 2))
    return out, treedef


def _check_names(lay, model: dict) -> None:
    for weight, want in ((True, W.tensor_specs(model)),
                         (False, W.gain_specs(model))):
        used = {n for _, _, ck, w in lay if w == weight for n in ck}
        if used != {n for n, _ in want}:
            raise ValueError(f"checkpoint tensors and program leaves "
                             f"differ: {sorted(used ^ {n for n, _ in want})}")


def fp8_leaves(cfg, model: dict, weight_seed: int):
    """The base fp8 matrices in the program's layout, one host leaf at a
    time: yields (leaf index, path names, array)."""
    specs = dict(W.tensor_specs(model))
    lay, _ = layout(cfg)
    _check_names(lay, model)
    for i, (names, shape, ck, weight) in enumerate(lay):
        if not weight:
            continue
        arr = np.empty(shape, W.fp8_dtype())
        views = {}
        for j, name in enumerate(ck):
            if specs[name] != arr.shape[-2:]:
                raise ValueError(f"program leaf {'/'.join(names)} has shape "
                                 f"{arr.shape[-2:]}, checkpoint tensor "
                                 f"{name} {specs[name]}")
            views[name] = arr[j] if arr.ndim == 3 else arr
        W.synth([(n, specs[n]) for n in ck], weight_seed, out=views)
        yield i, names, arr


def seeded_gains(cfg, seed: int) -> dict:
    """{leaf index: the program's norm scale} for ``seed``: the gains of
    ``lib/weights.gains`` less one, stacked as the program stacks them."""
    lay, _ = layout(cfg)
    out = {}
    for i, (names, shape, ck, weight) in enumerate(lay):
        if not weight:
            g = np.stack([W.gains(seed, n, shape[-1]) for n in ck])
            out[i] = (g - 1).reshape(shape)
    return out


# -- the cache ----------------------------------------------------------------

def _write(cfg, config: dict, path: Path, log) -> dict:
    """Synthesize and encode leaf by leaf (the program's offline
    compressor, ``core/store.compress_{array,stacked}``) into ``path``:
    one ``.npy`` per array and ``meta.json``.  One leaf's temporaries at a
    time, so a miss holds no whole-tree copies on the host."""
    from repro.core.store import compress_array, compress_stacked
    tmp = path.with_name(path.name + ".tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    meta, synth_s, encode_s = {}, 0.0, 0.0
    t0 = time.perf_counter()
    for i, names, arr in fp8_leaves(cfg, config["model"],
                                    config["weight_seed"]):
        t1 = time.perf_counter()
        synth_s += t1 - t0
        if (arr[0].size if arr.ndim == 3 else arr.size) < MIN_ELEMS:
            raise ValueError(f"weight {'/'.join(names)} is below "
                             f"{MIN_ELEMS} elements a layer and would "
                             f"stay uncompressed")
        enc = compress_stacked if arr.ndim == 3 else compress_array
        ct = enc(arr.view(np.uint8), fmt="tpu", out_dtype=cfg.dtype)
        for k, a in ct.arrays.items():
            np.save(tmp / f"{i}.{k}.npy", a)
        m = ct.meta
        meta[i] = {"ct": {"fmt": m.fmt, "shape": list(m.shape),
                          "n_elem": m.n_elem, "sym_per_lane": m.sym_per_lane,
                          "esc_capacity": m.esc_capacity,
                          "out_dtype": m.out_dtype},
                   "arrays": sorted(ct.arrays)}
        del arr
        t0 = time.perf_counter()
        encode_s += t0 - t1
    (tmp / "meta.json").write_text(json.dumps(meta))
    os.replace(tmp, path)
    return {"synth_s": synth_s, "encode_s": encode_s}


def _load(path: Path, treedef, gains: dict):
    import jax
    from repro.core.store import CompressedMeta, CompressedTensor
    meta = json.loads((path / "meta.json").read_text())
    leaves = []
    for i in range(treedef.num_leaves):
        if i in gains:
            leaves.append(gains[i])
            continue
        m = meta[str(i)]
        ct = dict(m["ct"], shape=tuple(m["ct"]["shape"]))
        leaves.append(CompressedTensor(
            arrays={k: np.load(path / f"{i}.{k}.npy") for k in m["arrays"]},
            meta=CompressedMeta(**ct)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def encoded_tree(cfg, config: dict, seed: int, log=print):
    """The ECF8 tree with ``seed``'s norm gains (host arrays): the
    matrices from the cache, or synthesized and encoded into it first.
    Returns (tree, info) where info records what set-up did."""
    import jax
    from repro.core.store import is_compressed
    key = cache_key(config)
    path = CACHE_DIR / key
    _, treedef = layout(cfg)
    info = {"cache": "hit", "synth_s": 0.0, "encode_s": 0.0}
    if not path.exists():
        info["cache"] = "miss"
        CACHE_DIR.mkdir(parents=True, exist_ok=True)
        for old in CACHE_DIR.glob(f"{config['name']}-*"):
            shutil.rmtree(old, ignore_errors=True)   # a stale format
        info.update(_write(cfg, config, path, log))
        log(f"checkpoint: encoded {key} (synthesis {info['synth_s']:.2f} s, "
            f"encode {info['encode_s']:.2f} s)")
    t0 = time.perf_counter()
    tree = _load(path, treedef, seeded_gains(cfg, seed))
    info["load_s"] = time.perf_counter() - t0
    n_ct = sum(is_compressed(x) for x in jax.tree_util.tree_leaves(
        tree, is_leaf=is_compressed))
    log(f"checkpoint: {key} {info['cache']}, {n_ct} compressed leaves, "
        f"load {info['load_s']:.2f} s")
    return tree, info
