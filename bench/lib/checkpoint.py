"""The encoded checkpoint the engine serves, and its on-disk cache.

ECF8 is compressed offline, so a deployment loads a checkpoint that is
already encoded.  The harness keeps that checkpoint in
``bench/.ckpt/<key>/`` (git-ignored): written on a configuration's first
run in a checkout, loaded by every later run.  The key covers the
configuration's model and program sections, its ``weight_seed`` and the
bytes of the encoder's source files, of this module, of
``lib/weights.py`` and of the references that name the tensors
(:func:`reference_files`), so a change to the format re-encodes and is
never served a stale container.

The cache holds the fp8 matrices only.  On every run the loader adds
``--seed``'s 1-D parameters on the host, in the form the program stores
their kind in (``lib/weights.program_form``), and puts the tree on the
device: the containers are never
touched, so no run re-encodes.

``ref`` below is the configuration's reference module: its
``tensor_specs`` and ``param_specs`` name every tensor.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from pathlib import Path

import numpy as np

from . import spec
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


def cache_key(config: dict, ref, src_root: Path | None = None) -> str:
    src_root = _src_root() if src_root is None else src_root
    h = hashlib.sha256()
    h.update(json.dumps({k: config[k] for k in ("model", "program",
                                                "weight_seed")},
                        sort_keys=True).encode())
    h.update(str(MIN_ELEMS).encode())
    for rel in ENCODER_SOURCES:
        h.update(rel.encode())
        h.update((src_root / rel).read_bytes())
    for path in (__file__, W.__file__, *reference_files(ref)):
        h.update(Path(path).read_bytes())
    return f"{config['name']}-{h.hexdigest()[:16]}"


def reference_files(ref) -> list:
    """Every file a reference's declaration can come from: those beside
    ``ref`` and those of ``bench/reference/``, which a reference elsewhere
    (a test fixture's) may load parts of through ``spec.module``."""
    dirs = [Path(ref.__file__).resolve().parent]
    if (spec.BENCH / "reference").resolve() != dirs[0]:
        dirs.append((spec.BENCH / "reference").resolve())
    return [p for d in dirs for p in sorted(d.glob("*.py"))]


# -- the program's parameter layout -----------------------------------------

def _path_names(path) -> list:
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


def layout(cfg):
    """[(path names, shape, per-layer ckpt names, is weight)] for every
    leaf of the program's parameter tree, plus its treedef.  A leaf maps
    to one checkpoint name per layer it stacks: a weight matrix of the
    reference's ``tensor_specs`` where the leaf is 2-D a layer, else a 1-D
    parameter of its ``param_specs``."""
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


def _check_names(lay, ref, model: dict) -> None:
    for weight, want in ((True, ref.tensor_specs(model)),
                         (False, ref.param_specs(model))):
        used = {n for _, _, ck, w in lay if w == weight for n in ck}
        names = {s[0] for s in want}
        if used != names:
            raise ValueError(f"checkpoint tensors and program leaves "
                             f"differ: {sorted(used ^ names)}")


def fp8_leaves(cfg, ref, model: dict, weight_seed: int):
    """The base fp8 matrices in the program's layout, one host leaf at a
    time: yields (leaf index, path names, array)."""
    specs = dict(ref.tensor_specs(model))
    lay, _ = layout(cfg)
    _check_names(lay, ref, model)
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


def seeded_params(cfg, ref, model: dict, seed: int) -> dict:
    """{leaf index: the program's 1-D parameter} for ``seed``: the values
    of ``lib/weights.program_form``, stacked as the program stacks
    them."""
    specs = {n: (k, kind) for n, k, kind in ref.param_specs(model)}
    lay, _ = layout(cfg)
    _check_names(lay, ref, model)
    out = {}
    for i, (names, shape, ck, weight) in enumerate(lay):
        if weight:
            continue
        rows = []
        for n in ck:
            k, kind = specs[n]
            if k != shape[-1]:
                raise ValueError(f"program leaf {'/'.join(names)} has "
                                 f"length {shape[-1]}, parameter {n} {k}")
            rows.append(W.program_form(seed, n, k, kind))
        out[i] = np.stack(rows).reshape(shape)
    return out


# -- the cache ----------------------------------------------------------------

def _write(cfg, ref, config: dict, path: Path, log) -> dict:
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
    for i, names, arr in fp8_leaves(cfg, ref, config["model"],
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


def _load(path: Path, treedef, params: dict):
    import jax
    from repro.core.store import CompressedMeta, CompressedTensor
    meta = json.loads((path / "meta.json").read_text())
    leaves = []
    for i in range(treedef.num_leaves):
        if i in params:
            leaves.append(params[i])
            continue
        m = meta[str(i)]
        ct = dict(m["ct"], shape=tuple(m["ct"]["shape"]))
        leaves.append(CompressedTensor(
            arrays={k: np.load(path / f"{i}.{k}.npy") for k in m["arrays"]},
            meta=CompressedMeta(**ct)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def encoded_tree(cfg, ref, config: dict, seed: int, log=print):
    """The ECF8 tree with ``seed``'s 1-D parameters (host arrays): the
    matrices from the cache, or synthesized and encoded into it first.
    Returns (tree, info) where info records what set-up did."""
    import jax
    from repro.core.store import is_compressed
    key = cache_key(config, ref)
    path = CACHE_DIR / key
    _, treedef = layout(cfg)
    info = {"cache": "hit", "synth_s": 0.0, "encode_s": 0.0}
    if not path.exists():
        info["cache"] = "miss"
        CACHE_DIR.mkdir(parents=True, exist_ok=True)
        for old in CACHE_DIR.glob(f"{config['name']}-*"):
            shutil.rmtree(old, ignore_errors=True)   # a stale format
        info.update(_write(cfg, ref, config, path, log))
        log(f"checkpoint: encoded {key} (synthesis {info['synth_s']:.2f} s, "
            f"encode {info['encode_s']:.2f} s)")
    t0 = time.perf_counter()
    tree = _load(path, treedef,
                 seeded_params(cfg, ref, config["model"], seed))
    info["load_s"] = time.perf_counter() - t0
    n_ct = sum(is_compressed(x) for x in jax.tree_util.tree_leaves(
        tree, is_leaf=is_compressed))
    log(f"checkpoint: {key} {info['cache']}, {n_ct} compressed leaves, "
        f"load {info['load_s']:.2f} s")
    return tree, info
