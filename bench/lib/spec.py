"""Finds everything by name: the cell in ``BENCHMARK.json``, its
configuration and traffic files, and the per-layer metric readers and
kernel cost files.  No cell, configuration or metric is named in code."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bm: dict, workload: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bm['workloads']]}")


def config(bm: dict, name: str, root: Path = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def module(kind: str, name: str, bench: Path = BENCH):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = bench / kind / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics(bm: dict, workload: str, trace: bool) -> list:
    """The metric entries a run of ``workload`` reports: end-to-end ones
    with ``trace`` off, per-layer ones with it on."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]
