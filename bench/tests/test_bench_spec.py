"""Everything is found by name, and the harness code names no cell."""
import os
import re
import subprocess
import sys

import pytest

# bench/run.py's import paths: the program's src/ and bench/ itself
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import flops, harness, spec
from lib.traffic import longest

BENCH = spec.BENCH
DATA = BENCH / "tests" / "data"


def test_every_entry_has_its_files():
    bm = spec.load_benchmark()
    for c in bm["configs"]:
        cfg = spec.config(bm, c["name"])
        assert cfg["name"] == c["name"]
    for w in bm["workloads"]:
        assert spec.cell(bm, w["name"]) is w
        cfg = spec.config(bm, w["config"])
        ref = spec.module("reference", cfg["reference"])
        # the program runs what the file states, for the cell's traffic
        harness.arch_config(cfg, ref, longest(spec.traffic(w["traffic"])))
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(spec.module("metrics", m["name"]).read)
    for k in ("ecf8_decode", "page_decode"):
        mod = spec.module("kernels", k)
        assert mod.PATTERN and callable(mod.cost)


def test_metric_lists_follow_the_cells():
    bm = spec.load_benchmark()
    for w in bm["workloads"]:
        e2e = [m["name"] for m in spec.metrics(bm, w["name"], trace=False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        per = spec.metrics(bm, w["name"], trace=True)
        assert per and all(m["moves"] in e2e for m in per)


@pytest.mark.parametrize("root, bench, name, mlp_matrices", [
    (spec.ROOT, BENCH, "qwen3-8b", 3), (DATA, DATA, "tiny-sqrelu", 2)])
def test_layer_flops_count_the_reference_matrices(root, bench, name,
                                                  mlp_matrices):
    """Two FLOPs for each element of every layer matrix: four of
    attention and the MLP's (SwiGLU three, squared ReLU two)."""
    cfg = spec.config(spec.load_benchmark(root), name, root)
    m = cfg["model"]
    d, hd, ff = m["hidden_size"], m["head_dim"], m["intermediate_size"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    one = 2 * (d * hq * hd * 2 + d * hkv * hd * 2 + mlp_matrices * d * ff)
    ref = spec.module("reference", cfg["reference"], bench)
    assert flops.layer_matmul(ref, m) == m["num_hidden_layers"] * one


def test_harness_code_names_no_cell():
    bm = spec.load_benchmark()
    names = ([w["name"] for w in bm["workloads"]]
             + [c["name"] for c in bm["configs"]]
             + [w["traffic"] for w in bm["workloads"]])
    code = [BENCH / "run.py"] + sorted((BENCH / "lib").glob("*.py"))
    for path in code:
        text = path.read_text()
        for n in names:
            assert not re.search(rf"[\"']{re.escape(n)}[\"']", text), \
                (path, n)


def test_run_exits_nonzero_without_a_tpu():
    bm = spec.load_benchmark()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         bm["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=BENCH.parent, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "cpu" in p.stderr
    assert not p.stdout.strip()
