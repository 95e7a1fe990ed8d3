"""Everything is found by name, and the harness code names no cell."""
import os
import re
import subprocess
import sys

# bench/run.py's import paths: the program's src/ and bench/ itself
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import harness, spec

BENCH = spec.BENCH


def test_every_entry_has_its_files():
    bm = spec.load_benchmark()
    for c in bm["configs"]:
        cfg = spec.config(bm, c["name"])
        assert cfg["name"] == c["name"]
        assert (BENCH / "reference" / f"{cfg['reference']}.py").exists()
        harness.arch_config(cfg)        # the program runs what it states
    for w in bm["workloads"]:
        assert spec.cell(bm, w["name"]) is w
        spec.traffic(w["traffic"])
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
